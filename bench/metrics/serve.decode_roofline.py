"""The decode program's share of its roofline: the bytes every decode step
must read (all weights but the embedding table, and the K/V of the
positions it attends to; flops.decode_request_bytes) over the HBM peak,
against the device time of the decode program. Memory bounds this step:
its operations over the peak take under a hundredth of its bytes' time.

``Server._decode`` jits the model's ``decode_step``, which the dense
model builds as a ``lambda``; no other ``lambda`` program runs while
serving."""

PROGRAMS = ("_lambda_", "_lambda")


def read(run):
    s = run.trace.device_seconds(PROGRAMS)
    if s <= 0:
        return None
    need = sum(run.flops.decode_request_bytes(run.ref, run.conf, p, n)
               for p, n in run.counts["requests"])
    return 100.0 * need / run.peaks["hbm_bytes_per_s"] / s
