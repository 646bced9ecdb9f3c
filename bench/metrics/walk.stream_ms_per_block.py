"""Device time of the walk's stream programs per block tuned: the teacher
and student advances (core/pruning/common.py ``adv_scan_fn``) and the
per-call stream set-up (the embedding ``lambda`` programs)."""

PROGRAMS = ("adv_scan_fn", "_lambda_", "_lambda")


def read(run):
    s = run.trace.device_seconds(PROGRAMS)
    return 1e3 * s / run.counts["blocks_tuned"] if s > 0 else None
