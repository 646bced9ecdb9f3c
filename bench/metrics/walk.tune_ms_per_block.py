"""Device time of the fused tune program (core/ebft.py ``fused_run``: the
whole epoch loop of one block in one dispatch) per block tuned."""

PROGRAMS = ("fused_run",)


def read(run):
    s = run.trace.device_seconds(PROGRAMS)
    return 1e3 * s / run.counts["blocks_tuned"] if s > 0 else None
