"""Device time of admission per request: the prefill program (and the
slot write, which with one slot is the prefilled state itself)."""

PROGRAMS = ("prefill",)


def read(run):
    s = run.trace.device_seconds(PROGRAMS)
    return 1e3 * s / len(run.counts["requests"]) if s > 0 else None
