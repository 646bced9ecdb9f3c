"""Chip idle time inside the program's stream spans per block tuned:
``walk/setup`` (calibration batches, each segment's stream set-up),
``walk/teacher`` (the prefetched teacher advance and the wait for it) and
``walk/student`` (the student advance's dispatch)."""
from harness import spans

PHASES = ("walk/setup", "walk/teacher", "walk/student")


def read(run):
    sp = spans.of(run)
    if not sp or not sp.named(*PHASES):
        return None
    return 1e3 * sp.idle_s(PHASES) / run.counts["blocks_tuned"]
