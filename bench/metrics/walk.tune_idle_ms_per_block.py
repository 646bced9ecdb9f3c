"""Chip idle time inside the program's ``walk/tune`` spans per block
tuned: the host's part of tuning a block (building the fused tune program,
its dispatch, the read of its losses, the write-back of the block) while
no program runs on the chip."""
from harness import spans


def read(run):
    sp = spans.of(run)
    if not sp or not sp.named("walk/tune"):
        return None
    return 1e3 * sp.idle_s(("walk/tune",)) / run.counts["blocks_tuned"]
