"""The whole walk's share of the chip's peak: the operations the walk
requires (flops.walk_block_flops of each block tuned, by its index, with
the epochs it really ran) over the traced window's time, the chips and
the bf16 peak."""


def read(run):
    c = run.counts
    work = sum(run.flops.walk_block_flops(run.ref, run.conf, i, c["seq_len"], c["tokens"], e)
               for i, e in c["epochs_run"])
    return 100.0 * work / (run.trace.window_s * run.chips * run.peaks["flops"])
