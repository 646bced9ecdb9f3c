"""The whole of serving's share of the chip's peak: 2 x the matmul weights
per token through the blocks and per token the head scores
(flops.serve_request_flops), over the traced window, the chips and the
bf16 peak."""


def read(run):
    work = sum(run.flops.serve_request_flops(run.ref, run.conf, p, n)
               for p, n in run.counts["requests"])
    return 100.0 * work / (run.trace.window_s * run.chips * run.peaks["flops"])
