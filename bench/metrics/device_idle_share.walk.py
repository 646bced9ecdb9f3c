"""Share of the traced walk window in which no program ran on the chip."""


def read(run):
    return 100.0 * run.trace.idle_share
