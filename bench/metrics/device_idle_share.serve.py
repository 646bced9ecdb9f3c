"""Share of the traced serving window in which no program ran on the chip."""


def read(run):
    return 100.0 * run.trace.idle_share
