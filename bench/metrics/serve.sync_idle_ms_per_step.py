"""Chip idle time per decode step inside the program's ``serve/sync``
spans: the device-to-host read of each step's token, from the end of the
step's programs to the host holding the id."""
from harness import spans


def read(run):
    sp = spans.of(run)
    steps = sp.named("serve/step") if sp else []
    if not steps:
        return None
    return 1e3 * sp.idle_s(("serve/sync",)) / len(steps)
