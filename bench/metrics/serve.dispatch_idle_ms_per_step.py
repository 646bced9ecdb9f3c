"""Chip idle time per decode step inside the program's ``serve/step``
spans but outside their ``serve/sync`` children: the host dispatching the
step (rng split, decode, sample) and updating the last tokens while the
chip waits."""
from harness import spans


def read(run):
    sp = spans.of(run)
    steps = sp.named("serve/step") if sp else []
    if not steps:
        return None
    return 1e3 * sp.idle_s(("serve/step",), less=("serve/sync",)) / len(steps)
