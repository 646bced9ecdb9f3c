"""Programs the walk builds per block tuned: JAX's lowering events
(``jaxpr_to_mlir_module_duration``, one per program built, then compiled
or read from the compilation cache) that ended inside the program's
``ebft/walk`` spans. A walk that reused its programs across calls would
read 0."""
from harness import spans


def read(run):
    sp = spans.of(run)
    if not sp or not sp.named("ebft/walk"):
        return None
    return sp.builds_in(("ebft/walk",)) / run.counts["blocks_tuned"]
