"""Host seconds spent building programs inside the window, per block
tuned: JAX's own trace, lowering, backend-compile and cache-read
durations (jax.monitoring). The walk makes fresh jitted closures on each
call, so every call traces again and reads its programs back."""


def read(run):
    return run.build_s / run.counts["blocks_tuned"]
