"""Faults planted in the timed path, for showing that the check fails them.

Each fault patches the program in memory for the length of a ``with``
block (no file changes): a step that returns its state unchanged, half of
each microbatch left out of the loss, or an answer altered where the
entry returns it. The benchmark's own runs never use them; ``bench/calibrate.py``
and the tests do.
"""
from __future__ import annotations

import contextlib
import dataclasses


@contextlib.contextmanager
def _patched(obj, name, value):
    old = getattr(obj, name)
    setattr(obj, name, value)
    try:
        yield
    finally:
        setattr(obj, name, old)


# -- walk ---------------------------------------------------------------
def walk_unchanged(ctx):
    """Every optimizer step returns the weights it was given."""
    from repro.core import ebft

    return _patched(ebft, "apply_updates", lambda params, updates: params)


def walk_half_batch(ctx):
    """The loss is the mean over the first half of each microbatch only."""
    from repro.core import reconstruction as R

    full = R.block_loss

    def half(model, i, bw, masks_b, h_in, target, positions, aux):
        n = h_in.shape[0] // 2
        return full(model, i, bw, masks_b, h_in[:n], target[:n], positions, aux)

    return _patched(R, "block_loss", half)


def walk_answer(ctx):
    """The tuned model comes back from ``finetune`` with its block weights
    scaled by 1.1."""
    from repro.core import ebft

    from harness import layout

    finetune = ebft.finetune

    def altered(*args, **kw):
        tuned, reports = finetune(*args, **kw)
        return layout.map_blocks(ctx.ref, lambda a: a * 1.1, tuned), reports

    return _patched(ebft, "finetune", altered)


# -- serving ------------------------------------------------------------
@contextlib.contextmanager
def serve_unchanged(ctx):
    """Each decode step returns the cache it was given."""
    model = ctx.model
    step = model.decode_step
    ctx.model = dataclasses.replace(
        model, decode_step=lambda p, t, s: (step(p, t, s)[0], s))
    try:
        yield
    finally:
        ctx.model = model


def serve_answer(ctx):
    """``Server.serve`` returns every eighth id of a request as the next id."""
    from repro.serving import decode

    serve = decode.Server.serve
    vocab = ctx.conf["vocab_size"]

    def altered(self, *args, **kw):
        return {uid: [(t + 1) % vocab if j % 8 == 7 else t for j, t in enumerate(ids)]
                for uid, ids in serve(self, *args, **kw).items()}

    return _patched(decode.Server, "serve", altered)


FAULTS = {
    "walk": {"unchanged": walk_unchanged, "half_batch": walk_half_batch,
             "answer": walk_answer},
    "serve_single": {"unchanged": serve_unchanged, "answer": serve_answer},
}
