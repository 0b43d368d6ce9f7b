"""Faults planted underneath the timed path, each of which the check must
catch (``correct`` false).  The tests plant them on the CPU at a small
size; ``calibrate.py`` plants them on the chip at the cell's own size to
read the numbers a fault gives.

  - ``frozen_round``: a gossip round returns its state unchanged;
  - ``half_batch``: the loss takes the mean over half of each batch;
  - ``no_exchange``: the gossip exchange delivers nothing.
"""

from __future__ import annotations

import contextlib


def _frozen_round():
    from repro.fl.gossip import GossipTrainer

    orig = GossipTrainer._build_stacked_round

    def build(self):
        fn = orig(self)
        return lambda state, xs, ys: (state, fn(state, xs, ys)[1])

    return GossipTrainer, "_build_stacked_round", build


def _half_batch():
    import repro.fl.cnn as cnn

    orig = cnn.cnn_loss

    def half(params, batch):
        n = batch["y"].shape[0] // 2
        return orig(params, {"x": batch["x"][:n], "y": batch["y"][:n]})

    return cnn, "cnn_loss", half


def _no_exchange():
    import jax.numpy as jnp

    import repro.fl.gossip as gossip

    return [
        (gossip, "gossip_mix_segment_ref", lambda m, src, dst, w, n: jnp.zeros_like(m)),
        (gossip, "gossip_mix_all_fwd",
         lambda X, W, **kw: jnp.zeros((W.shape[0], X.shape[1]), X.dtype)),
    ]


FAULTS = {
    "frozen_round": _frozen_round,
    "half_batch": _half_batch,
    "no_exchange": _no_exchange,
}


@contextlib.contextmanager
def planted(name: str):
    """Plant fault ``name`` for the duration of the block."""
    patches = FAULTS[name]()
    if isinstance(patches, tuple):
        patches = [patches]
    saved = [(obj, attr, getattr(obj, attr)) for obj, attr, _ in patches]
    try:
        for obj, attr, new in patches:
            setattr(obj, attr, new)
        yield
    finally:
        for obj, attr, old in saved:
            setattr(obj, attr, old)
