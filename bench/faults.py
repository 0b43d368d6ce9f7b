"""Faults planted underneath the timed path, each of which the check must
catch (``correct`` false).  The tests plant them on the CPU at a small
size; ``calibrate.py`` plants them on the chip at the cell's own size to
read the numbers a fault gives.

A fault is found by its name and the kind of the cell's configuration:
first among the ``FAULTS`` of ``faults_<kind>.py``, a kind's own faults
(such as a loss that reads half of each batch), then among those below,
shared by every runner that trains on ``GossipTrainer``:

  - ``frozen_round``: a gossip round returns its state unchanged;
  - ``no_exchange``: the gossip exchange delivers nothing.

A fault is a function that returns the patch to make, or a list of
them, each ``(object, attribute, new value)``.
"""

from __future__ import annotations

import contextlib
import importlib


def _frozen_round():
    from repro.fl.gossip import GossipTrainer

    orig = GossipTrainer._build_stacked_round

    def build(self):
        fn = orig(self)
        return lambda state, xs, ys: (state, fn(state, xs, ys)[1])

    return GossipTrainer, "_build_stacked_round", build


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
    "no_exchange": _no_exchange,
}


def kind_faults(kind: str) -> dict:
    """The ``FAULTS`` of ``faults_<kind>.py``; none where there is no such
    module."""
    module = f"faults_{kind}"
    try:
        return importlib.import_module(module).FAULTS
    except ModuleNotFoundError as exc:
        if exc.name != module:
            raise
        return {}


def names(kind: str) -> set[str]:
    """Every fault a cell of this kind can have planted."""
    return set(kind_faults(kind)) | set(FAULTS)


def lookup(name: str, kind: str):
    """Fault ``name`` of the kind's own module, else the shared one."""
    own = kind_faults(kind)
    if name in own:
        return own[name]
    if name in FAULTS:
        return FAULTS[name]
    raise KeyError(f"no fault {name!r} for kind {kind!r}; choose from {sorted(names(kind))}")


@contextlib.contextmanager
def planted(name: str, *, kind: str):
    """Plant fault ``name`` of a cell of ``kind`` for the duration of the
    block."""
    patches = lookup(name, kind)()
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
