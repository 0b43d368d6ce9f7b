"""Faults of the ``gossip_fl`` kind, found before the shared ones of
``faults.py``:

  - ``half_batch``: the CNN's loss takes the mean over half of each batch.
"""

from __future__ import annotations


def _half_batch():
    import repro.fl.cnn as cnn

    orig = cnn.cnn_loss

    def half(params, batch):
        n = batch["y"].shape[0] // 2
        return orig(params, {"x": batch["x"][:n], "y": batch["y"][:n]})

    return cnn, "cnn_loss", half


FAULTS = {"half_batch": _half_batch}
