"""Pallas fused delta-compression kernels with error feedback.

The stacked gossip engine (``repro.fl.gossip``, DESIGN.md §8) compresses
each round's parameter delta and keeps the error-feedback residual:

    delta    = params + residual
    msgs     = roundtrip(delta)          # what the wire carries
    residual = delta - msgs              # fed back next round

On the jnp path that is two full passes over the stacked (N_T, L) delta
(roundtrip, then the subtraction).  These kernels fuse the quantize /
sparsify decision with the residual into ONE stream per L-block: the delta
slab is read once and both ``msgs`` and ``residual`` come out of the same
pass.

The data-dependent per-row statistics (the top-k magnitude threshold, the
int8 scale) are tiny (N_T,) reductions computed by the caller in plain jnp
— the kernels take them as inputs, mirroring how ``gossip_mix_all_fwd``
takes the precomputed mixing matrix.

Contracts (element-wise in f32, cast back to ``X.dtype``):

  - ``topk_mask_fwd``:  msg = x · [|x| ≥ thresh_row],  resid = x − msg.
    With ``thresh_row`` = the row's k-th largest |x| this reproduces
    ``TopK.roundtrip`` exactly on tie-free rows (ties keep ≥ k entries —
    measure zero on training deltas).
  - ``int8_roundtrip_fwd``:  q = clip(round(x / scale_row), ±127),
    msg = q · scale_row,  resid = x − msg — msgs bit-equal to
    ``Int8.roundtrip`` for f32 inputs given the same per-row scale; the
    residual may differ by 1 ulp of |x| (XLA may contract q·scale into the
    subtraction as an FMA on either path).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.vmem import lane_block


def compress_block_len(rows: int, length: int) -> int:
    """Lane block for a (rows, length) delta: the delta block in and the
    message and residual blocks out, all under the scoped-VMEM budget."""
    return lane_block(length, rows, rows, rows)


def _topk_kernel(x_ref, t_ref, m_ref, r_ref):
    x = x_ref[...].astype(jnp.float32)          # (N, bl)
    thr = t_ref[...].astype(jnp.float32)        # (N,)
    msg = jnp.where(jnp.abs(x) >= thr[:, None], x, 0.0)
    m_ref[...] = msg.astype(m_ref.dtype)
    r_ref[...] = (x - msg).astype(r_ref.dtype)


def _int8_kernel(x_ref, s_ref, m_ref, r_ref):
    x = x_ref[...].astype(jnp.float32)          # (N, bl)
    scale = s_ref[...].astype(jnp.float32)[:, None]
    q = jnp.clip(jnp.round(x / scale), -127.0, 127.0)
    msg = q * scale
    m_ref[...] = msg.astype(m_ref.dtype)
    r_ref[...] = (x - msg).astype(r_ref.dtype)


def _blocked_rowstat_call(kernel, X, row_stat, *, block_len, interpret):
    n, l = X.shape
    assert row_stat.shape == (n,), (row_stat.shape, n)
    bl = min(block_len or compress_block_len(n, l), l)
    pad = (-l) % bl
    if pad:
        X = jnp.pad(X, ((0, 0), (0, pad)))
    lp = l + pad
    msg, resid = pl.pallas_call(
        kernel,
        grid=(lp // bl,),
        in_specs=[
            pl.BlockSpec((n, bl), lambda i: (0, i)),
            pl.BlockSpec((n,), lambda i: (0,)),
        ],
        out_specs=[
            pl.BlockSpec((n, bl), lambda i: (0, i)),
            pl.BlockSpec((n, bl), lambda i: (0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, lp), X.dtype),
            jax.ShapeDtypeStruct((n, lp), X.dtype),
        ],
        interpret=interpret,
    )(X, row_stat)
    return msg[:, :l], resid[:, :l]


def topk_mask_fwd(
    X: jnp.ndarray,        # (N, L) stacked per-user flat deltas
    thresh: jnp.ndarray,   # (N,) per-row keep threshold (k-th largest |x|)
    *,
    block_len: int | None = None,
    interpret: bool = False,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """One stream of X -> (sparsified msgs, error-feedback residual)."""
    return _blocked_rowstat_call(
        _topk_kernel, X, thresh, block_len=block_len, interpret=interpret
    )


def int8_roundtrip_fwd(
    X: jnp.ndarray,        # (N, L) stacked per-user flat deltas
    scale: jnp.ndarray,    # (N,) per-row symmetric quantization scale (> 0)
    *,
    block_len: int | None = None,
    interpret: bool = False,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """One stream of X -> (dequantized int8 msgs, error-feedback residual)."""
    return _blocked_rowstat_call(
        _int8_kernel, X, scale, block_len=block_len, interpret=interpret
    )
