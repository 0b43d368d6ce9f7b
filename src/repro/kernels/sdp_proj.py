"""Pallas fused projection kernels for the SDP's partial-spectrum cone step.

The Douglas-Rachford hot loop (``repro.core.sdp``, DESIGN.md §3) spends its
time in the subspace iteration of ``cone_partial``: per sweep it streams the
dense (n, n) Gram iterate ``Y`` for the matvec ``Y @ V``, then again for the
Rayleigh-Ritz Gram matrix ``Vᵀ(YV)``, and once more for the Frobenius norm
and the final rank-k clip update.  ``roofline.py::sdp_batch_profile``
measured this loop at ~7.8 flops/byte against a machine balance of ~32 —
memory-bound, so fewer streams of ``Y`` is wall-clock (ROADMAP item 5).

Two kernels cover the loop:

  - ``sdp_subspace_fwd``: one pass over row-blocks of ``Y`` emits the
    matvec ``YV``, the small Gram ``G = VᵀYV`` (the Rayleigh-Ritz
    small-solve input), and ``ss = ΣY²`` (the shift ``σ = ‖Y‖_F``) —
    three reductions for ONE stream of ``Y`` instead of three.
  - ``rank_k_update_fwd``: the clip epilogue ``Yp = Y − A Bᵀ`` (caller
    passes ``A = W·θ⁻``, ``B = W``) fused into the same row-blocked
    stream, so the rank-k outer product is never materialized.

Inputs may be f32 or bf16; all arithmetic is f32 (the solver's working
precision), and the dots ask for it explicitly: the TPU default is one bf16
pass.  ``sdp_subspace_fwd`` returns f32 (its outputs feed the f32
``eigh``/``qr`` epilogue); ``rank_k_update_fwd`` casts back to ``Y.dtype``.
Rows are padded to the block size with zeros — zero rows of ``Y``/``V``
contribute nothing to any of the reductions — and sliced off the outputs.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _pad_rows(x: jnp.ndarray, rows: int) -> jnp.ndarray:
    pad = rows - x.shape[0]
    if pad == 0:
        return x
    return jnp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1))


def _dot(a, b):
    # f32 at full precision: the default TPU dot is one bf16 pass, which
    # stalls the DR solve (core/sdp.py, DOT_PRECISION)
    return jnp.dot(a, b, precision=jax.lax.Precision.HIGHEST,
                   preferred_element_type=jnp.float32)


def _subspace_kernel(y_ref, vfull_ref, vblk_ref, yv_ref, g_ref, ss_ref):
    i = pl.program_id(0)
    y = y_ref[...].astype(jnp.float32)            # (bn, np)
    yv = _dot(y, vfull_ref[...].astype(jnp.float32))   # (bn, k)
    yv_ref[...] = yv

    @pl.when(i == 0)
    def _init():
        g_ref[...] = jnp.zeros_like(g_ref)
        ss_ref[...] = jnp.zeros_like(ss_ref)

    g_ref[...] += _dot(vblk_ref[...].astype(jnp.float32).T, yv)
    ss_ref[...] += jnp.sum(y * y)


def sdp_subspace_fwd(
    Y: jnp.ndarray,   # (n, n) symmetric iterate
    V: jnp.ndarray,   # (n, k) subspace basis
    *,
    block_rows: int = 256,
    interpret: bool = False,
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """One stream of ``Y`` -> (``YV`` (n, k), ``G = VᵀYV`` (k, k), ``ΣY²``).

    ``V`` rides along twice: the full (n, k) block for the matvec and the
    row-block aligned with ``Y``'s rows for the ``G`` accumulation — both
    KiB-scale next to the (bn, n) slab of ``Y`` streamed once per step.
    """
    n = Y.shape[0]
    k = V.shape[1]
    assert Y.shape == (n, n), Y.shape
    assert V.shape == (n, k), (V.shape, n)
    bn = min(block_rows, n)
    n_pad = -(-n // bn) * bn
    Yp = _pad_rows(Y, n_pad)
    if n_pad != n:
        Yp = jnp.pad(Yp, ((0, 0), (0, n_pad - n)))
    Vp = _pad_rows(V, n_pad)
    yv, g, ss = pl.pallas_call(
        _subspace_kernel,
        grid=(n_pad // bn,),
        in_specs=[
            pl.BlockSpec((bn, n_pad), lambda i: (i, 0)),
            pl.BlockSpec((n_pad, k), lambda i: (0, 0)),
            pl.BlockSpec((bn, k), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bn, k), lambda i: (i, 0)),
            pl.BlockSpec((k, k), lambda i: (0, 0)),
            pl.BlockSpec((1, 1), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n_pad, k), jnp.float32),
            jax.ShapeDtypeStruct((k, k), jnp.float32),
            jax.ShapeDtypeStruct((1, 1), jnp.float32),
        ],
        interpret=interpret,
    )(Yp, Vp, Vp)
    return yv[:n], g, ss[0, 0]


def _rank_k_kernel(y_ref, ablk_ref, bfull_ref, o_ref):
    y = y_ref[...].astype(jnp.float32)            # (bn, np)
    a = ablk_ref[...].astype(jnp.float32)         # (bn, k)
    b = bfull_ref[...].astype(jnp.float32)        # (np, k)
    o_ref[...] = (y - _dot(a, b.T)).astype(o_ref.dtype)


def rank_k_update_fwd(
    Y: jnp.ndarray,   # (n, n)
    A: jnp.ndarray,   # (n, k) — e.g. W · θ⁻ (the negative Ritz pairs, scaled)
    B: jnp.ndarray,   # (n, k) — e.g. W
    *,
    block_rows: int = 256,
    interpret: bool = False,
) -> jnp.ndarray:
    """Rank-k downdate ``Y − A Bᵀ`` without materializing the outer product."""
    n = Y.shape[0]
    k = A.shape[1]
    assert Y.shape == (n, n), Y.shape
    assert A.shape == (n, k) and B.shape == (n, k), (A.shape, B.shape)
    bn = min(block_rows, n)
    n_pad = -(-n // bn) * bn
    Yp = _pad_rows(Y, n_pad)
    if n_pad != n:
        Yp = jnp.pad(Yp, ((0, 0), (0, n_pad - n)))
    Ap = _pad_rows(A, n_pad)
    Bp = _pad_rows(B, n_pad)
    out = pl.pallas_call(
        _rank_k_kernel,
        grid=(n_pad // bn,),
        in_specs=[
            pl.BlockSpec((bn, n_pad), lambda i: (i, 0)),
            pl.BlockSpec((bn, k), lambda i: (i, 0)),
            pl.BlockSpec((n_pad, k), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((bn, n_pad), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n_pad, n_pad), Y.dtype),
        interpret=interpret,
    )(Yp, Ap, Bp)
    return out[:n, :n]
