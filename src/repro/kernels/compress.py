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

The data-dependent per-row statistics are computed by the caller and
passed in, mirroring how ``gossip_mix_all_fwd`` takes the precomputed
mixing matrix.  The int8 scale is one tiny (N_T,) max reduction.  The
top-k threshold comes from ``topk_thresholds``, an exact radix select
with no sort: 8 fused compare-and-count passes over the delta, each one
read of it that settles 4 bits of every row's threshold.  On one v5e a
pass over the CNN's 128 MiB fc1 leaf at 64 users takes about 0.29 ms
(one read alone, 0.2 ms: the 15 compares a pass make it partly
compute-bound), and the whole search over the CNN's ten leaves 2.6 ms,
where ``lax.top_k`` took 51 ms.

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

# |x| as a uint32 bit pattern has its sign bit clear, so a threshold is
# settled by searching the 31 bits below it, RADIX_BITS per pass.  Four
# bits a pass beat one (31 memory-bound passes) and two on a v5e.
MAGNITUDE_BITS = 31
RADIX_BITS = 4
SELECT_PASSES = -(-MAGNITUDE_BITS // RADIX_BITS)
assert SELECT_PASSES * RADIX_BITS <= 32
SELECT_SCOPE = "topk_select"


def compress_block_len(rows: int, length: int) -> int:
    """Lane block for a (rows, length) delta: the delta block in and the
    message and residual blocks out, all under the scoped-VMEM budget."""
    return lane_block(length, rows, rows, rows)


def topk_thresholds(
    flats: list[jnp.ndarray],   # (N_i, L_i) float arrays, f32 or narrower
    ks: list[int],              # 1 <= k_i <= L_i
) -> list[jnp.ndarray]:
    """Per row of each array, the exact k-th largest |x| (an (N_i,) array).

    Bit-equal to ``jax.lax.top_k(jnp.abs(x), k)[0][:, -1]``, by a radix
    select on the bit pattern of |x|, which orders as the value: the
    threshold is the largest pattern p with count(bits(|x|) >= p) >= k.
    The search settles ``RADIX_BITS`` bits a pass, highest first; every
    pass is one fused compare-and-count over each array, for all arrays in
    one ``lax.fori_loop``, so many leaves cost ``SELECT_PASSES`` loop steps.
    A NaN ranks above +inf by its pattern, as ``lax.top_k`` ranks it; a
    row whose k-th largest |x| is NaN gets a NaN threshold, under which
    ``topk_mask_fwd`` keeps nothing of that row.
    """
    for x, k in zip(flats, ks, strict=True):
        assert jnp.finfo(x.dtype).bits <= 32, x.dtype
        assert 1 <= k <= x.shape[1], (k, x.shape)
    digits = jnp.arange(1, 1 << RADIX_BITS, dtype=jnp.uint32)

    def one_pass(i, prefixes):
        # The barrier ties the arrays to the pass index, so XLA cannot hoist
        # the bitcast out of the loop as a uint32 copy of every array: each
        # pass reads the float array itself.
        xs, _ = jax.lax.optimization_barrier((flats, i))
        shift = ((SELECT_PASSES - 1 - i) * RADIX_BITS).astype(jnp.uint32)
        out = []
        for x, k, p in zip(xs, ks, prefixes):
            # |x| as uint32 bit patterns, which order as the values do
            bits = jax.lax.bitcast_convert_type(
                x.astype(jnp.float32), jnp.uint32) & jnp.uint32(0x7FFFFFFF)
            cands = p[:, None] | (digits << shift)              # (N, 2^b - 1)
            # counts fall as the candidate digit rises: the digit is the
            # number of candidates that still leave k or more entries
            digit = sum(
                (jnp.sum(bits >= cands[:, j, None], axis=1, dtype=jnp.int32)
                 >= k).astype(jnp.uint32)
                for j in range(digits.shape[0])
            )
            out.append(p | (digit << shift))
        return out

    with jax.named_scope(SELECT_SCOPE):
        prefixes = jax.lax.fori_loop(
            0, SELECT_PASSES, one_pass,
            [jnp.zeros(x.shape[:1], jnp.uint32) for x in flats],
        )
    return [
        jax.lax.bitcast_convert_type(p, jnp.float32).astype(x.dtype)
        for p, x in zip(prefixes, flats)
    ]


def topk_threshold(flat: jnp.ndarray, k: int) -> jnp.ndarray:
    """(N, L) -> (N,): each row's exact k-th largest |x| (``topk_thresholds``)."""
    return topk_thresholds([flat], [k])[0]


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
