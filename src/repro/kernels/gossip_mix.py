"""Pallas fused gossip aggregation: out = Σ_n w[n] · params[n] in one pass.

The gossip step averages N neighbor models (paper §2.1).  Naively that is
N-1 separate AXPY sweeps (2(N-1) HBM round-trips of the full parameter
vector); this kernel streams the stacked (N, L) neighbor buffer once and
writes the mix — bandwidth-bound at (N+1)/(2(N-1))× fewer bytes.

Three entry points:
  - ``gossip_mix_fwd``: one receiver — stacked (N, L) · weights (N,) -> (L,).
  - ``gossip_mix_block_fwd``: one SHARD of receivers of the mesh-sharded
    engine — the shard's local (m, L) sender slab under the intra-shard
    mixing block (m, m) plus the gathered boundary-row halo (H, L) under
    the cross-shard block (m, H), fused so both slabs stream once per
    L-block (DESIGN.md §13).
  - ``gossip_mix_all_fwd``: ALL receivers of a gossip round at once —
    stacked (N, L) · row-normalized mixing matrix W (M, N) -> (M, L).
    Per L-block the kernel reads the (N, bl) slab ONCE and emits every
    receiver's mix, so the whole exchange moves (N+M)·L words instead of
    the Σ_j (indeg_j + 1)·L ≈ (|E|+M)·L of per-edge AXPY aggregation
    (or the (2|E|+M)·L of a gather + segment_sum).  This is the
    device-resident exchange of the stacked gossip-FL engine
    (``repro.fl.gossip``, DESIGN.md §8).

Inputs: stacked flat params (N, L), weights (N,) or (M, N).  Grid over L
chunks (and receiver blocks, once the mixing matrix outgrows VMEM); block
sizes come from the scoped-VMEM budget of ``repro.kernels.vmem``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.vmem import (
    LANE,
    STREAM_BUDGET_BYTES,
    SUBLANE,
    lane_block,
    pad_rows,
)


def _weight_row_bytes(senders) -> int:
    """Double-buffered VMEM bytes of one receiver row of the mixing blocks
    (one (·, rows) block per sender slab, lane-padded)."""
    return 2 * 4 * sum(-(-rows // LANE) * LANE for rows in senders)


def mix_receiver_block(receivers: int, *senders: int) -> int:
    """Receivers per grid step: all of them while their mixing blocks take
    at most half of the streaming budget (N_T up to a few hundred), else
    the largest multiple of 8 that does."""
    per_row = _weight_row_bytes(senders)
    if pad_rows(receivers) * per_row <= STREAM_BUDGET_BYTES // 2:
        return receivers
    return max(SUBLANE, STREAM_BUDGET_BYTES // 2 // per_row // SUBLANE * SUBLANE)


def mix_block_len(length: int, receivers: int, *senders: int) -> int:
    """Lane block for mixing ``length`` columns into ``receivers`` rows
    from one (rows, length) slab per entry of ``senders``: the sender
    blocks in and the receiver block out are streamed, and the receiver
    block's rows of every mixing block ride along."""
    bm = mix_receiver_block(receivers, *senders)
    return lane_block(
        length, bm, *senders, fixed_bytes=pad_rows(bm) * _weight_row_bytes(senders)
    )


def _mix_kernel(x_ref, w_ref, o_ref):
    x = x_ref[...].astype(jnp.float32)          # (N, bl)
    w = w_ref[...].astype(jnp.float32)          # (N,)
    o_ref[...] = jnp.dot(
        w, x, precision=jax.lax.Precision.HIGHEST
    ).astype(o_ref.dtype)


def gossip_mix_fwd(
    stacked: jnp.ndarray,   # (N, L) neighbor parameter vectors (incl. self)
    weights: jnp.ndarray,   # (N,) aggregation weights (sum to 1)
    *,
    block_len: int = 65536,
    interpret: bool = False,
) -> jnp.ndarray:
    n, l = stacked.shape
    bl = min(block_len, l)
    assert l % bl == 0, (l, bl)
    return pl.pallas_call(
        _mix_kernel,
        grid=(l // bl,),
        in_specs=[
            pl.BlockSpec((n, bl), lambda i: (0, i)),
            pl.BlockSpec((n,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((bl,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((l,), stacked.dtype),
        interpret=interpret,
    )(stacked, weights)


def _mix_groups_kernel(*refs):
    # refs: G sender slabs (n_g, bl), G mixing blocks (bm, n_g), out (bm, bl)
    # The default TPU dot is one bf16 pass (~2e-3 relative error); the mix
    # must equal the f32 segment-sum exchange, so it asks for full f32.
    g = (len(refs) - 1) // 2
    acc = None
    for x_ref, w_ref in zip(refs[:g], refs[g:2 * g]):
        term = jnp.dot(
            w_ref[...].astype(jnp.float32), x_ref[...].astype(jnp.float32),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32,
        )
        acc = term if acc is None else acc + term
    refs[-1][...] = acc.astype(refs[-1].dtype)


def _mix_groups(xs, ws, *, block_len, interpret):
    """out = Σ_g ws[g] @ xs[g] over sender groups, blocked over L × receivers.

    The grid walks lane blocks in the outer dimension and receiver blocks
    in the inner one, so each (n_g, bl) sender block is fetched once per
    lane block and stays resident while every receiver block is emitted.
    """
    l = xs[0].shape[1]
    m = ws[0].shape[0]
    senders = [x.shape[0] for x in xs]
    for x, w in zip(xs, ws):
        assert x.shape[1] == l, (x.shape, l)
        assert w.shape == (m, x.shape[0]), (w.shape, (m, x.shape[0]))
    bl = min(block_len or mix_block_len(l, m, *senders), l)
    assert l % bl == 0, (l, bl)
    bm = mix_receiver_block(m, *senders)
    mp = -(-m // bm) * bm
    ws = [jnp.pad(w, ((0, mp - m), (0, 0))) for w in ws]
    out = pl.pallas_call(
        _mix_groups_kernel,
        grid=(l // bl, mp // bm),
        in_specs=[pl.BlockSpec((n, bl), lambda i, j: (0, i)) for n in senders]
        + [pl.BlockSpec((bm, n), lambda i, j: (j, 0)) for n in senders],
        out_specs=pl.BlockSpec((bm, bl), lambda i, j: (j, i)),
        out_shape=jax.ShapeDtypeStruct((mp, l), xs[0].dtype),
        interpret=interpret,
    )(*xs, *ws)
    return out[:m]


def gossip_mix_block_fwd(
    local: jnp.ndarray,     # (m, L) this shard's flat sender vectors
    w_block: jnp.ndarray,   # (m, m) intra-shard mixing block
    halo: jnp.ndarray,      # (H, L) gathered boundary rows of other shards
    w_halo: jnp.ndarray,    # (m, H) cross-shard mixing block
    *,
    block_len: int | None = None,
    interpret: bool = False,
) -> jnp.ndarray:
    """Block-local mixing of the mesh-sharded exchange (one shard's view):
    ``out = w_block @ local + w_halo @ halo``.

    Per L-block the kernel streams the (m, bl) local slab AND the (H, bl)
    halo slab exactly once and emits every local receiver's mix — the
    sharded counterpart of ``gossip_mix_all_fwd``, whose (N, L) all-users
    slab no longer exists on any one device.  With no cross-shard edges
    (H = 0) the halo term is skipped entirely.
    """
    if halo.shape[0] == 0:
        return gossip_mix_all_fwd(
            local, w_block, block_len=block_len, interpret=interpret
        )
    return _mix_groups(
        [local, halo], [w_block, w_halo], block_len=block_len,
        interpret=interpret,
    )


def gossip_mix_all_fwd(
    stacked: jnp.ndarray,   # (N, L) flat sender parameter vectors
    weights: jnp.ndarray,   # (M, N) mixing matrix, row m = receiver m's weights
    *,
    block_len: int | None = None,
    interpret: bool = False,
) -> jnp.ndarray:
    """All-receivers blocked mixing: out[m] = Σ_n W[m, n] · stacked[n].

    The (N, bl) slab of the stacked buffer is streamed exactly once for
    all M receivers; W rides along whole while it is small (N_T up to a
    few hundred) and in receiver blocks beyond that.
    """
    return _mix_groups(
        [stacked], [weights], block_len=block_len, interpret=interpret
    )
