"""Mixture-of-Experts FFN (Mixtral 8x7B top-2, OLMoE 64-expert top-8).

GShard-style *grouped* capacity dispatch: each sequence (= group) routes
its own tokens with per-group capacity C = ceil(S·k·cf / E), so the
dispatch cumsum stays local to a data shard (no cross-device sequential
dependency) and GSPMD can shard the expert matmuls:

    xe  (B, E, C, D)  — B over data axes, E over 'model' (EP) when E is
                        divisible (OLMoE 64/16), else F over 'model'
                        (Mixtral 8 experts -> expert-internal TP)
    h   (B, E, C, F)
    out scatter-adds back into (B, S, D) weighted by router probs.

No sorts and no O(N·E·C) one-hot einsums: positions-in-expert come from a
per-group cumsum, gather/scatter move the tokens.  Tokens beyond capacity
are dropped (Switch/GShard semantics; capacity_factor controls the rate).

``moe_dropless`` is the other layer: told which experts this chip holds,
it routes every token over all of them and computes its own experts' part
with no capacity, by a grouped matmul over the token-slots sorted by
expert (DeepSeek-V2 under expert parallelism, frozen experts).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.models.common import ModelConfig, dense_init, swiglu


def init_moe_params(key, cfg: ModelConfig) -> dict:
    kr, kg, ku, kd = jax.random.split(key, 4)
    d, f, e = cfg.d_model, cfg.d_ff, cfg.num_experts
    pd = cfg.param_dtype
    return {
        "router": dense_init(kr, (d, e), dtype=pd),
        "w_gate": dense_init(kg, (e, d, f), in_axis=1, dtype=pd),
        "w_up": dense_init(ku, (e, d, f), in_axis=1, dtype=pd),
        "w_down": dense_init(kd, (e, f, d), in_axis=1, dtype=pd),
    }


def _shard(rules, x, kind):
    return rules.constrain(x, kind) if rules is not None else x


def topk_gates(probs: jnp.ndarray, cfg: ModelConfig) -> tuple[jnp.ndarray, jnp.ndarray]:
    """The top-k experts of each token and their gate weights: renormalised
    to sum 1 (``cfg.norm_topk_prob``), else the router's probabilities as
    they are (DeepSeek-V2-Lite, whose ``routed_scaling_factor`` is 1)."""
    gate_vals, gate_idx = jax.lax.top_k(probs, cfg.num_experts_per_tok)
    if cfg.norm_topk_prob:
        return gate_vals / jnp.sum(gate_vals, axis=-1, keepdims=True), gate_idx
    return gate_vals, gate_idx


def moe_ffn(
    params: dict, x: jnp.ndarray, cfg: ModelConfig, rules=None
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """x: (B, S, D) -> (out (B, S, D), aux load-balancing loss (scalar)).

    With a mesh and E % tp == 0, uses the explicit shard_map EP path
    (``moe_ffn_sharded``) — GSPMD's sharding propagation hits "last-resort
    replication" on the data-dependent dispatch gather/scatter and moves
    E·C-sized buffers (§Perf olmoe iteration: 834 -> ~60 GB link bytes).
    """
    if (
        rules is not None
        and getattr(rules, "mesh", None) is not None
        and getattr(rules, "shard_moe", True)
        and x.shape[1] % rules.tp_size == 0
        and (
            cfg.num_experts % rules.tp_size == 0     # expert-parallel
            or cfg.d_ff % rules.tp_size == 0         # expert-internal TP
        )
    ):
        return moe_ffn_sharded(params, x, cfg, rules)
    return _moe_ffn_gspmd(params, x, cfg, rules)


def _moe_ffn_gspmd(
    params: dict, x: jnp.ndarray, cfg: ModelConfig, rules=None
) -> tuple[jnp.ndarray, jnp.ndarray]:
    b, s, d = x.shape
    e, k = cfg.num_experts, cfg.num_experts_per_tok
    capacity = int(max(1, -(-s * k * cfg.capacity_factor // e)))

    router_logits = jnp.einsum(
        "bsd,de->bse", x.astype(jnp.float32), params["router"].astype(jnp.float32)
    )
    probs = jax.nn.softmax(router_logits, axis=-1)            # (B, S, E)
    gate_vals, gate_idx = topk_gates(probs, cfg)               # (B, S, k)

    # --- per-group dispatch ------------------------------------------------
    expert_of = gate_idx.reshape(b, s * k)                     # (B, S·k)
    onehot = jax.nn.one_hot(expert_of, e, dtype=jnp.int32)     # (B, S·k, E)
    pos = jnp.cumsum(onehot, axis=1) * onehot                  # 1-based
    pos_in_expert = jnp.max(pos, axis=-1) - 1                  # (B, S·k)
    keep = (pos_in_expert >= 0) & (pos_in_expert < capacity)

    slot = expert_of * capacity + jnp.where(keep, pos_in_expert, 0)
    token_of_choice = jnp.repeat(jnp.arange(s), k)[None].repeat(b, axis=0)
    grp = jnp.arange(b)[:, None]

    # dropped choices scatter into a trash slot (index e·C) so they can
    # never clobber a real slot (slot 0 belongs to expert 0, position 0!)
    slot_or_trash = jnp.where(keep, slot, e * capacity)
    dispatch = jnp.zeros((b, e * capacity + 1), dtype=jnp.int32)
    dispatch = dispatch.at[grp, slot_or_trash].set(
        token_of_choice, mode="drop"
    )[:, :-1]
    slot_used = jnp.zeros((b, e * capacity + 1), dtype=jnp.bool_)
    slot_used = slot_used.at[grp, slot_or_trash].set(keep, mode="drop")[:, :-1]
    slot_gate = jnp.zeros((b, e * capacity + 1), dtype=jnp.float32)
    slot_gate = slot_gate.at[grp, slot_or_trash].set(
        gate_vals.reshape(b, s * k), mode="drop"
    )[:, :-1]

    # --- expert compute ------------------------------------------------------
    xe = jnp.take_along_axis(x, dispatch[..., None], axis=1)   # (B, E·C, D)
    xe = xe * slot_used[..., None].astype(x.dtype)
    xe = _shard(rules, xe.reshape(b, e, capacity, d), "moe_tokens")

    h = swiglu(
        jnp.einsum("becd,edf->becf", xe, params["w_gate"].astype(xe.dtype)),
        jnp.einsum("becd,edf->becf", xe, params["w_up"].astype(xe.dtype)),
    )
    h = _shard(rules, h, "moe_hidden")
    ye = jnp.einsum("becf,efd->becd", h, params["w_down"].astype(h.dtype))
    ye = _shard(rules, ye, "moe_tokens")

    # --- combine -------------------------------------------------------------
    yw = ye.reshape(b, e * capacity, d) * slot_gate[..., None].astype(ye.dtype)
    out = jnp.zeros((b, s, d), dtype=jnp.float32)
    out = out.at[grp, dispatch].add(
        jnp.where(slot_used[..., None], yw, 0).astype(jnp.float32)
    )

    # Switch-style load-balancing auxiliary loss
    me = jnp.mean(probs, axis=(0, 1))                          # (E,)
    frac = jnp.mean(
        jax.nn.one_hot(gate_idx, e, dtype=jnp.float32), axis=(0, 1, 2)
    )
    aux = e * jnp.sum(frac * me)

    return out.astype(x.dtype), aux.astype(jnp.float32)


# ---------------------------------------------------------------------------
# Explicit expert-parallel path (shard_map)
# ---------------------------------------------------------------------------
#
# Pattern (per tensor-parallel shard): all-gather the sequence-sharded
# hidden over 'model' (cheap: B·S·D), route ALL tokens (router weights are
# replicated so every shard computes identical assignments), dispatch only
# the tokens destined for the shard's OWN experts, run the local expert
# FFNs, scatter-add a partial (B, S, D), and reduce-scatter it straight
# back into the sequence-sharded layout.  Per-layer link bytes ≈
# 2·B·S·D — independent of top-k and capacity factor, which is what makes
# high-k MoE (OLMoE top-8) schedulable.


def _moe_core_local(
    params_local: dict, xf: jnp.ndarray, cfg: ModelConfig, lo: int, e_local: int
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Dispatch/compute/combine for experts [lo, lo + e_local) only.

    xf: (B, S, D) full-sequence tokens (identical on every shard).
    Returns (partial out (B, S, D), aux loss).
    """
    b, s, d = xf.shape
    e, k = cfg.num_experts, cfg.num_experts_per_tok
    capacity = int(max(1, -(-s * k * cfg.capacity_factor // e)))

    router_logits = jnp.einsum(
        "bsd,de->bse", xf.astype(jnp.float32),
        params_local["router"].astype(jnp.float32),
    )
    probs = jax.nn.softmax(router_logits, axis=-1)
    gate_vals, gate_idx = topk_gates(probs, cfg)

    expert_of = gate_idx.reshape(b, s * k)
    local_of = expert_of - lo
    in_range = (local_of >= 0) & (local_of < e_local)
    local_of = jnp.where(in_range, local_of, 0)

    onehot = jax.nn.one_hot(local_of, e_local, dtype=jnp.int32)
    onehot = onehot * in_range[..., None].astype(jnp.int32)
    pos = jnp.cumsum(onehot, axis=1) * onehot
    pos_in_expert = jnp.max(pos, axis=-1) - 1
    keep = in_range & (pos_in_expert >= 0) & (pos_in_expert < capacity)

    slot = local_of * capacity + jnp.where(keep, pos_in_expert, 0)
    token_of_choice = jnp.repeat(jnp.arange(s), k)[None].repeat(b, axis=0)
    grp = jnp.arange(b)[:, None]

    # see _moe_ffn_gspmd: dropped choices go to a trash slot
    slot_or_trash = jnp.where(keep, slot, e_local * capacity)
    dispatch = jnp.zeros((b, e_local * capacity + 1), dtype=jnp.int32)
    dispatch = dispatch.at[grp, slot_or_trash].set(
        token_of_choice, mode="drop"
    )[:, :-1]
    slot_used = jnp.zeros((b, e_local * capacity + 1), dtype=jnp.bool_)
    slot_used = slot_used.at[grp, slot_or_trash].set(keep, mode="drop")[:, :-1]
    slot_gate = jnp.zeros((b, e_local * capacity + 1), dtype=jnp.float32)
    slot_gate = slot_gate.at[grp, slot_or_trash].set(
        gate_vals.reshape(b, s * k), mode="drop"
    )[:, :-1]

    xe = jnp.take_along_axis(xf, dispatch[..., None], axis=1)
    xe = (xe * slot_used[..., None].astype(xf.dtype)).reshape(
        b, e_local, capacity, d
    )
    h = swiglu(
        jnp.einsum("becd,edf->becf", xe, params_local["w_gate"].astype(xe.dtype)),
        jnp.einsum("becd,edf->becf", xe, params_local["w_up"].astype(xe.dtype)),
    )
    ye = jnp.einsum("becf,efd->becd", h, params_local["w_down"].astype(h.dtype))

    yw = ye.reshape(b, e_local * capacity, d) * slot_gate[..., None].astype(ye.dtype)
    out = jnp.zeros((b, s, d), dtype=jnp.float32)
    out = out.at[grp, dispatch].add(
        jnp.where(slot_used[..., None], yw, 0).astype(jnp.float32)
    )

    me = jnp.mean(probs, axis=(0, 1))
    frac = jnp.mean(jax.nn.one_hot(gate_idx, e, dtype=jnp.float32), axis=(0, 1, 2))
    aux = e * jnp.sum(frac * me)
    return out.astype(xf.dtype), aux.astype(jnp.float32)


def moe_ffn_sharded(
    params: dict, x: jnp.ndarray, cfg: ModelConfig, rules
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Sharded MoE via shard_map (see block comment above).

    Two modes, same communication structure (all-gather seq in,
    psum_scatter partial outputs back to sequence-sharded):
      - EP   (E % tp == 0): each shard owns E/tp whole experts;
      - F-TP (otherwise, F % tp == 0 — Mixtral's 8 experts on tp=16):
        every shard owns all experts but only F/tp of each FFN; swiglu is
        elementwise over F and w_down contracts F, so per-shard outputs
        are exact partial sums.
    """
    from jax.sharding import PartitionSpec as P


    dp, tp = rules.dp, rules.tp_axis
    tp_size = rules.tp_size
    ep_mode = cfg.num_experts % tp_size == 0
    e_local = cfg.num_experts // tp_size if ep_mode else cfg.num_experts
    b_spec = dp if x.shape[0] % rules.dp_size == 0 else None

    def inner(x_shard, router, wg, wu, wd):
        # x_shard (B_l, S/tp, D): recover the full sequence locally
        xf = jax.lax.all_gather(x_shard, tp, axis=1, tiled=True)
        lo = jax.lax.axis_index(tp) * e_local if ep_mode else 0
        plocal = {"router": router, "w_gate": wg, "w_up": wu, "w_down": wd}
        out_partial, aux = _moe_core_local(plocal, xf, cfg, lo, e_local)
        out = jax.lax.psum_scatter(
            out_partial, tp, scatter_dimension=1, tiled=True
        )
        return out, aux

    if ep_mode:
        w_specs = (P(tp, None, None),) * 3
    else:
        w_specs = (P(None, None, tp), P(None, None, tp), P(None, tp, None))
    out, aux = jax.shard_map(
        inner,
        mesh=rules.mesh,
        in_specs=(P(b_spec, tp, None), P(None, None)) + w_specs,
        out_specs=(P(b_spec, tp, None), P()),
        check_vma=False,
    )(x, params["router"], params["w_gate"], params["w_up"], params["w_down"])
    return out, aux


# ---------------------------------------------------------------------------
# Dropless expert layer over the experts one chip holds
# ---------------------------------------------------------------------------
#
# Expert parallelism without a capacity: the chip holds experts
# [lo, lo + e_local) of cfg.num_experts, routes every token over all of
# them and computes its own experts' part of the result.  The T·k
# token-slots (token, choice) are sorted by held expert, the slots of absent
# experts last.  The sorted rows are then taken ``rows_per_block`` at a
# time, up to the last held slot: each block gathers its tokens, runs a
# grouped matmul (each held expert on its rows of the block) and adds its
# rows, times their gate weights, into the output.  Every held slot falls in
# some block, so no routing, however uneven, drops one; the blocks past the
# last held slot are skipped.  The backward pass walks the same blocks and
# recomputes each block's hidden activations instead of keeping them: the
# expert weights are frozen, so it needs the inputs' and the gate weights'
# cotangents only.  The blocks count the held rows they run through the
# grouped matmuls; a held slot that no block ran shows as dropped.


# The grouped matmuls' device scope; the gossip-FL trainer's trace reads it
# as a stage of its own, inside the layer's ``fl.moe`` (``models/mla.py``).
EXPERT_SCOPE = "fl.moe.experts"
GMM_TILING = (512, 512, 128)      # megablox (rows, contraction, output) tile


def grouped_matmul(lhs: jnp.ndarray, rhs: jnp.ndarray, group_sizes: jnp.ndarray,
                   transpose_rhs: bool = False) -> jnp.ndarray:
    """Rows of ``lhs`` (M, K) in consecutive groups times ``rhs[g]`` (G, K, N),
    or its transpose (G, N, K); rows past the last group come out zero.
    On an accelerator the megablox Pallas kernel, which visits only the row
    tiles the groups cover and leaves the other rows of its output
    unwritten (they are zeroed here); on the CPU ``lax.ragged_dot``.  Not
    differentiable: ``moe_dropless`` writes its backward pass."""
    from repro.kernels.ops import interpret_mode

    if interpret_mode():
        return jax.lax.ragged_dot(
            lhs, jnp.swapaxes(rhs, 1, 2) if transpose_rhs else rhs, group_sizes)
    from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm

    out = gmm(lhs, rhs, group_sizes, lhs.dtype, GMM_TILING, transpose_rhs=transpose_rhs)
    row = jax.lax.broadcasted_iota(jnp.int32, (out.shape[0], 1), 0)
    return jnp.where(row < jnp.sum(group_sizes), out, 0)


def _block_sizes(starts, ends, r0, rows):
    """Each group's rows within sorted rows [r0, r0 + rows)."""
    return jnp.clip(ends, r0, r0 + rows) - jnp.clip(starts, r0, r0 + rows)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def _expert_blocks(x, weight, token, starts, ends, w, rows):
    """Σ over sorted rows r < ends[-1] of weight[r] · expert(x[token[r]]),
    the rows ``rows`` at a time; -> ((T, D) float32, the number of rows the
    blocks ran through the grouped matmuls)."""
    out, _ = _expert_blocks_fwd(x, weight, token, starts, ends, w, rows)
    return out


def _block_forward(x, token, starts, ends, w, r0, rows):
    sizes = _block_sizes(starts, ends, r0, rows)
    tok = jax.lax.dynamic_slice_in_dim(token, r0, rows)
    xs = jnp.take(x, tok, axis=0)
    with jax.named_scope(EXPERT_SCOPE):
        g = grouped_matmul(xs, w["w_gate"], sizes)
        u = grouped_matmul(xs, w["w_up"], sizes)
        ys = grouped_matmul(swiglu(g, u), w["w_down"], sizes)
    return sizes, tok, g, u, ys


def _expert_blocks_fwd(x, weight, token, starts, ends, w, rows):
    t, d = x.shape
    blocks = token.shape[0] // rows

    def add_block(carry, b):
        r0 = b * rows

        def add(carry):
            out, ran = carry
            sizes, tok, _, _, ys = _block_forward(x, token, starts, ends, w, r0, rows)
            wb = jax.lax.dynamic_slice_in_dim(weight, r0, rows)
            return out.at[tok].add(ys.astype(jnp.float32) * wb[:, None]), ran + jnp.sum(sizes)

        return jax.lax.cond(r0 < ends[-1], add, lambda carry: carry, carry), None

    out, _ = jax.lax.scan(add_block, (jnp.zeros((t, d), jnp.float32), jnp.int32(0)),
                          jnp.arange(blocks, dtype=jnp.int32))
    return out, (x, weight, token, starts, ends, w)


def _expert_blocks_bwd(rows, res, cot):
    x, weight, token, starts, ends, w = res
    dout = cot[0]
    blocks = token.shape[0] // rows

    def grad_block(dx, b):
        r0 = b * rows

        def grad(dx):
            sizes, tok, g, u, ys = _block_forward(x, token, starts, ends, w, r0, rows)
            wb = jax.lax.dynamic_slice_in_dim(weight, r0, rows)
            dyw = jnp.take(dout, tok, axis=0)                      # (rows, D) f32
            dwb = jnp.sum(dyw * ys.astype(jnp.float32), axis=-1)
            dys = (dyw * wb[:, None]).astype(ys.dtype)
            with jax.named_scope(EXPERT_SCOPE):
                dh = grouped_matmul(dys, w["w_down"], sizes, transpose_rhs=True)
                _, swiglu_vjp = jax.vjp(swiglu, g, u)
                dg, du = swiglu_vjp(dh)
                dxs = (grouped_matmul(dg, w["w_gate"], sizes, transpose_rhs=True)
                       + grouped_matmul(du, w["w_up"], sizes, transpose_rhs=True))
            return dx.at[tok].add(dxs.astype(jnp.float32)), dwb

        return jax.lax.cond(r0 < ends[-1], grad,
                            lambda dx: (dx, jnp.zeros(rows, jnp.float32)), dx)

    dx, dw = jax.lax.scan(grad_block, jnp.zeros(x.shape, jnp.float32),
                          jnp.arange(blocks, dtype=jnp.int32))
    return (dx.astype(x.dtype), dw.reshape(-1).astype(weight.dtype), None, None, None,
            jax.tree.map(jnp.zeros_like, w))


_expert_blocks.defvjp(_expert_blocks_fwd, _expert_blocks_bwd)


def moe_dropless(
    params: dict, x: jnp.ndarray, cfg: ModelConfig, lo: int, e_local: int,
    rows_per_block: int = 16384,
) -> tuple[jnp.ndarray, dict]:
    """This chip's part of the expert layer for tokens ``x`` (T, D): routing
    over all ``cfg.num_experts`` (float32 router, softmax, ``topk_gates``),
    then experts [lo, lo + e_local) (``params["w_gate"]`` etc. hold just
    those, (e_local, ·, ·), frozen) on every token-slot routed to them.

    Returns ``(out (T, D), stats)``: ``expert_slots`` (e_local,) the slots
    routed to each held expert, and ``dropped_slots`` the held slots that
    the blocks did not run through the grouped matmuls (0 when every block
    that holds one runs).
    """
    t, d = x.shape
    k = cfg.num_experts_per_tok
    logits = jnp.dot(x.astype(jnp.float32), params["router"].astype(jnp.float32))
    gate_vals, gate_idx = topk_gates(jax.nn.softmax(logits, axis=-1), cfg)

    local = gate_idx.reshape(t * k) - lo
    held = (local >= 0) & (local < e_local)
    key = jnp.where(held, local, e_local)
    order = jnp.argsort(key, stable=True).astype(jnp.int32)     # sorted row -> slot
    group_sizes = jnp.zeros(e_local + 1, jnp.int32).at[key].add(1)[:e_local]
    ends = jnp.cumsum(group_sizes)
    weight = jnp.where(held, gate_vals.reshape(t * k), 0.0)[order]

    n = t * k
    rows = min(rows_per_block, n)
    pad = -n % rows
    token = jnp.pad(order // k, (0, pad))
    weight = jnp.pad(weight, (0, pad))
    experts = {name: params[name] for name in ("w_gate", "w_up", "w_down")}
    out, ran = _expert_blocks(x, weight, token, ends - group_sizes, ends, experts, rows)
    stats = {"expert_slots": group_sizes,
             "dropped_slots": jnp.sum(held, dtype=jnp.int32) - ran}
    return out.astype(x.dtype), stats
