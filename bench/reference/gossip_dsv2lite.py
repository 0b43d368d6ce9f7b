"""Plain reference of gossip LoRA fine-tuning of a DeepSeek-V2 block stack
(DeepSeek-V2-Lite's widths), D-PSGD over the adapters.

Imports nothing of the program under test.  Every user holds its LoRA
adapters over one frozen base; one round is, for every user:

  1. one step of SGD with momentum (``b = μ b + g``, ``p -= lr b``) on its
     next ``batch`` sequences, the loss being the mean next-token
     cross-entropy over the batch's tokens and the (sliced) vocabulary;
  2. the exchange: receiver j keeps ``w_self`` of its adapters and adds
     ``(1 - w_self) / indeg(j)`` of each sender's.

The round's loss is the mean over users.  The model, per
``modeling_deepseek.py``: embedding; layer 0 with a dense SiLU-gated MLP,
then layers whose feed-forward is the routed experts this chip holds
(softmax router over all of them, top-k, weights not renormalised, times
``routed_scaling_factor``; what the absent experts would add is left out)
plus the shared experts; pre-norm residual blocks with RMSNorm
(``w · x / rms``); latent attention: ``q = x·Wq`` (nope | rope per head),
``c, k_pe = x·Wkva`` (``k_pe`` one head for all), ``k_nope, v =
rmsnorm(c)·Wkvb``, rope on the rope parts after de-interleaving them, with
YaRN frequencies and ``cos, sin`` times ``mscale(f, mscale) / mscale(f,
mscale_all_dim)``, ``softmax(q·kᵀ·d_qk^-½·mscale(f, mscale_all_dim)² +
causal)·v``, then ``·Wo``; LoRA ``(alpha / rank)·(x·A)·B`` on the q, kv_a,
kv_b and o projections; final norm and an untied head.

Float32 throughout at ``highest`` matmul precision, from the bf16 base
upcast; one user (its ``batch`` sequences) at a time.  The attention is
the full (S, S) softmax; each held expert runs on every token, weighted by
its gate weight where it is among the token's top k and by 0 elsewhere.

``precision="fp8"`` is the low-precision control: both operands of every
matmul with a base weight (the projections, the MLPs, the experts, the
router and the head) are rounded to float8_e4m3fn under a per-tensor scale
that maps the tensor's largest magnitude to 448, the gradients passing
through unrounded.
"""

from __future__ import annotations

import math

import numpy as np


def _yarn_inv_freq(dim: int, base: float, rs: dict) -> np.ndarray:
    f_extra = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    factor = rs["factor"]

    def corr_dim(rotations):
        return (dim * math.log(rs["original_max_position_embeddings"]
                               / (rotations * 2 * math.pi)) / (2 * math.log(base)))

    low = max(math.floor(corr_dim(rs["beta_fast"])), 0)
    high = min(math.ceil(corr_dim(rs["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    keep = 1.0 - np.clip((np.arange(dim // 2) - low) / (high - low), 0.0, 1.0)
    return f_extra / factor * (1.0 - keep) + f_extra * keep


def _mscale(factor: float, m: float) -> float:
    return 0.1 * m * math.log(factor) + 1.0 if factor > 1 else 1.0


def _fp8():
    """Identity whose value is rounded to float8_e4m3fn under a per-tensor
    scale; the gradient passes through unchanged."""
    import jax
    import jax.numpy as jnp

    @jax.custom_vjp
    def r(x):
        s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
        return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s

    r.defvjp(lambda x: (r(x), None), lambda _, g: (g,))
    return r


def make_model(c: dict, lora_scale: float, seq: int, precision: str = "highest") -> dict:
    """The model's pieces on float32 weights: ``attention(p, ad, x)`` (one
    layer's latent attention with its adapters ``{"a": (in, r), "b": (r,
    out)}`` per projection, x (B, S, D)), ``experts(p, x)`` (the held
    routed experts plus the shared ones) and ``loss(adapters, base, x, y)``
    of one user (adapters stacked over the layers, x and y (B, S))."""
    import jax
    import jax.numpy as jnp

    h, dn, dr, dv = (c["num_attention_heads"], c["qk_nope_head_dim"],
                     c["qk_rope_head_dim"], c["v_head_dim"])
    kv_rank, eps, k_top = c["kv_lora_rank"], c["rms_norm_eps"], c["num_experts_per_tok"]
    rs = c["rope_scaling"]
    held = c["n_routed_experts"]
    r = _fp8() if precision == "fp8" else (lambda t: t)

    inv = _yarn_inv_freq(dr, c["rope_theta"], rs)
    ang = np.outer(np.arange(seq, dtype=np.float64), inv)
    emb = np.concatenate([ang, ang], axis=-1)
    att = _mscale(rs["factor"], rs["mscale"]) / _mscale(rs["factor"], rs["mscale_all_dim"])
    cos = jnp.asarray(np.cos(emb) * att, jnp.float32)
    sin = jnp.asarray(np.sin(emb) * att, jnp.float32)
    scale = (dn + dr) ** -0.5 * _mscale(rs["factor"], rs["mscale_all_dim"]) ** 2
    causal = jnp.tril(jnp.ones((seq, seq), bool))

    def mm(x, w):
        return r(x) @ r(w)

    def norm(x, w):
        return w * x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)

    def lora(x, w, ad):
        return mm(x, w) + lora_scale * (x @ ad["a"]) @ ad["b"]

    def rope(x):        # (B, S, heads, d)
        b, s, n, d = x.shape
        x = x.reshape(b, s, n, d // 2, 2).swapaxes(-1, -2).reshape(b, s, n, d)
        rot = jnp.concatenate([-x[..., d // 2:], x[..., : d // 2]], axis=-1)
        return x * cos[:, None, :] + rot * sin[:, None, :]

    def attention(p, ad, x):
        b, s, _ = x.shape
        q = lora(x, p["wq"], ad["q"]).reshape(b, s, h, dn + dr)
        kva = lora(x, p["wkv_a"], ad["kv_a"])
        ckv, k_pe = kva[..., :kv_rank], kva[..., kv_rank:]
        kv = lora(norm(ckv, p["kv_norm"]), p["wkv_b"], ad["kv_b"]).reshape(b, s, h, dn + dv)
        q = jnp.concatenate([q[..., :dn], rope(q[..., dn:])], axis=-1)
        k_pe = rope(k_pe.reshape(b, s, 1, dr))
        k = jnp.concatenate([kv[..., :dn], jnp.broadcast_to(k_pe, (b, s, h, dr))], axis=-1)
        logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
        probs = jax.nn.softmax(jnp.where(causal, logits, -jnp.inf), axis=-1)
        o = jnp.einsum("bhqk,bkhd->bqhd", probs, kv[..., dn:]).reshape(b, s, h * dv)
        return lora(o, p["wo"], ad["o"])

    def mlp(p, x):
        return mm(jax.nn.silu(mm(x, p["w_gate"])) * mm(x, p["w_up"]), p["w_down"])

    def experts(p, x):
        probs = jax.nn.softmax(mm(x, p["router"]), axis=-1)
        top, idx = jax.lax.top_k(probs, k_top)
        if c["norm_topk_prob"]:
            top = top / jnp.sum(top, axis=-1, keepdims=True)
        else:
            top = top * c["routed_scaling_factor"]

        def add_expert(out, e_w):
            e, w = e_w
            gate = jnp.sum(jnp.where(idx == e, top, 0.0), axis=-1, keepdims=True)
            return out + gate * mlp(w, x), None

        held_w = {name: p[name] for name in ("w_gate", "w_up", "w_down")}
        out, _ = jax.lax.scan(add_expert, mlp(p["shared"], x), (jnp.arange(held), held_w))
        return out

    def layer(p, ad, x, ffn):
        x = x + attention(p["attn"], ad, norm(x, p["ln1"]))
        y = norm(x, p["ln2"])
        return x + (mlp(p["mlp"], y) if ffn == "dense" else experts(p["moe"], y))

    def loss(adapters, base, x, y):
        hid = base["embed"][x]
        first = jax.tree.map(lambda v: v[0], adapters)
        hid = jax.checkpoint(lambda a, t: layer(base["dense"], a, t, "dense"))(first, hid)
        rest = jax.tree.map(lambda v: v[1:], adapters)
        hid, _ = jax.lax.scan(
            jax.checkpoint(lambda t, pa: (layer(pa[0], pa[1], t, "moe"), None)),
            hid, (base["moe"], rest))
        logits = mm(norm(hid, base["final_norm"]), base["head"])
        gold = jnp.take_along_axis(logits, y[..., None], axis=-1)[..., 0]
        return jnp.mean(jax.nn.logsumexp(logits, axis=-1) - gold)

    return {"attention": attention, "experts": experts, "loss": loss}


def mixing(edges, users: int, self_weight: float):
    """(self weights (N,), dense W (N, N) with W[j, i] the weight of i's
    message at receiver j)."""
    indeg = np.zeros(users)
    for _, j in edges:
        indeg[j] += 1
    W = np.zeros((users, users), np.float32)
    for i, j in edges:
        W[j, i] += (1.0 - self_weight) / indeg[j]
    self_w = np.where(indeg > 0, self_weight, 1.0).astype(np.float32)
    return self_w, W


def run(base, adapters0, tokens, edges, c: dict, *, rounds: int, batch: int, lr: float,
        momentum: float, self_weight: float, lora_scale: float,
        precision: str = "highest") -> dict:
    """Run ``rounds`` D-PSGD rounds of every user from the common start
    ``adapters0`` over the bf16 ``base``; ``tokens`` (N, rows, S + 1), round
    r reading rows [r·batch, (r+1)·batch) of every user.

    Returns the per-round losses, and per leaf (in ``jax.tree.leaves``
    order, users stacked) the momentum after round 1 and the adapters'
    change after the last round, as float32 numpy arrays.
    """
    import jax
    import jax.numpy as jnp

    users, seq = tokens.shape[0], tokens.shape[2] - 1
    self_w, W = mixing(edges, users, self_weight)
    loss = make_model(c, lora_scale, seq, precision)["loss"]
    with jax.default_matmul_precision("highest"):
        base32 = jax.tree.map(lambda w: jnp.asarray(w, jnp.float32), base)
        grad = jax.jit(jax.value_and_grad(loss))
        leaves0, treedef = jax.tree.flatten(adapters0)
        params = [np.broadcast_to(np.asarray(l, np.float32), (users,) + l.shape).copy()
                  for l in leaves0]
        mom = [np.zeros_like(p) for p in params]
        losses, mom1 = [], None
        for r in range(rounds):
            total = 0.0
            for u in range(users):
                rows = jnp.asarray(tokens[u, r * batch:(r + 1) * batch])
                val, g = grad(treedef.unflatten([jnp.asarray(p[u]) for p in params]),
                              base32, rows[:, :-1], rows[:, 1:])
                total += float(val)
                for p, b, gl in zip(params, mom, jax.tree.leaves(g)):
                    b[u] = momentum * b[u] + np.asarray(gl, np.float32)
                    p[u] = p[u] - lr * b[u]
            losses.append(total / users)
            if r == 0:
                mom1 = [b.copy() for b in mom]
            params = [self_w.reshape((-1,) + (1,) * (p.ndim - 1)) * p
                      + np.tensordot(W, p, axes=1) for p in params]
        change = [p - np.asarray(l0, np.float32)[None] for p, l0 in zip(params, leaves0)]
    return {"losses": losses, "momentum": mom1, "change": change}
