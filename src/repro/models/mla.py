"""DeepSeek-V2: latent attention (MLA) with YaRN rope, a dense first layer,
then layers of routed experts beside shared ones; LoRA adapters on the
attention projections over a frozen base.

The equations are those of the model's public ``modeling_deepseek.py``
(DeepSeek-V2-Lite, ``configs/deepseek_v2_lite.py``):

  - attention, per layer: ``q = x·Wq`` split (nope 128 | rope 64) per head;
    ``c, k_pe = split(x·Wkva, [kv_lora_rank, 64])``, ``k_pe`` one head shared
    by all; ``k_nope, v = split(rmsnorm(c)·Wkvb, [128, 128])`` per head;
    rope on ``q_pe`` and ``k_pe`` only, after de-interleaving their last
    dim; ``softmax(q·kᵀ·s + causal)·v`` with ``s = d_qk^-½ · mscale²``;
    then ``·Wo``;
  - YaRN: ``inv_freq`` blends the plain frequencies and the same over the
    factor with a linear ramp between the correction dims of ``beta_fast``
    and ``beta_slow`` (``yarn_inv_freq``); ``cos`` and ``sin`` are scaled by
    ``mscale(f, mscale) / mscale(f, mscale_all_dim)``;
  - feed-forward: SiLU-gated; layers ``first_k_dense`` and on hold the
    routed experts (``moe.moe_dropless``, over the experts this chip holds)
    plus the shared experts, one SiLU-gated MLP of ``num_shared_experts``
    expert widths;
  - pre-norm residual blocks, RMSNorm (``x / rms · w``), untied head.

The base is frozen and shared: ``frozen`` holds its bf16 weights (layer 0
dense, the rest stacked on a leading layer axis).  Each user holds LoRA
adapters ``y += (alpha / rank) · (x·A)·B`` on ``q``, ``kv_a``, ``kv_b`` and
``o`` (``ADAPTED``), stacked (layers, ·, ·) per projection.  ``user_losses``
takes every user's adapters and token batch at once, so the expert layer
routes all users' tokens together; each user's loss depends on its own
adapters only, so the gradient of their sum gives each user its own.

Device scopes: ``fl.mla`` (projections, rope, attention), ``fl.moe``
(router, sort, gather, combine, shared experts) and ``fl.moe.experts``
(the grouped matmuls, ``moe.EXPERT_SCOPE``), read by the FL trainer's
trace stages.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import moe
from repro.models.attention import flash_attention_jnp
from repro.models.common import ModelConfig, swiglu

STAGE_MLA = "fl.mla"
STAGE_MOE = "fl.moe"

ADAPTED = ("q", "kv_a", "kv_b", "o")      # LoRA targets: q_proj, kv_a_proj_with_mqa, kv_b_proj, o_proj
ATTN_BLOCK = 512                          # flash attention's query block and kv chunk
LOSS_BLOCK = 512                          # tokens a user per block of the loss's logits
RMS_NORM_EPS = 1e-6                       # DeepSeek-V2-Lite's rms_norm_eps


# ---------------------------------------------------------------------------
# YaRN
# ---------------------------------------------------------------------------


def yarn_mscale(factor: float, mscale: float) -> float:
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def yarn_inv_freq(dim: int, base: float, yarn: tuple) -> np.ndarray:
    """Rope frequencies of a ``dim``-wide rope head under YaRN
    ``(factor, original_max_position_embeddings, beta_fast, beta_slow, ·, ·)``
    (plain rope where ``yarn`` is empty)."""
    f_extra = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    if not yarn:
        return f_extra
    factor, orig, beta_fast, beta_slow = yarn[:4]

    def corr_dim(rotations):
        return dim * math.log(orig / (rotations * 2 * math.pi)) / (2 * math.log(base))

    low = max(math.floor(corr_dim(beta_fast)), 0)
    high = min(math.ceil(corr_dim(beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0.0, 1.0)
    m = 1.0 - ramp
    return f_extra / factor * (1.0 - m) + f_extra * m


def softmax_scale(cfg: ModelConfig) -> float:
    """``d_qk^-½`` times ``mscale(factor, mscale_all_dim)²`` under YaRN."""
    scale = (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5
    if cfg.rope_yarn:
        factor, mscale_all_dim = cfg.rope_yarn[0], cfg.rope_yarn[5]
        scale *= yarn_mscale(factor, mscale_all_dim) ** 2
    return scale


def rope_tables(cfg: ModelConfig, seq: int) -> tuple[jnp.ndarray, jnp.ndarray]:
    """(cos, sin) of positions 0..seq-1, (seq, qk_rope_head_dim) float32."""
    inv = yarn_inv_freq(cfg.qk_rope_head_dim, cfg.rope_theta, cfg.rope_yarn)
    ang = np.outer(np.arange(seq, dtype=np.float64), inv)
    emb = np.concatenate([ang, ang], axis=-1)
    att = 1.0
    if cfg.rope_yarn:
        factor, _, _, _, mscale, mscale_all_dim = cfg.rope_yarn
        att = yarn_mscale(factor, mscale) / yarn_mscale(factor, mscale_all_dim)
    return (jnp.asarray(np.cos(emb) * att, jnp.float32),
            jnp.asarray(np.sin(emb) * att, jnp.float32))


def apply_rope_interleaved(x: jnp.ndarray, cos: jnp.ndarray, sin: jnp.ndarray):
    """x (..., S, heads, d): de-interleave the last dim (pairs to halves),
    then ``x·cos + rotate_half(x)·sin``."""
    d = x.shape[-1]
    xf = x.astype(jnp.float32)
    xf = xf.reshape(x.shape[:-1] + (d // 2, 2)).swapaxes(-1, -2).reshape(x.shape)
    x1, x2 = xf[..., : d // 2], xf[..., d // 2:]
    rot = jnp.concatenate([-x2, x1], axis=-1)
    c, s = cos[:, None, :], sin[:, None, :]
    return (xf * c + rot * s).astype(x.dtype)


# ---------------------------------------------------------------------------
# Blocks
# ---------------------------------------------------------------------------


def rms_norm(x: jnp.ndarray, w: jnp.ndarray) -> jnp.ndarray:
    xf = x.astype(jnp.float32)
    xf = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + RMS_NORM_EPS)
    return (w.astype(jnp.float32) * xf).astype(x.dtype)


def lora_proj(x, w, ad, scale):
    """x (U, T, in) · w (in, out), plus each user's ``scale·(x·A_u)·B_u``;
    the adapters' products run in float32, so their gradients do too."""
    y = jnp.einsum("utd,de->ute", x, w)
    z = jnp.einsum("utd,udr->utr", x.astype(jnp.float32), ad["a"])
    return y + (scale * jnp.einsum("utr,ure->ute", z, ad["b"])).astype(y.dtype)


def mla_attention(p, ad, x, cfg: ModelConfig, cos, sin, lora_scale):
    """x (U, B, S, D) -> (U, B, S, D)."""
    u, b, s, d = x.shape
    h, dn, dr, dv = (cfg.num_heads, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                     cfg.v_head_dim)
    xt = x.reshape(u, b * s, d)
    q = lora_proj(xt, p["wq"], ad["q"], lora_scale).reshape(u * b, s, h, dn + dr)
    kva = lora_proj(xt, p["wkv_a"], ad["kv_a"], lora_scale)
    c, k_pe = kva[..., : cfg.kv_lora_rank], kva[..., cfg.kv_lora_rank:]
    c = rms_norm(c, p["kv_norm"])
    kv = lora_proj(c, p["wkv_b"], ad["kv_b"], lora_scale).reshape(u * b, s, h, dn + dv)
    k_nope, v = kv[..., :dn], kv[..., dn:]
    q_pe = apply_rope_interleaved(q[..., dn:], cos, sin)
    k_pe = apply_rope_interleaved(k_pe.reshape(u * b, s, 1, dr), cos, sin)
    q = jnp.concatenate([q[..., :dn], q_pe], axis=-1)
    k = jnp.concatenate([k_nope, jnp.broadcast_to(k_pe, (u * b, s, h, dr))], axis=-1)
    blk = min(ATTN_BLOCK, s)
    o = flash_attention_jnp(q, k, v, True, 0, blk, blk, 0, softmax_scale(cfg))
    return lora_proj(o.reshape(u, b * s, h * dv), p["wo"], ad["o"],
                     lora_scale).reshape(u, b, s, d)


def mlp(p, x):
    return swiglu(x @ p["w_gate"], x @ p["w_up"]) @ p["w_down"]


def shared_experts(p, x):
    """The always-on experts: one SiLU-gated MLP on every token."""
    return mlp(p, x)


def expert_layer(p, x, cfg: ModelConfig):
    """x (U, B, S, D): the held routed experts on every user's tokens at
    once, plus the shared experts; -> (out, dropless stats)."""
    xt = x.reshape(-1, x.shape[-1])
    routed, stats = moe.moe_dropless(p, xt, cfg, 0, cfg.experts_held or cfg.num_experts)
    out = routed + shared_experts(p["shared"], xt)
    return out.reshape(x.shape), stats


def _layer(p, ad, x, cfg, cos, sin, lora_scale, ffn):
    with jax.named_scope(STAGE_MLA):
        x = x + mla_attention(p["attn"], ad, rms_norm(x, p["ln1"]), cfg, cos, sin,
                              lora_scale)
    h = rms_norm(x, p["ln2"])
    if ffn == "dense":
        return x + mlp(p["mlp"], h), None
    with jax.named_scope(STAGE_MOE):
        y, stats = expert_layer(p["moe"], h, cfg)
    return x + y, stats


# ---------------------------------------------------------------------------
# The model: frozen base, per-user adapters, per-user losses
# ---------------------------------------------------------------------------


def token_nll(h, head, labels):
    """Per-user mean next-token loss over the (sliced) vocabulary, the logits
    made a block of ``LOSS_BLOCK`` tokens at a time and never kept.
    h (U, T, D), labels (U, T) -> (U,)."""
    u, t, d = h.shape
    blk = min(LOSS_BLOCK, t)
    hb = h.reshape(u, t // blk, blk, d).swapaxes(0, 1)
    lb = labels.reshape(u, t // blk, blk).swapaxes(0, 1)

    @jax.checkpoint
    def block(acc, xs):
        hh, ll = xs
        logits = jnp.einsum("utd,dv->utv", hh, head, preferred_element_type=jnp.float32)
        gold = jnp.take_along_axis(logits, ll[..., None], axis=-1)[..., 0]
        return acc + jnp.sum(jax.nn.logsumexp(logits, axis=-1) - gold, axis=-1), None

    acc, _ = jax.lax.scan(block, jnp.zeros(u, jnp.float32), (hb, lb))
    return acc / t


def user_losses(adapters, batch, frozen, cfg: ModelConfig, lora_scale: float):
    """Every user's loss at once.  ``adapters``: per projection ``{"a": (U,
    L, in, r), "b": (U, L, r, out)}``; ``batch``: ``x`` and ``y`` (U, B, S)
    int32 token ids and their next ids; ``frozen``: the bf16 base,
    ``embed`` (V, D), ``head`` (D, V), ``final_norm``, layer 0 under
    ``dense`` (``ln1``, ``ln2``, ``attn``: ``wq``, ``wkv_a``, ``kv_norm``,
    ``wkv_b``, ``wo``; ``mlp``: ``w_gate``, ``w_up``, ``w_down``) and layers
    1.. stacked under ``moe`` (the same norms and ``attn``; ``moe``:
    ``router`` (D, E), the held experts' ``w_gate``, ``w_up``, ``w_down``
    (held, ·, ·) and the ``shared`` MLP).  Returns ``(losses (U,), aux)``:
    ``aux["expert_slots"]`` (L-1, held experts) and ``aux["dropped_slots"]``
    from the expert layers."""
    tokens, labels = batch["x"], batch["y"]
    u, b, s = tokens.shape
    cos, sin = rope_tables(cfg, s)
    x = jnp.take(frozen["embed"], tokens, axis=0)
    ad = jax.tree.map(lambda a: jnp.moveaxis(a, 1, 0), adapters)   # layer-major

    dense = jax.checkpoint(lambda p, a, x: _layer(p, a, x, cfg, cos, sin, lora_scale,
                                                  "dense")[0])
    x = dense(frozen["dense"], jax.tree.map(lambda a: a[0], ad), x)

    @jax.checkpoint
    def moe_layer(x, pa):
        p, a = pa
        return _layer(p, a, x, cfg, cos, sin, lora_scale, "moe")

    rest = jax.tree.map(lambda a: a[1:], ad)
    x, stats = jax.lax.scan(moe_layer, x, (frozen["moe"], rest))
    h = rms_norm(x, frozen["final_norm"])
    losses = token_nll(h.reshape(u, b * s, -1), frozen["head"], labels.reshape(u, b * s))
    aux = {"expert_slots": stats["expert_slots"],
           "dropped_slots": jnp.sum(stats["dropped_slots"])}
    return losses, aux


def user_loss(adapters, batch, frozen, cfg: ModelConfig, lora_scale: float):
    """One user's loss (adapters and batch without the user axis)."""
    adapters, batch = jax.tree.map(lambda a: a[None], (adapters, batch))
    return user_losses(adapters, batch, frozen, cfg, lora_scale)[0][0]
