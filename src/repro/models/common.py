"""Shared model machinery: config, initializers, norms, RoPE, embeddings.

All models are pure-functional JAX: ``params`` are pytrees of ``jnp``
arrays, built by ``init(rng, cfg)`` and consumed by ``apply(params, ...)``.
Layer stacks use ``jax.lax.scan`` over stacked parameters so the lowered
HLO size is independent of depth (critical for 88-layer dry-runs).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Sequence

import jax
import jax.numpy as jnp
import numpy as np


def round_up(x: int, multiple: int) -> int:
    return ((x + multiple - 1) // multiple) * multiple


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    """One config covers every assigned architecture family.

    ``block_pattern`` selects the per-layer block type cycle, e.g.
    ``("attn",)`` for dense transformers, ``("ssm",)`` for mamba2,
    ``("rglru", "rglru", "local_attn")`` for recurrentgemma.
    """

    name: str = "model"
    family: str = "dense"            # dense | moe | ssm | hybrid | encdec | vlm | audio
    num_layers: int = 2
    d_model: int = 128
    num_heads: int = 4
    num_kv_heads: int = 4
    head_dim: int = 0                # 0 => d_model // num_heads
    d_ff: int = 512
    vocab_size: int = 1000
    vocab_pad_multiple: int = 256
    tied_embeddings: bool = False   # lm_head = embedᵀ (mamba2 ties them)
    max_seq_len: int = 131072
    # attention
    qk_norm: bool = False
    rope_theta: float = 10000.0
    mrope: bool = False              # qwen2-vl 3-axis M-RoPE
    window: int = 0                  # 0 => full causal; >0 sliding window
    block_pattern: tuple[str, ...] = ("attn",)
    # MoE
    num_experts: int = 0
    num_experts_per_tok: int = 0
    capacity_factor: float = 1.25
    norm_topk_prob: bool = True      # renormalise the top-k gate weights to sum 1
    moe_d_ff: int = 0                # routed expert width (0 => d_ff)
    num_shared_experts: int = 0      # always-on experts, one MLP of that many widths
    first_k_dense: int = 0           # leading layers with a dense MLP of width d_ff
    experts_held: int = 0            # experts [0, experts_held) on this chip (0 => all)
    # latent attention (MLA, DeepSeek-V2)
    kv_lora_rank: int = 0            # 0 => no MLA
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    # YaRN rope scaling: (factor, original_max_position_embeddings,
    # beta_fast, beta_slow, mscale, mscale_all_dim); () => plain rope
    rope_yarn: tuple = ()
    # SSM (mamba2)
    ssm_state: int = 128
    ssm_head_dim: int = 64
    ssm_expand: int = 2
    ssm_chunk: int = 256
    conv_width: int = 4
    # RG-LRU (recurrentgemma)
    lru_width: int = 0               # 0 => d_model
    local_window: int = 2048
    # encoder-decoder (whisper)
    num_encoder_layers: int = 0
    encoder_seq_ratio: int = 1       # encoder frames per decoder token slot
    # training
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    remat: bool = True
    scan_layers: bool = True
    train_microbatches: int = 1   # gradient-accumulation steps per batch
    # Cast the f32 master params to ``dtype`` ONCE per step (outside the
    # layer scan) so FSDP all-gathers move bf16, not f32.  §Perf iteration:
    # False reproduces the recorded baseline artifacts.
    cast_params_once: bool = True
    attn_chunk: int = 1024           # kv-chunk for flash-style jnp attention
    # frontend stubs
    frontend: str = "none"           # none | audio | vision

    @property
    def resolved_head_dim(self) -> int:
        return self.head_dim or self.d_model // self.num_heads

    @property
    def padded_vocab(self) -> int:
        return round_up(self.vocab_size, self.vocab_pad_multiple)

    @property
    def d_inner(self) -> int:
        return self.ssm_expand * self.d_model

    @property
    def ssm_heads(self) -> int:
        return self.d_inner // self.ssm_head_dim

    @property
    def resolved_lru_width(self) -> int:
        return self.lru_width or self.d_model

    def replace(self, **kw) -> "ModelConfig":
        return dataclasses.replace(self, **kw)


# ---------------------------------------------------------------------------
# Initializers
# ---------------------------------------------------------------------------


def dense_init(key, shape: Sequence[int], in_axis: int = 0, dtype=jnp.float32):
    """Truncated-normal fan-in init (matches common LM practice)."""
    fan_in = shape[in_axis]
    std = 1.0 / math.sqrt(fan_in)
    return (jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32) * std).astype(dtype)


def embed_init(key, shape, dtype=jnp.float32):
    return (jax.random.normal(key, shape, jnp.float32) * 0.02).astype(dtype)


# ---------------------------------------------------------------------------
# Norms / activations
# ---------------------------------------------------------------------------


def rms_norm(x: jnp.ndarray, scale: jnp.ndarray, eps: float = 1e-6) -> jnp.ndarray:
    dtype = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    out = x * jax.lax.rsqrt(var + eps) * (1.0 + scale.astype(jnp.float32))
    return out.astype(dtype)


def swiglu(x_gate: jnp.ndarray, x_up: jnp.ndarray) -> jnp.ndarray:
    return jax.nn.silu(x_gate) * x_up


# ---------------------------------------------------------------------------
# Rotary position embeddings (standard + M-RoPE)
# ---------------------------------------------------------------------------


def rope_frequencies(head_dim: int, theta: float) -> np.ndarray:
    return 1.0 / (theta ** (np.arange(0, head_dim, 2, dtype=np.float64) / head_dim))


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float) -> jnp.ndarray:
    """x: (..., seq, heads, head_dim); positions: (..., seq) int32."""
    head_dim = x.shape[-1]
    freqs = jnp.asarray(rope_frequencies(head_dim, theta), dtype=jnp.float32)
    angles = positions.astype(jnp.float32)[..., None] * freqs  # (..., seq, hd/2)
    cos = jnp.cos(angles)[..., None, :]
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def apply_mrope(
    x: jnp.ndarray, positions: jnp.ndarray, theta: float, sections=None
) -> jnp.ndarray:
    """Qwen2-VL multimodal RoPE: 3 position axes (t, h, w) across frequency
    sections.  positions: (3, ..., seq).  Default sections follow the
    published 2:3:3 split ((16, 24, 24) at head_dim 128), scaled to the
    actual head_dim so reduced smoke configs work."""
    head_dim = x.shape[-1]
    half = head_dim // 2
    if sections is None:
        a = half * 2 // 8
        b = half * 3 // 8
        sections = (a, b, half - a - b)
    assert sum(sections) == half, (sections, half)
    freqs = jnp.asarray(rope_frequencies(head_dim, theta), dtype=jnp.float32)
    # choose which position axis drives each frequency band
    axis_for_freq = np.concatenate(
        [np.full(s, i) for i, s in enumerate(sections)]
    )  # (half,)
    pos = positions.astype(jnp.float32)  # (3, ..., seq)
    sel = jnp.take(pos, jnp.asarray(axis_for_freq), axis=0)  # (half, ..., seq)
    sel = jnp.moveaxis(sel, 0, -1)  # (..., seq, half)
    angles = sel * freqs
    cos = jnp.cos(angles)[..., None, :]
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Losses / metrics
# ---------------------------------------------------------------------------


def softmax_cross_entropy(logits: jnp.ndarray, labels: jnp.ndarray,
                          ignore_id: int = -1) -> jnp.ndarray:
    """Mean next-token CE over valid positions. logits (..., V) f32/bf16."""
    logits = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(
        logits, jnp.maximum(labels, 0)[..., None], axis=-1
    )[..., 0]
    nll = logz - gold
    mask = (labels != ignore_id).astype(jnp.float32)
    return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
