"""Pure-jnp oracles for every Pallas kernel (the test ground truth)."""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def flash_attention_ref(
    q: jnp.ndarray,      # (B, H, S, D)
    k: jnp.ndarray,      # (B, Hkv, S, D)
    v: jnp.ndarray,
    *,
    causal: bool = True,
    window: int = 0,
) -> jnp.ndarray:
    b, h, sq, d = q.shape
    hkv, sk = k.shape[1], k.shape[2]
    g = h // hkv
    scale = 1.0 / math.sqrt(d)
    qg = q.reshape(b, hkv, g, sq, d).astype(jnp.float32) * scale
    logits = jnp.einsum("bhgqd,bhkd->bhgqk", qg, k.astype(jnp.float32))
    qpos = jnp.arange(sq)
    kpos = jnp.arange(sk)
    mask = jnp.ones((sq, sk), dtype=bool)
    if causal:
        mask &= qpos[:, None] >= kpos[None, :]
    if window:
        mask &= qpos[:, None] - kpos[None, :] < window
    logits = jnp.where(mask[None, None, None], logits, -1e30)
    p = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhgqk,bhkd->bhgqd", p, v.astype(jnp.float32))
    return out.reshape(b, h, sq, d).astype(q.dtype)


def decode_attention_ref(
    q: jnp.ndarray,        # (B, H, D)
    k_cache: jnp.ndarray,  # (B, S, Hkv, D)
    v_cache: jnp.ndarray,
    valid_len: jnp.ndarray,
) -> jnp.ndarray:
    b, h, d = q.shape
    s, hkv = k_cache.shape[1], k_cache.shape[2]
    g = h // hkv
    scale = 1.0 / math.sqrt(d)
    qg = q.reshape(b, hkv, g, d).astype(jnp.float32) * scale
    logits = jnp.einsum("bhgd,bshd->bhgs", qg, k_cache.astype(jnp.float32))
    mask = jnp.arange(s)[None] < jnp.reshape(valid_len, (-1, 1))
    logits = jnp.where(mask[:, None, None], logits, -1e30)
    p = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhgs,bshd->bhgd", p, v_cache.astype(jnp.float32))
    return out.reshape(b, h, d).astype(q.dtype)


def rmsnorm_ref(x: jnp.ndarray, scale: jnp.ndarray, eps: float = 1e-6):
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps) * (1.0 + scale.astype(jnp.float32))).astype(
        x.dtype
    )


def gossip_mix_ref(stacked: jnp.ndarray, weights: jnp.ndarray) -> jnp.ndarray:
    return (
        weights.astype(jnp.float32) @ stacked.astype(jnp.float32)
    ).astype(stacked.dtype)


def gossip_mix_all_ref(stacked: jnp.ndarray, weights: jnp.ndarray) -> jnp.ndarray:
    """All-receivers dense oracle: (M, N) @ (N, L) -> (M, L) — the same
    matmul as the one-receiver oracle, batched over weight rows."""
    return gossip_mix_ref(stacked, weights)


def sdp_subspace_ref(
    Y: jnp.ndarray, V: jnp.ndarray
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Fused subspace-iteration oracle: (Y@V, Vᵀ(Y@V), ΣY²) in f32."""
    Yf = Y.astype(jnp.float32)
    Vf = V.astype(jnp.float32)
    YV = Yf @ Vf
    return YV, Vf.T @ YV, jnp.sum(Yf * Yf)


def rank_k_update_ref(
    Y: jnp.ndarray, A: jnp.ndarray, B: jnp.ndarray
) -> jnp.ndarray:
    """Rank-k downdate oracle: Y − A Bᵀ (f32 math, Y.dtype out)."""
    out = Y.astype(jnp.float32) - A.astype(jnp.float32) @ B.astype(jnp.float32).T
    return out.astype(Y.dtype)


def topk_mask_ref(
    X: jnp.ndarray, thresh: jnp.ndarray
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Threshold-sparsification oracle with error feedback (per-row thresh)."""
    Xf = X.astype(jnp.float32)
    msg = jnp.where(jnp.abs(Xf) >= thresh.astype(jnp.float32)[:, None], Xf, 0.0)
    return msg.astype(X.dtype), (Xf - msg).astype(X.dtype)


def int8_roundtrip_ref(
    X: jnp.ndarray, scale: jnp.ndarray
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Symmetric int8 quantize→dequantize oracle with error feedback."""
    Xf = X.astype(jnp.float32)
    s = scale.astype(jnp.float32)[:, None]
    msg = jnp.clip(jnp.round(Xf / s), -127.0, 127.0) * s
    return msg.astype(X.dtype), (Xf - msg).astype(X.dtype)


def bottleneck_eval_ref(
    assign: jnp.ndarray,   # (S, T) int machine index per task per sample
    p: jnp.ndarray,        # (T,)
    e: jnp.ndarray,        # (K,)
    C: jnp.ndarray,        # (K, K)
    src: jnp.ndarray,      # (E,) int edge sources (E may be 0)
    dst: jnp.ndarray,      # (E,) int edge destinations
) -> jnp.ndarray:
    """Eq. 2 over samples by index gathers — the kernel contract and the
    fused rounding's jnp evaluator.

    Per sample: machine loads (scatter-add of the workloads), each task's
    compute time ``(loads / e)[a]``, each edge's delay ``C[a[src], a[dst]]``
    max-accumulated into its source task from zero, then the max over
    tasks.  Equivalence to the float64 host evaluator
    (``bottleneck_time_batch``) is pinned in the property suite.
    """
    n_machines = e.shape[0]
    p = p.astype(jnp.float32)
    e = e.astype(jnp.float32)
    C = C.astype(jnp.float32)

    def one(a):
        loads = jnp.zeros(n_machines, jnp.float32).at[a].add(p)
        t_comp = (loads / e)[a]
        comm = jnp.zeros_like(t_comp).at[src].max(C[a[src], a[dst]])
        return jnp.max(t_comp + comm)

    return jax.vmap(one)(assign)


def gossip_mix_segment_ref(
    stacked: jnp.ndarray,    # (N, L) flat sender vectors
    src: jnp.ndarray,        # (|E|,) sender index per edge
    dst: jnp.ndarray,        # (|E|,) receiver index per edge
    w_edge: jnp.ndarray,     # (|E|,) per-edge mixing weight
    num_receivers: int,
) -> jnp.ndarray:
    """Sparse-mix reference: scatter-add the weighted sender rows per edge.

    Materializes the (|E|, L) gather, so it moves ~(2|E| + M)·L words —
    the baseline the all-receivers Pallas kernel is measured against.
    """
    contrib = stacked[src].astype(jnp.float32) * w_edge[:, None].astype(jnp.float32)
    out = jax.ops.segment_sum(contrib, dst, num_segments=num_receivers)
    return out.astype(stacked.dtype)
