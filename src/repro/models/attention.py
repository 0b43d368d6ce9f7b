"""Attention: GQA training/prefill attention + distributed decode attention.

Three implementations with identical semantics (tested against each other):

  - ``dense``   : full (S, S) logits — reference / small shapes.
  - ``chunked`` : flash-style online softmax in pure jnp — python loop over
    query blocks, ``lax.scan`` over kv chunks, *triangular block skipping*
    for causal masks so HLO FLOPs ≈ S²/2 instead of S².  Memory is
    O(q_block × kv_chunk) — this is what the 32k prefill dry-runs lower.
  - ``pallas``  : the Pallas flash kernel (repro.kernels) on TPU.

Decode attention supports a sequence-sharded KV cache via ``shard_map``
(kv_heads of the assigned archs are mostly 8 < model-axis 16, so the cache
shards over *sequence*; softmax runs distributed with psum-max/psum-sum).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp


def _split_heads(q, k, v):
    """(B,S,H,D),(B,S,Hkv,D) -> grouped views; returns group size g."""
    h, hkv = q.shape[2], k.shape[2]
    assert h % hkv == 0, (h, hkv)
    return h // hkv


def dense_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    causal: bool = True,
    window: int = 0,
    q_offset: int = 0,
    scale: float | None = None,
) -> jnp.ndarray:
    """Reference attention. q, k (B,S,H|Hkv,D), v (B,Sk,Hkv,Dv) -> (B,Sq,H,Dv).

    ``scale`` multiplies the logits (default ``D**-0.5``)."""
    g = _split_heads(q, k, v)
    b, sq, h, d = q.shape
    sk, hkv, dv = k.shape[1], k.shape[2], v.shape[-1]
    scale = 1.0 / math.sqrt(d) if scale is None else scale
    qg = q.reshape(b, sq, hkv, g, d)
    logits = jnp.einsum("bqhgd,bkhd->bhgqk", qg.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    if causal or window:
        qpos = jnp.arange(sq) + q_offset
        kpos = jnp.arange(sk)
        mask = jnp.ones((sq, sk), dtype=bool)
        if causal:
            mask &= qpos[:, None] >= kpos[None, :]
        if window:
            mask &= qpos[:, None] - kpos[None, :] < window
        logits = jnp.where(mask[None, None, None], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", probs, v.astype(jnp.float32))
    return out.reshape(b, sq, h, dv).astype(q.dtype)


def chunked_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    causal: bool = True,
    window: int = 0,
    q_block: int = 1024,
    kv_chunk: int = 1024,
    q_offset: int = 0,
    return_lse: bool = False,
    scale: float | None = None,
):
    """Flash-style attention in pure jnp (see module docstring).

    Causal triangular skipping: query block t only scans kv chunks that can
    contain unmasked keys, so compiled FLOPs follow the true mask area.
    ``v`` may have a head dim of its own; ``scale`` as in
    ``dense_attention``.
    """
    b, sq, h, d = q.shape
    sk, hkv, dv = k.shape[1], k.shape[2], v.shape[-1]
    g = _split_heads(q, k, v)
    scale = 1.0 / math.sqrt(d) if scale is None else scale
    q_block = min(q_block, sq)
    kv_chunk = min(kv_chunk, sk)
    assert sq % q_block == 0 and sk % kv_chunk == 0, (sq, q_block, sk, kv_chunk)

    kc = k.reshape(b, sk // kv_chunk, kv_chunk, hkv, d)
    vc = v.reshape(b, sk // kv_chunk, kv_chunk, hkv, dv)

    outs = []
    lses = []
    for qb in range(sq // q_block):
        qi = q[:, qb * q_block : (qb + 1) * q_block]
        qi = qi.reshape(b, q_block, hkv, g, d).astype(jnp.float32) * scale
        q_lo = q_offset + qb * q_block
        q_hi = q_lo + q_block
        # kv chunk range that intersects the mask for this q block
        hi_chunk = min(sk, q_hi) if causal else sk
        lo_chunk = max(0, q_lo - window + 1) if window else 0
        c0 = lo_chunk // kv_chunk
        c1 = (hi_chunk + kv_chunk - 1) // kv_chunk
        c1 = max(c1, c0 + 1)

        def step(carry, ck):
            m_prev, l_prev, acc = carry
            kj, vj, cidx = ck
            logits = jnp.einsum(
                "bqhgd,bkhd->bhgqk", qi, kj.astype(jnp.float32)
            )  # (B, Hkv, g, qb, kc)
            qpos = q_lo + jnp.arange(q_block)
            kpos = cidx * kv_chunk + jnp.arange(kv_chunk)
            mask = jnp.ones((q_block, kv_chunk), dtype=bool)
            if causal:
                mask &= qpos[:, None] >= kpos[None, :]
            if window:
                mask &= qpos[:, None] - kpos[None, :] < window
            logits = jnp.where(mask[None, None, None], logits, -1e30)
            m_new = jnp.maximum(m_prev, jnp.max(logits, axis=-1))
            p = jnp.exp(logits - m_new[..., None])
            corr = jnp.exp(m_prev - m_new)
            l_new = l_prev * corr + jnp.sum(p, axis=-1)
            acc = acc * corr[..., None] + jnp.einsum(
                "bhgqk,bkhd->bhgqd", p, vj.astype(jnp.float32)
            )
            return (m_new, l_new, acc), None

        m0 = jnp.full((b, hkv, g, q_block), -jnp.inf, dtype=jnp.float32)
        l0 = jnp.zeros((b, hkv, g, q_block), dtype=jnp.float32)
        a0 = jnp.zeros((b, hkv, g, q_block, dv), dtype=jnp.float32)
        ks = jnp.moveaxis(kc[:, c0:c1], 1, 0)   # (nc, B, kc, Hkv, d)
        vs = jnp.moveaxis(vc[:, c0:c1], 1, 0)
        cidxs = jnp.arange(c0, c1)
        (m, l, acc), _ = jax.lax.scan(step, (m0, l0, a0), (ks, vs, cidxs))
        out = acc / jnp.maximum(l[..., None], 1e-30)
        out = jnp.moveaxis(out, 3, 1).reshape(b, q_block, h, dv)
        outs.append(out.astype(q.dtype))
        if return_lse:
            lses.append(m + jnp.log(jnp.maximum(l, 1e-30)))  # (B,Hkv,g,qb)
    result = jnp.concatenate(outs, axis=1) if len(outs) > 1 else outs[0]
    if return_lse:
        lse = jnp.concatenate(lses, axis=-1) if len(lses) > 1 else lses[0]
        return result, lse
    return result


# ---------------------------------------------------------------------------
# Flash attention with custom VJP: the backward recomputes per-block
# probabilities from (q, k, v, out, lse) instead of letting jax AD save the
# per-chunk S²-sized intermediates of the forward scan.  This is the
# memory-correct training/prefill path (the Pallas kernel mirrors it on TPU).
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def flash_attention_jnp(q, k, v, causal=True, window=0, q_block=1024,
                        kv_chunk=1024, q_offset=0, scale=None):
    out, _ = _flash_fwd(q, k, v, causal, window, q_block, kv_chunk, q_offset, scale)
    return out


def _flash_fwd(q, k, v, causal, window, q_block, kv_chunk, q_offset, scale):
    out, lse = chunked_attention(
        q, k, v, causal=causal, window=window, q_block=q_block,
        kv_chunk=kv_chunk, q_offset=q_offset, return_lse=True, scale=scale,
    )
    return out, (q, k, v, out, lse)


def _fwd_rule(q, k, v, causal, window, q_block, kv_chunk, q_offset, scale):
    out, res = _flash_fwd(q, k, v, causal, window, q_block, kv_chunk, q_offset, scale)
    return out, res


def _bwd_rule(causal, window, q_block, kv_chunk, q_offset, scale, res, dout):
    q, k, v, out, lse = res
    b, sq, h, d = q.shape
    sk, hkv, dv = k.shape[1], k.shape[2], v.shape[-1]
    g = h // hkv
    scale = 1.0 / math.sqrt(d) if scale is None else scale
    q_block = min(q_block, sq)
    kv_chunk = min(kv_chunk, sk)

    qg = q.reshape(b, sq, hkv, g, d).astype(jnp.float32) * scale
    og = out.reshape(b, sq, hkv, g, dv).astype(jnp.float32)
    dog = dout.reshape(b, sq, hkv, g, dv).astype(jnp.float32)
    # D_i = rowsum(dout ∘ out)  (B, S, hkv, g)
    delta = jnp.sum(og * dog, axis=-1)

    dq = jnp.zeros((b, sq, hkv, g, d), jnp.float32)
    dk = jnp.zeros((b, sk, hkv, d), jnp.float32)
    dval = jnp.zeros((b, sk, hkv, dv), jnp.float32)

    nq = sq // q_block
    for cj in range(sk // kv_chunk):
        k_lo = cj * kv_chunk
        kj = k[:, k_lo : k_lo + kv_chunk].astype(jnp.float32)  # (B,kc,hkv,d)
        vj = v[:, k_lo : k_lo + kv_chunk].astype(jnp.float32)
        # q blocks that can see this chunk
        qb0 = (k_lo // q_block) if causal else 0
        qb1 = nq
        if window:
            # q < k_lo + kv_chunk + window
            qb1 = min(
                nq, (k_lo + kv_chunk + window - q_offset + q_block - 1) // q_block
            )
            qb1 = max(qb1, qb0 + 1)
        idxs = jnp.arange(qb0, qb1)

        def step(carry, qi):
            dkj, dvj = carry
            sl = qi * q_block
            qi_blk = jax.lax.dynamic_slice_in_dim(qg, sl, q_block, axis=1)
            do_blk = jax.lax.dynamic_slice_in_dim(dog, sl, q_block, axis=1)
            lse_blk = jax.lax.dynamic_slice_in_dim(lse, sl, q_block, axis=-1)
            dl_blk = jax.lax.dynamic_slice_in_dim(delta, sl, q_block, axis=1)
            logits = jnp.einsum("bqhgd,bkhd->bhgqk", qi_blk, kj)
            p = jnp.exp(logits - lse_blk[..., None])
            qpos = q_offset + sl + jnp.arange(q_block)
            kpos = k_lo + jnp.arange(kv_chunk)
            mask = jnp.ones((q_block, kv_chunk), dtype=bool)
            if causal:
                mask &= qpos[:, None] >= kpos[None, :]
            if window:
                mask &= qpos[:, None] - kpos[None, :] < window
            p = jnp.where(mask[None, None, None], p, 0.0)
            dvj = dvj + jnp.einsum("bhgqk,bqhgd->bkhd", p, do_blk)
            dp = jnp.einsum("bqhgd,bkhd->bhgqk", do_blk, vj)
            ds = p * (dp - jnp.moveaxis(dl_blk, 1, -1)[..., None])
            dq_blk = jnp.einsum("bhgqk,bkhd->bqhgd", ds, kj)
            dkj = dkj + jnp.einsum("bhgqk,bqhgd->bkhd", ds, qi_blk)
            return (dkj, dvj), dq_blk

        dk0 = jnp.zeros((b, kv_chunk, hkv, d), jnp.float32)
        dv0 = jnp.zeros((b, kv_chunk, hkv, dv), jnp.float32)
        (dkj, dvj), dq_blocks = jax.lax.scan(step, (dk0, dv0), idxs)
        # dq_blocks: (nqj, B, q_block, hkv, g, d) -> add into dq
        nqj = qb1 - qb0
        dq_add = jnp.moveaxis(dq_blocks, 0, 1).reshape(
            b, nqj * q_block, hkv, g, d
        )
        dq = jax.lax.dynamic_update_slice_in_dim(
            dq,
            jax.lax.dynamic_slice_in_dim(dq, qb0 * q_block, nqj * q_block, 1)
            + dq_add,
            qb0 * q_block,
            axis=1,
        )
        dk = jax.lax.dynamic_update_slice_in_dim(
            dk,
            jax.lax.dynamic_slice_in_dim(dk, k_lo, kv_chunk, 1) + dkj,
            k_lo,
            axis=1,
        )
        dval = jax.lax.dynamic_update_slice_in_dim(
            dval,
            jax.lax.dynamic_slice_in_dim(dval, k_lo, kv_chunk, 1) + dvj,
            k_lo,
            axis=1,
        )

    dq = (dq * scale).reshape(b, sq, h, d).astype(q.dtype)
    return dq, dk.astype(k.dtype), dval.astype(v.dtype)


flash_attention_jnp.defvjp(_fwd_rule, _bwd_rule)


def attention(
    q, k, v, *, causal=True, window=0, impl="chunked", q_block=1024,
    kv_chunk=1024, q_offset=0,
):
    if impl == "dense" or q.shape[1] * k.shape[1] <= 512 * 512:
        return dense_attention(
            q, k, v, causal=causal, window=window, q_offset=q_offset
        )
    if impl == "pallas":
        from repro.kernels import ops as kops

        return kops.flash_attention(
            q, k, v, causal=causal, window=window, q_offset=q_offset
        )
    return flash_attention_jnp(
        q, k, v, causal, window, q_block, kv_chunk, q_offset
    )


# ---------------------------------------------------------------------------
# Decode attention (single new token against a KV cache)
# ---------------------------------------------------------------------------


def decode_attention_local(
    q: jnp.ndarray,        # (B, H, D)
    k_cache: jnp.ndarray,  # (B, S, Hkv, D)
    v_cache: jnp.ndarray,
    valid_len,             # scalar or (B,) number of valid cache slots
) -> jnp.ndarray:
    """Reference single-token attention over a (local) cache."""
    b, h, d = q.shape
    s, hkv = k_cache.shape[1], k_cache.shape[2]
    g = h // hkv
    scale = 1.0 / math.sqrt(d)
    qg = q.reshape(b, hkv, g, d).astype(jnp.float32) * scale
    logits = jnp.einsum("bhgd,bshd->bhgs", qg, k_cache.astype(jnp.float32))
    pos = jnp.arange(s)
    mask = pos[None] < jnp.reshape(valid_len, (-1, 1))
    logits = jnp.where(mask[:, None, None], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhgs,bshd->bhgd", probs, v_cache.astype(jnp.float32))
    return out.reshape(b, h, d).astype(q.dtype)


def decode_attention_seq_sharded(
    q: jnp.ndarray,        # (B, H, D) replicated over the model axis
    k_cache: jnp.ndarray,  # (B, S_local, Hkv, D) — seq shard of the cache
    v_cache: jnp.ndarray,
    valid_mask: jnp.ndarray,  # (B, S_local) bool — local validity
    axis_name: str,
) -> jnp.ndarray:
    """Distributed flash-softmax decode: runs *inside* shard_map.

    Each model shard holds S/tp cache slots; we compute local partial
    (max, exp-sum, weighted V) and combine with three psums.  This is the
    sequence-parallel decode path used when kv_heads < model-axis size.
    """
    b, h, d = q.shape
    hkv = k_cache.shape[2]
    g = h // hkv
    scale = 1.0 / math.sqrt(d)
    qg = q.reshape(b, hkv, g, d).astype(jnp.float32) * scale
    logits = jnp.einsum("bhgd,bshd->bhgs", qg, k_cache.astype(jnp.float32))
    logits = jnp.where(valid_mask[:, None, None, :], logits, -1e30)
    m_local = jnp.max(logits, axis=-1)
    m = jax.lax.pmax(m_local, axis_name)
    p = jnp.exp(logits - m[..., None])
    # zero out invalid slots exactly (exp(-1e30 - m) may underflow anyway)
    p = jnp.where(valid_mask[:, None, None, :], p, 0.0)
    l = jax.lax.psum(jnp.sum(p, axis=-1), axis_name)
    acc = jnp.einsum("bhgs,bshd->bhgd", p, v_cache.astype(jnp.float32))
    acc = jax.lax.psum(acc, axis_name)
    out = acc / jnp.maximum(l[..., None], 1e-30)
    return out.reshape(b, h, d).astype(q.dtype)
