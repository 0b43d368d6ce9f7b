"""Jit'd public wrappers around the Pallas kernels.

On CPU they run the kernel body in interpret mode (bit-accurate Python
execution) so tests validate the exact kernel logic; on any accelerator
they dispatch the compiled kernels.  Set ``REPRO_FORCE_REF=1`` to bypass
kernels entirely (pure-jnp oracles).
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.bottleneck import bottleneck_eval_fwd
from repro.kernels.compress import int8_roundtrip_fwd, topk_mask_fwd
from repro.kernels.decode_attention import decode_attention_fwd
from repro.kernels.flash_attention import flash_attention_fwd
from repro.kernels.gossip_mix import gossip_mix_fwd
from repro.kernels.rmsnorm import rmsnorm_fwd
from repro.kernels.sdp_proj import rank_k_update_fwd, sdp_subspace_fwd


def interpret_mode() -> bool:
    """The one rule for every Pallas call site: interpret mode on CPU only.

    On an accelerator the kernels compile (a kernel the platform cannot
    compile fails loudly there rather than silently running interpreted),
    and every ``auto`` backend switch picks its kernel exactly when this is
    False.
    """
    return jax.default_backend() == "cpu"


def _force_ref() -> bool:
    return os.environ.get("REPRO_FORCE_REF", "0") == "1"


@functools.partial(jax.jit, static_argnames=("causal", "window", "q_offset"))
def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    q_offset: int = 0):
    """(B, S, H, D) x (B, S, Hkv, D) -> (B, S, H, D) (model layout)."""
    del q_offset  # kernel grid assumes aligned self-attention
    qt = jnp.swapaxes(q, 1, 2)
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)
    if _force_ref():
        out = ref.flash_attention_ref(qt, kt, vt, causal=causal, window=window)
    else:
        out = flash_attention_fwd(
            qt, kt, vt, causal=causal, window=window,
            interpret=interpret_mode(),
        )
    return jnp.swapaxes(out, 1, 2)


@jax.jit
def decode_attention(q, k_cache, v_cache, valid_len):
    """(B, H, D) vs (B, S, Hkv, D) cache -> (B, H, D)."""
    if _force_ref():
        return ref.decode_attention_ref(q, k_cache, v_cache, valid_len)
    return decode_attention_fwd(
        q, k_cache, v_cache, valid_len, interpret=interpret_mode()
    )


@jax.jit
def rmsnorm(x, scale):
    """(..., D) fused RMSNorm."""
    shape = x.shape
    x2 = x.reshape(-1, shape[-1])
    if _force_ref():
        out = ref.rmsnorm_ref(x2, scale)
    else:
        out = rmsnorm_fwd(x2, scale, interpret=interpret_mode())
    return out.reshape(shape)


@jax.jit
def gossip_mix(stacked, weights):
    """(N, L) neighbor params + (N,) weights -> (L,) aggregated params."""
    if _force_ref():
        return ref.gossip_mix_ref(stacked, weights)
    return gossip_mix_fwd(stacked, weights, interpret=interpret_mode())


@jax.jit
def sdp_subspace(Y, V):
    """(n, n) iterate + (n, k) basis -> (Y@V, VᵀYV, ΣY²) in one Y stream."""
    if _force_ref():
        return ref.sdp_subspace_ref(Y, V)
    return sdp_subspace_fwd(Y, V, interpret=interpret_mode())


@jax.jit
def rank_k_update(Y, A, B):
    """(n, n) − (n, k) @ (n, k)ᵀ without materializing the outer product."""
    if _force_ref():
        return ref.rank_k_update_ref(Y, A, B)
    return rank_k_update_fwd(Y, A, B, interpret=interpret_mode())


@jax.jit
def compress_topk(X, thresh):
    """(N, L) deltas + (N,) thresholds -> (msgs, residual) in one stream."""
    if _force_ref():
        return ref.topk_mask_ref(X, thresh)
    return topk_mask_fwd(X, thresh, interpret=interpret_mode())


@jax.jit
def compress_int8(X, scale):
    """(N, L) deltas + (N,) scales -> (dequantized msgs, residual)."""
    if _force_ref():
        return ref.int8_roundtrip_ref(X, scale)
    return int8_roundtrip_fwd(X, scale, interpret=interpret_mode())


@jax.jit
def bottleneck_eval(assign, p, e, C, src, dst):
    """(S, T) sampled assignments -> (S,) Eq. 2 bottleneck times."""
    if _force_ref():
        return ref.bottleneck_eval_ref(assign, p, e, C, src, dst)
    return bottleneck_eval_fwd(
        assign, p, e, C, src, dst, interpret=interpret_mode()
    )
