"""The top-k threshold of the compress stage: the radix select against
``jax.lax.top_k``, and the stage's pallas lane against its jnp lane.

``topk_threshold`` must return each row's k-th largest |x| bit for bit,
since the mask kernel keeps ``|x| >= thresh``: a threshold one ulp off
keeps another entry.  A NaN ranks above +inf in both (by bit pattern in
the select, as ``lax.top_k`` orders it); a row whose k-th largest |x| is
NaN gets a NaN threshold and the mask keeps nothing of it.  That is
documented, not asserted.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.graphs import gossip_task_graph
from repro.data.synthetic import image_dataset
from repro.fl import gossip
from repro.fl.cnn import cnn_loss, init_cnn_params
from repro.kernels.compress import SELECT_SCOPE, topk_threshold
from repro.train.compression import Int8, TopK

# leaf lengths of the paper's CNN on 32x32x3 inputs (fl/cnn.py), at the
# benchmark's 64 users, then odd shapes
CNN_LEAF_LENGTHS = (864, 32, 18432, 64, 524288, 128, 8192, 64, 640, 10)
SHAPES = [(64, l) for l in CNN_LEAF_LENGTHS] + [(3, 7), (1, 1), (5, 129), (9, 1000)]


def _rows(shape, contents, seed=0):
    """f32 rows of N(0, 1) at scales from 1e-4 to 1.  ``special`` gives row
    r the case r % 7: all zeros, ±0.0, half zeros, ties, ±inf among the
    values, subnormals and normals mixed, or plain."""
    n, l = shape
    rng = np.random.default_rng(seed)
    x = rng.standard_normal(shape) * 10.0 ** rng.uniform(-4, 0, (n, 1))
    x = x.astype(np.float32)
    if contents == "special":
        for r in range(n):
            case = r % 7
            if case == 0:
                x[r] = 0.0
            elif case == 1:
                x[r] = np.where(rng.random(l) < 0.5, -0.0, 0.0)
            elif case == 2:
                x[r, rng.random(l) < 0.5] = 0.0
            elif case == 3:
                x[r] = rng.integers(-3, 4, l) * 0.25
            elif case == 4:
                x[r, rng.integers(0, l, 3)] = np.inf
                x[r, rng.integers(0, l, 2)] = -np.inf
            elif case == 5:
                tiny = (rng.standard_normal(l) * 1e-39).astype(np.float32)
                x[r] = np.where(rng.random(l) < 0.5, tiny, x[r])
    return jnp.asarray(x)


def _bits(a):
    return np.asarray(a).view(np.uint32 if a.dtype == jnp.float32 else np.uint16)


@pytest.mark.parametrize("contents", ["normal", "special"])
@pytest.mark.parametrize("k", ["one", "five_pct", "all"])
@pytest.mark.parametrize("shape", SHAPES, ids=lambda s: f"{s[0]}x{s[1]}")
def test_topk_threshold_is_bit_equal_to_top_k(shape, k, contents):
    x = _rows(shape, contents)
    l = shape[1]
    k = {"one": 1, "five_pct": max(1, int(0.05 * l)), "all": l}[k]
    want = jax.lax.top_k(jnp.abs(x), k)[0][:, -1]
    got = jax.jit(topk_threshold, static_argnums=1)(x, k)
    np.testing.assert_array_equal(_bits(got), _bits(want))


@pytest.mark.parametrize("l", [7, 864])
def test_topk_threshold_bf16_is_bit_equal_to_top_k(l):
    x = _rows((8, l), "special").astype(jnp.bfloat16)
    k = max(1, l // 20)
    got = topk_threshold(x, k)
    assert got.dtype == jnp.bfloat16
    np.testing.assert_array_equal(
        _bits(got), _bits(jax.lax.top_k(jnp.abs(x), k)[0][:, -1]))


# ---------------------------------------------------------------------------
# the compress stage: pallas lane (interpret mode here) against jnp lane
# ---------------------------------------------------------------------------


def _trainer(compress_backend, compressor=TopK(fraction=0.05)):
    rng = np.random.default_rng(0)
    tg = gossip_task_graph(rng, 4, degree_low=2, degree_high=3)
    train, _ = image_dataset("mnist", 256, seed=0)
    cfg = gossip.GossipConfig(local_steps=1, batch_size=32,
                              compressor=compressor,
                              compress_backend=compress_backend)
    return gossip.GossipTrainer(
        tg, lambda k: init_cnn_params(k, (28, 28, 1), 10), cnn_loss,
        train.split(4, rng), cfg, seed=0,
    )


@pytest.mark.parametrize("compressor", [TopK(fraction=0.05), Int8()],
                         ids=["topk", "int8"])
def test_compress_stage_pallas_lane_equals_jnp_lane(compressor):
    pallas, ref = _trainer("pallas", compressor), _trainer("jnp", compressor)
    params = pallas._state[0]
    leaves, treedef = jax.tree.flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(1), 2 * len(leaves))
    # a stacked delta of every CNN leaf, zero-initialised biases included
    params = treedef.unflatten([
        l + 1e-2 * jax.random.normal(kk, l.shape) for l, kk in zip(leaves, keys)])
    residual = treedef.unflatten([
        1e-3 * jax.random.normal(kk, l.shape)
        for l, kk in zip(leaves, keys[len(leaves):])])
    got_msgs, got_resid = jax.jit(pallas._make_compress_stage())(params, residual)
    want_msgs, want_resid = jax.jit(ref._make_compress_stage())(params, residual)
    for g, w in zip(jax.tree.leaves(got_msgs), jax.tree.leaves(want_msgs), strict=True):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    for g, w in zip(jax.tree.leaves(got_resid), jax.tree.leaves(want_resid), strict=True):
        if isinstance(compressor, TopK):
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
        else:   # 1 ulp of |x| apart at most (FMA contraction, kernels/compress.py)
            np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=0, atol=2e-7)


# a sort op, or the CPU backend's TopK custom call that lax.top_k becomes
SORT = re.compile(r'= \S+ sort\(|custom_call_target="TopK"')


def _round_hlo(trainer) -> str:
    return trainer._round_jit.lower(trainer._state, *trainer._data).compile().as_text()


def test_topk_round_of_the_pallas_lane_has_no_sort():
    hlo = _round_hlo(_trainer("pallas"))
    assert SORT.search(hlo) is None
    # the search is read as part of the compress stage, under a scope of its own
    assert f"/{gossip.STAGE_COMPRESS}/{SELECT_SCOPE}/" in hlo
    assert SORT.search(_round_hlo(_trainer("jnp")))    # the check can see a sort
