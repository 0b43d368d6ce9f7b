"""DeepSeek-V2 latent attention with YaRN, the dropless expert layer over
the experts one chip holds, and gossip LoRA rounds over a frozen base,
against the plain float32 reference on the CPU at smoke sizes."""

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dsv2_reference import base_weights, f32, lora_init, model, ref, token_shards
from repro.configs import get_config, get_smoke_config
from repro.core import TaskGraph
from repro.data.synthetic import TokenDataset, stack_shards
from repro.fl.gossip import GossipConfig, GossipTrainer
from repro.models import mla, moe

CFG = get_smoke_config("deepseek-v2-lite")
RANK, SCALE = 4, 2.0
SEQ = 64


@pytest.fixture(scope="module")
def base():
    return f32(base_weights(0, CFG))


def adapters(key, cfg=CFG, rank=RANK):
    """A LoRA state with B non-zero, so that every adapter path counts."""
    ad = lora_init(1, cfg, rank)
    return jax.tree.map(
        lambda a: a if a.any() else 0.05 * jax.random.normal(key, a.shape), ad)


def layer_adapters(ad, i):
    return jax.tree.map(lambda a: a[i], ad)


def test_published_configuration():
    c = get_config("deepseek-v2-lite")
    assert (c.d_model, c.num_heads, c.kv_lora_rank, c.qk_nope_head_dim,
            c.qk_rope_head_dim, c.v_head_dim) == (2048, 16, 512, 128, 64, 128)
    assert (c.num_experts, c.moe_d_ff, c.num_experts_per_tok, c.num_shared_experts) == (
        64, 1408, 6, 2)
    assert not c.norm_topk_prob and mla.RMS_NORM_EPS == 1e-6
    assert (c.first_k_dense, c.d_ff, c.vocab_size, c.num_layers) == (1, 10944, 102400, 27)
    assert c.rope_yarn == (40.0, 4096, 32.0, 1.0, 0.707, 0.707)


def test_yarn_frequencies_and_mscale_follow_the_formulas():
    c = get_config("deepseek-v2-lite")
    inv = mla.yarn_inv_freq(64, 10000.0, c.rope_yarn)
    i = np.arange(32)
    f_extra = 10000.0 ** (-2 * i / 64)

    def corr(r):
        return 64 * math.log(4096 / (r * 2 * math.pi)) / (2 * math.log(10000))

    low, high = max(math.floor(corr(32)), 0), min(math.ceil(corr(1)), 63)
    assert (low, high) == (10, 23)
    m = 1 - np.clip((i - low) / (high - low), 0, 1)
    np.testing.assert_allclose(inv, f_extra / 40 * (1 - m) + f_extra * m, rtol=1e-12)
    assert inv[0] == 1.0 and inv[-1] == pytest.approx(f_extra[-1] / 40)
    assert mla.yarn_mscale(40, 0.707) == pytest.approx(1.2608, abs=5e-5)
    assert mla.softmax_scale(c) * math.sqrt(192) == pytest.approx(1.2608 ** 2, rel=1e-4)
    cos, _ = mla.rope_tables(c, 8)          # mscale / mscale_all_dim = 1
    assert float(cos[0, 0]) == 1.0


def test_mla_block_output_and_input_gradient_match_the_reference(base):
    p = base["moe"]["attn"]
    p = jax.tree.map(lambda a: a[0], p)
    ad = layer_adapters(adapters(jax.random.PRNGKey(2)), 1)
    x = jax.random.normal(jax.random.PRNGKey(3), (1, 2, SEQ, CFG.d_model))
    cot = jax.random.normal(jax.random.PRNGKey(4), x.shape)
    cos, sin = mla.rope_tables(CFG, SEQ)
    plain = model(CFG, SCALE, SEQ)["attention"]
    with jax.default_matmul_precision("highest"):
        got, vjp = jax.vjp(lambda t: mla.mla_attention(
            p, jax.tree.map(lambda a: a[None], ad), t, CFG, cos, sin, SCALE), x)
        want, vjp_ref = jax.vjp(lambda t: plain(p, ad, t[0])[None], x)
        np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
        np.testing.assert_allclose(vjp(cot)[0], vjp_ref(cot)[0], rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("impl", ["chunked", "flash"])
def test_attention_with_a_v_head_dim_of_its_own(impl):
    from repro.models.attention import chunked_attention, dense_attention, flash_attention_jnp

    kq, kk, kv, kc = jax.random.split(jax.random.PRNGKey(5), 4)
    q = jax.random.normal(kq, (2, 128, 4, 24))
    k = jax.random.normal(kk, (2, 128, 4, 24))
    v = jax.random.normal(kv, (2, 128, 4, 16))
    cot = jax.random.normal(kc, (2, 128, 4, 16))
    scale = 0.37

    def run(fn):
        out, vjp = jax.vjp(fn, q, k, v)
        return out, vjp(cot)

    with jax.default_matmul_precision("highest"):
        want = run(lambda q, k, v: dense_attention(q, k, v, scale=scale))
        if impl == "chunked":
            got = run(lambda q, k, v: chunked_attention(q, k, v, q_block=32, kv_chunk=32,
                                                        scale=scale))
        else:
            got = run(lambda q, k, v: flash_attention_jnp(q, k, v, True, 0, 32, 32, 0, scale))
    assert got[0].shape == (2, 128, 4, 16)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(g, w, rtol=1e-4, atol=1e-4)


def _experts(base, layer=0):
    return jax.tree.map(lambda a: a[layer], base["moe"]["moe"])


def test_eight_expert_shares_add_up_to_the_uncut_layer():
    """The partial outputs of the 8 chips that share a layer, each holding
    E/8 experts, with the shared experts counted once, sum to the layer
    whose every expert is held."""
    full = CFG.replace(experts_held=0)
    whole = f32(base_weights(6, full))
    p = _experts(whole)
    x = jax.random.normal(jax.random.PRNGKey(7), (256, CFG.d_model))
    e, shares = CFG.num_experts, 8
    span = e // shares
    with jax.default_matmul_precision("highest"):
        parts = [moe.moe_dropless({**p, **{n: p[n][s * span:(s + 1) * span]
                                           for n in ("w_gate", "w_up", "w_down")}},
                                  x, CFG, s * span, span)[0] for s in range(shares)]
        got = sum(parts) + mla.shared_experts(p["shared"], x)
        want = model(full, SCALE, SEQ)["experts"](p, x)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("rows_per_block", [64, 4096])
def test_dropless_layer_under_a_router_that_sends_every_token_to_one_expert(
        base, rows_per_block):
    p = dict(_experts(base))
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(8), (512, CFG.d_model)))
    # every token's top choices are experts 0..k-1, all of them held
    bias = 10.0 * jnp.arange(CFG.num_experts, 0, -1, dtype=jnp.float32)
    p["router"] = jnp.zeros_like(p["router"]) + bias[None, :] / CFG.d_model
    with jax.default_matmul_precision("highest"):
        out, stats = moe.moe_dropless(p, x, CFG, 0, CFG.experts_held,
                                      rows_per_block=rows_per_block)
        routed, vjp = jax.vjp(lambda t: moe.moe_dropless(
            p, t, CFG, 0, CFG.experts_held, rows_per_block=rows_per_block)[0], x)
        plain = model(CFG, SCALE, SEQ)["experts"]
        want, vjp_ref = jax.vjp(lambda t: plain(p, t) - mla.shared_experts(p["shared"], t), x)
    assert int(stats["dropped_slots"]) == 0
    assert stats["expert_slots"].tolist()[:CFG.num_experts_per_tok] == [512] * 4
    assert int(stats["expert_slots"].sum()) == 512 * CFG.num_experts_per_tok
    np.testing.assert_allclose(out, want, rtol=1e-5, atol=1e-5)
    cot = jax.random.normal(jax.random.PRNGKey(9), out.shape)
    with jax.default_matmul_precision("highest"):
        np.testing.assert_allclose(vjp(cot)[0], vjp_ref(cot)[0], rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("rows_per_block", [64, 4096])
def test_dropped_slots_count_the_rows_the_blocks_leave_out(base, monkeypatch, rows_per_block):
    """The counter reads what the blocks ran: blocks that leave out the last
    held expert's rows show those rows as dropped."""
    p = _experts(base)
    x = jax.random.normal(jax.random.PRNGKey(15), (512, CFG.d_model))
    orig = moe._block_sizes
    monkeypatch.setattr(moe, "_block_sizes",
                        lambda *a: orig(*a).at[-1].set(0))
    _, stats = moe.moe_dropless(p, x, CFG, 0, CFG.experts_held,
                                rows_per_block=rows_per_block)
    left_out = int(stats["expert_slots"][-1])
    assert left_out > 0 and int(stats["dropped_slots"]) == left_out


def test_top_k_renormalisation_is_a_config_field():
    probs = jax.nn.softmax(jax.random.normal(jax.random.PRNGKey(10), (5, 16)), axis=-1)
    cfg = get_smoke_config("olmoe-1b-7b").replace(num_experts=16, num_experts_per_tok=4)
    assert cfg.norm_topk_prob            # the default: existing configs unchanged
    vals, _ = moe.topk_gates(probs, cfg)
    np.testing.assert_allclose(vals.sum(-1), 1.0, rtol=1e-6)
    raw, idx = moe.topk_gates(probs, cfg.replace(norm_topk_prob=False))
    np.testing.assert_array_equal(raw, jnp.take_along_axis(probs, idx, -1))
    np.testing.assert_array_equal(idx, jax.lax.top_k(probs, 4)[1])


def _graph(users):
    return TaskGraph(p=np.ones(users), edges=tuple(
        (i, (i + s) % users) for i in range(users) for s in (1, 2)))


def _trainer(base, backend, users=4, shards=None, ad0=None):
    shards = shards or token_shards(11, users, 8, SEQ, CFG.vocab_size)
    ad0 = ad0 if ad0 is not None else lora_init(12, CFG, RANK)
    gcfg = GossipConfig(local_steps=1, batch_size=2, lr=0.05, momentum=0.9)
    return GossipTrainer(
        _graph(users), lambda _k: ad0,
        functools.partial(mla.user_loss, cfg=CFG, lora_scale=SCALE), shards, gcfg,
        backend=backend, frozen=base,
        user_losses=functools.partial(mla.user_losses, cfg=CFG, lora_scale=SCALE))


def test_stacked_lora_rounds_equal_the_reference_engine_and_the_plain_reference(base):
    users, rounds = 4, 2
    shards = token_shards(11, users, 8, SEQ, CFG.vocab_size)
    ad0 = lora_init(12, CFG, RANK)
    got = {}
    with jax.default_matmul_precision("highest"):
        for backend in ("stacked", "reference"):
            tr = _trainer(base, backend, users, shards, ad0)
            losses, mom = [], None
            for r in range(rounds):
                losses.append(tr.step_round()["mean_loss"])
                if r == 0:
                    mom = (tr._state[1] if backend == "stacked" else
                           jax.tree.map(lambda *u: jnp.stack(u), *tr.opt_state))
            got[backend] = (losses, jax.tree.map(lambda *u: jnp.stack(u), *tr.params), mom)
        tokens = np.stack([np.concatenate([s.x, s.y[:, -1:]], axis=1) for s in shards])
        want = ref.run(base, ad0, tokens, _graph(users).edges, _cfg_dict(), rounds=rounds,
                       batch=2, lr=0.05, momentum=0.9, self_weight=0.5, lora_scale=SCALE)
    for backend, (losses, params, mom) in got.items():
        np.testing.assert_allclose(losses, want["losses"], rtol=1e-5)
        for g, l0, w in zip(jax.tree.leaves(params), jax.tree.leaves(ad0), want["change"]):
            np.testing.assert_allclose(g - l0[None], w, rtol=2e-3, atol=1e-6)
        for g, w in zip(jax.tree.leaves(mom), want["momentum"]):
            np.testing.assert_allclose(g, w, rtol=2e-3, atol=1e-6)


def _cfg_dict():
    from dsv2_reference import config_dict

    return config_dict(CFG)


def test_the_frozen_base_is_an_argument_not_a_constant():
    """The round program's text does not grow with the base: a base with a
    vocabulary 256 times wider lowers to the same size of text, which is far
    smaller than the base."""
    sizes = []
    for vocab in (256, 65536):
        cfg = CFG.replace(vocab_size=vocab)
        frozen = base_weights(0, cfg)
        shards = token_shards(1, 2, 4, 16, vocab)
        gcfg = GossipConfig(local_steps=1, batch_size=2)
        tr = GossipTrainer(
            TaskGraph(p=np.ones(2), edges=((0, 1), (1, 0))),
            lambda _k: lora_init(1, cfg, RANK),
            functools.partial(mla.user_loss, cfg=cfg, lora_scale=SCALE), shards, gcfg,
            frozen=frozen,
            user_losses=functools.partial(mla.user_losses, cfg=cfg, lora_scale=SCALE))
        assert len(tr._data) == 3 and tr._data[2] is not None
        text = tr._round_jit.lower(tr._state, *tr._data).as_text()
        sizes.append(len(text))
    base_bytes = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(frozen))
    assert sizes[1] < base_bytes / 20, (sizes, base_bytes)
    assert abs(sizes[1] - sizes[0]) < 0.01 * sizes[0], sizes


def test_the_cnn_round_takes_no_frozen_operand():
    from repro.data.synthetic import image_dataset
    from repro.fl.cnn import cnn_loss, init_cnn_params

    train, _ = image_dataset("cifar10", 4 * 32, seed=0)
    shards = train.split(4, np.random.default_rng(0))
    tr = GossipTrainer(_graph(4), lambda k: init_cnn_params(k, (32, 32, 3), 10), cnn_loss,
                       shards, GossipConfig(local_steps=1, batch_size=16))
    assert len(tr._data) == 2 and len(tr._state) == 6 and tr.aux_totals is None
    lowered = tr._round_jit.lower(tr._state, *tr._data)
    assert len(jax.tree.leaves(lowered.args_info)) == len(jax.tree.leaves(tr._state)) + 2


def test_the_lora_round_counts_expert_slots_on_the_device(base):
    tr = _trainer(base, "stacked")
    for _ in range(2):
        tr.step_round()
    aux = tr.aux_totals
    n_moe = CFG.num_layers - CFG.first_k_dense
    assert aux["expert_slots"].shape == (n_moe, CFG.experts_held)
    assert int(aux["dropped_slots"]) == 0
    # 2 rounds x 4 users x 2 sequences x 64 tokens x 4 choices, over the
    # expert layers: the held half of the 16 experts gets some of them
    total = 2 * 4 * 2 * SEQ * CFG.num_experts_per_tok * n_moe
    assert 0 < int(aux["expert_slots"].sum()) < total


def test_token_shards_stack_and_differ_by_user():
    shards = token_shards(4, 3, 5, 32, 100)
    assert all(isinstance(s, TokenDataset) for s in shards)
    xs, ys = stack_shards(shards)
    assert xs.shape == ys.shape == (3, 5, 32) and xs.dtype == np.int32
    np.testing.assert_array_equal(xs[:, :, 1:], ys[:, :, :-1])
    assert xs.max() < 100
    assert not np.array_equal(shards[0].x, shards[1].x)


@pytest.mark.parametrize("transpose_rhs", [False, True])
def test_the_grouped_matmul_kernel_zeroes_the_rows_past_its_groups(monkeypatch, transpose_rhs):
    """The TPU path (the megablox kernel, here in interpret mode, which fills
    the rows it leaves unwritten with NaN) equals ``lax.ragged_dot``: each
    group's rows times its matrix, and zero past the last group."""
    import importlib

    import repro.kernels.ops as kops

    # the module, which its package's function of the same name shadows
    megablox = importlib.import_module("jax.experimental.pallas.ops.tpu.megablox.gmm")

    lhs = jax.random.normal(jax.random.PRNGKey(13), (1024, 512))
    rhs = jax.random.normal(jax.random.PRNGKey(14), (4, 512, 256))
    sizes = jnp.array([100, 0, 200, 50], jnp.int32)
    want = jax.lax.ragged_dot(lhs, rhs, sizes)
    monkeypatch.setattr(kops, "interpret_mode", lambda: False)
    monkeypatch.setattr(megablox, "gmm", functools.partial(megablox.gmm, interpret=True))
    with jax.default_matmul_precision("highest"):
        got = moe.grouped_matmul(lhs, jnp.swapaxes(rhs, 1, 2) if transpose_rhs else rhs,
                                 sizes, transpose_rhs=transpose_rhs)
    assert not jnp.any(got[350:])
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-3)
