"""The language-model cell: what it reports, its check driven through the
harness on the CPU at a tiny size (the program passes; the float8 control,
the shared ``no_exchange`` and every fault of ``faults_gossip_lm.py`` come
out not correct), and its operation counts."""

import json
import math

import jax
import pytest

import faults
import faults_gossip_lm
import flops_gossip_lm
import gen_lm
import run

CELL = "fl_dsv2lite16.lora_dpsgd"
SPEC = json.loads((run.ROOT / "BENCHMARK.json").read_text())
SEED = 2**31 + 515_151
KIND = "gossip_lm"
# the cell's widths cut to a CPU test: 3 layers (dense + 2 expert layers),
# 16 experts routed over, 4 held, top-4; 4 users of 8 sequences of 64
# tokens; YaRN kept, its factor over 64 positions
TINY = dict(hidden_size=64, num_attention_heads=4, num_key_value_heads=4,
            intermediate_size=96, kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
            v_head_dim=12, moe_intermediate_size=32, n_routed_experts=4, router_experts=16,
            num_experts_per_tok=4, num_hidden_layers=3, vocab_size=256, users=4,
            sequences_per_user=8, seq_len=64, batch=2,
            lora={"rank": 4, "alpha": 8, "targets": ["q_proj", "kv_a_proj_with_mqa",
                                                       "kv_b_proj", "o_proj"]})


# The loss gap is a mean over a round's tokens, and its rounding noise falls
# as one over the root of their number: the cell's limit holds for its
# 65,536 tokens a round; the tiny cell's 512 take that limit times
# sqrt(65,536 / 512).  The momentum and change gaps are per-leaf ratios and
# keep the cell's limits.
TOKENS_CELL, TOKENS_TINY = 16 * 2 * 2048, 4 * 2 * 64


def tiny():
    cell = run.make_cell({"name": CELL, "chips": 1},
                         run.BENCH / "configs" / "fl_dsv2lite_lora_u16.json", "lm_lora_dpsgd")
    c = cell["config_data"]
    c.update(TINY)
    c["rope_scaling"] = dict(c["rope_scaling"], factor=4, original_max_position_embeddings=64)
    limits = c["checks"][cell["traffic_data"]["checks"]]
    limits["loss_gap"] *= math.sqrt(TOKENS_CELL / TOKENS_TINY)
    return cell


def checks(variant="program"):
    """(correct, every number the check read, the names it compared)."""
    numbers: dict = {}
    if variant in faults.names(KIND):
        with faults.planted(variant, kind=KIND):
            out = run.run_cell(tiny(), SEED, 1.0, False, jax.devices(), readings=numbers)
    else:
        out = run.run_cell(tiny(), SEED, 1.0, False, jax.devices(), variant, readings=numbers)
    return out["correct"], numbers, set(out["checks"])


def test_the_cell_reports_setup_throughput_and_exactly_its_listed_layers():
    cell = run.load_cell(CELL, SPEC)
    assert cell["config_data"]["kind"] == KIND and cell["chips"] == 1
    assert {m["name"] for m in cell["end_to_end"]} == {"setup_s", "fl_samples_per_s"}
    assert {m["name"] for m in cell["per_layer"]} == {
        "fl_step_mfu", "device_idle_share.fl", "round_gap_ms.fl",
        "dispatch_ms_per_round.fl", "mla_ms_per_round.lm", "moe_ms_per_round.lm",
        "expert_matmul_roofline.lm"}
    own = [m for m in SPEC["per_layer"] if m["name"].endswith(".lm")]
    assert own and all(m["workloads"] == [CELL] for m in own)


def test_the_configuration_keeps_the_published_widths_and_states_its_cut():
    c = run.load_cell(CELL, SPEC)["config_data"]
    entry = next(e for e in SPEC["configs"] if e["name"] == "fl_dsv2lite_lora_u16")
    assert entry["reduced"] == c["reduced"] == ["num_hidden_layers", "n_routed_experts",
                                                "vocab_size"]
    assert c["published"] == {"num_hidden_layers": 27, "n_routed_experts": 64,
                              "vocab_size": 102400}
    assert (c["num_hidden_layers"], c["n_routed_experts"], c["vocab_size"]) == (5, 8, 12800)
    assert c["router_experts"] == 64 and c["num_experts_per_tok"] == 6
    assert (c["hidden_size"], c["moe_intermediate_size"], c["intermediate_size"],
            c["kv_lora_rank"], c["qk_nope_head_dim"], c["qk_rope_head_dim"],
            c["v_head_dim"]) == (2048, 1408, 10944, 512, 128, 64, 128)
    assert c["rope_scaling"]["factor"] == 40 and not c["norm_topk_prob"]


def test_the_program_is_correct_and_compares_what_its_configuration_names():
    ok, vals, compared = checks()
    assert ok, vals
    cell = tiny()
    limits = cell["config_data"]["checks"][cell["traffic_data"]["checks"]]
    assert compared == set(limits) | {"window_compiles"}
    assert vals["dropped_slots"] == 0


@pytest.mark.parametrize("variant",
                         ["control", "no_exchange"] + sorted(faults_gossip_lm.FAULTS))
def test_the_float8_control_and_every_fault_are_not_correct(variant):
    ok, vals, _ = checks(variant)
    assert not ok, vals


@pytest.mark.parametrize("name", sorted(faults_gossip_lm.FAULTS))
def test_each_lm_fault_is_found_for_its_kind(name):
    assert faults.lookup(name, KIND) is faults_gossip_lm.FAULTS[name]


def _tiny_config():
    return tiny()["config_data"]


def test_operation_counts_match_a_hand_count_of_the_tiny_widths():
    c, seq, rank = _tiny_config(), 64, 4
    d, h, dqk, dv, kv, f, fe = 64, 4, 24, 12, 32, 96, 32
    proj = d * h * dqk + d * (kv + 8) + kv * h * (16 + dv) + h * dv * d
    core = h * (dqk + dv) * (seq + 1) / 2
    fwd = {"mla": 3 * (proj + core), "dense": 3 * d * f, "shared": 2 * 3 * d * fe * 2,
           "routed": 2 * (4 * 4 / 16) * 3 * d * fe, "router": 2 * d * 16, "head": d * 256}
    assert flops_gossip_lm.forward_macs_per_token(c, seq) == pytest.approx(fwd)
    adapters = 3 * rank * ((d + h * dqk) + (d + kv + 8) + (kv + h * (16 + dv)) + (h * dv + d))
    assert flops_gossip_lm.adapter_count(c, rank) == adapters
    base = sum(fwd.values())
    grads = base + 3 * core - (d * h * dqk + d * (kv + 8))
    want = 2 * seq * (base + grads + adapters + (adapters - 2 * rank * d) + adapters)
    assert flops_gossip_lm.train_flops_per_sequence(c, seq, rank) == pytest.approx(want)


def test_operation_counts_at_the_cell_widths():
    c = run.load_cell(CELL, SPEC)["config_data"]
    per_token = 2 * sum(flops_gossip_lm.forward_macs_per_token(c, 2048).values())
    assert per_token == pytest.approx(568.4e6, rel=1e-3)
    assert flops_gossip_lm.adapter_count(c, 16) == 1_315_840
    assert flops_gossip_lm.train_flops_per_sequence(c, 2048, 16) == pytest.approx(
        2.4207e12, rel=1e-3)
    ops, nbytes = flops_gossip_lm.expert_matmul_cost(c, 1000, 2, 4)
    assert ops == 1000 * 2 * 3 * 2048 * 1408 * 4
    assert nbytes == 4 * (2 * 8 * 3 * 2048 * 1408 * 2 + 1000 * (2 * 2048 + 3 * 1408) * 2)


def test_the_generator_is_seeded_and_draws_from_the_vocabulary():
    c = _tiny_config()
    a = gen_lm.token_data(SEED, 3, 4, 17, 256, 8, 0.3, 4)
    b = gen_lm.token_data(SEED, 3, 4, 17, 256, 8, 0.3, 4)
    assert a.shape == (3, 4, 17) and (a == b).all()
    assert 0 <= int(a.min()) and int(a.max()) < 256
    base = gen_lm.base_weights(SEED, c)
    shapes = jax.tree.map(lambda w: w.shape, base)
    assert shapes["moe"]["moe"]["w_gate"] == (2, 4, 64, 32)
    assert shapes["moe"]["moe"]["router"] == (2, 64, 16)
    assert all(w.dtype == jax.numpy.bfloat16 for w in jax.tree.leaves(base))
    lora = gen_lm.lora_init(SEED, c, 4)
    assert not lora["q"]["b"].any() and lora["q"]["a"].shape == (3, 64, 4)


def test_a_traced_cpu_run_reads_every_metric_of_the_cell_as_a_number_or_nothing():
    cell = tiny()
    cell["per_layer"] = run.load_cell(CELL, SPEC)["per_layer"]
    out = run.run_cell(cell, SEED, 0.5, True, jax.devices())
    assert set(out["metrics"]) <= {m["name"] for m in cell["per_layer"]}
    assert all(math.isfinite(m["value"]) for m in out["metrics"].values())
    assert out["correct"], out["checks"]
