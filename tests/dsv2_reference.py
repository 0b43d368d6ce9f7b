"""The plain DeepSeek-V2 LoRA reference for the CPU tests: the benchmark's
float32 reference (``bench/reference/gossip_dsv2lite.py``, which imports
nothing of the program) and its seeded generator (``bench/gen_lm.py``),
with the configuration of a ``ModelConfig`` in the keys of the model's
``config.json``."""

from __future__ import annotations

import importlib.util
import pathlib
import sys

import jax
import numpy as np

from repro.data.synthetic import TokenDataset
from repro.models import mla
from repro.models.common import ModelConfig

_BENCH = pathlib.Path(__file__).resolve().parents[1] / "bench"
if str(_BENCH) not in sys.path:
    sys.path.append(str(_BENCH))
import gen_lm  # noqa: E402

_spec = importlib.util.spec_from_file_location(
    "dsv2_plain_reference", _BENCH / "reference" / "gossip_dsv2lite.py")
ref = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(ref)


def config_dict(cfg: ModelConfig) -> dict:
    """``cfg`` in ``config.json``'s keys, as the reference and the generator
    read them."""
    factor, orig, beta_fast, beta_slow, mscale, mscale_all_dim = cfg.rope_yarn
    return {
        "hidden_size": cfg.d_model, "intermediate_size": cfg.d_ff,
        "num_hidden_layers": cfg.num_layers, "first_k_dense_replace": cfg.first_k_dense,
        "vocab_size": cfg.vocab_size,
        "num_attention_heads": cfg.num_heads, "qk_nope_head_dim": cfg.qk_nope_head_dim,
        "qk_rope_head_dim": cfg.qk_rope_head_dim, "v_head_dim": cfg.v_head_dim,
        "kv_lora_rank": cfg.kv_lora_rank, "rms_norm_eps": mla.RMS_NORM_EPS,
        "num_experts_per_tok": cfg.num_experts_per_tok, "rope_theta": cfg.rope_theta,
        "rope_scaling": {"factor": factor, "original_max_position_embeddings": orig,
                         "beta_fast": beta_fast, "beta_slow": beta_slow, "mscale": mscale,
                         "mscale_all_dim": mscale_all_dim},
        "moe_intermediate_size": cfg.moe_d_ff, "n_shared_experts": cfg.num_shared_experts,
        "n_routed_experts": cfg.experts_held or cfg.num_experts,
        "router_experts": cfg.num_experts,
        "norm_topk_prob": cfg.norm_topk_prob,
        "routed_scaling_factor": 1,
    }


def model(cfg: ModelConfig, lora_scale: float, seq: int) -> dict:
    """The reference's ``attention``, ``experts`` and ``loss`` at ``cfg``."""
    return ref.make_model(config_dict(cfg), lora_scale, seq)


def base_weights(seed: int, cfg: ModelConfig) -> dict:
    """The generator's bf16 frozen base at ``cfg``."""
    return gen_lm.base_weights(seed, config_dict(cfg))


def lora_init(seed: int, cfg: ModelConfig, rank: int) -> dict:
    """The generator's LoRA start at ``cfg`` (``B`` zero)."""
    return gen_lm.lora_init(seed, config_dict(cfg), rank)


def token_shards(seed: int, users: int, seqs: int, seq_len: int,
                 vocab: int) -> list[TokenDataset]:
    """Each user's ``seqs`` sequences of ``seq_len`` + 1 generated ids (8
    Markov topics, Dirichlet(0.3) mixes, 4 successors an id)."""
    tokens = np.asarray(gen_lm.token_data(seed, users, seqs, seq_len + 1, vocab, 8, 0.3, 4))
    return [TokenDataset.from_sequences(t) for t in tokens]


def f32(tree):
    return jax.tree.map(lambda a: a.astype("float32"), tree)
