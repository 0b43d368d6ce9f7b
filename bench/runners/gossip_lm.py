"""Runner of the language-model gossip cells: LoRA fine-tuning of a frozen
DeepSeek-V2 base by synchronous D-PSGD rounds of the stacked engine.

Set-up makes the frozen bf16 base, the adapters' common start and every
user's token sequences from the seed (``gen_lm``) and the gossip graph from
the configuration; it builds one ``GossipTrainer`` (stacked engine, ``auto``
mix) whose replica model is the adapters, with the base passed as the
round program's ``frozen`` operand and ``models/mla.py``'s ``user_losses``
as its loss over every user's batch at once.  It drives the trainer through
its first rounds with ``step_round``, the window's own call, on rows that
all differ: they compile the round program and give the readings the
reference is held to (each round's loss, the adapters' momentum after round
1 and their change after the last of them).  The window then calls
``step_round`` back to back, a closed loop of rounds.

The program's expert layer counts on the device, over every round, the
token-slots its held experts computed and the held slots it dropped; the
runner reads the totals once before the window and once after it, and
sets ``expert_slots`` (the window's), ``expert_layer_calls`` and
``expert_matmul_passes`` for ``expert_matmul_roofline.lm``, beside
``users``, ``params_per_user`` (adapters), ``samples_per_round``
(sequences) and ``train_flops_per_sample`` (``flops_gossip_lm``).

After the window the plain reference (``reference/gossip_dsv2lite.py``)
runs the same rounds from the same start on the same rows in float32 at
``highest`` precision.  The check reads: the loss gap (worst round, and
round 1 alone), the momentum and change gaps (the worst and the median
adapter leaf of ``‖got - want‖`` over the larger of ``‖want‖`` and the
median leaf's), and the dropped slots; the configuration's ``checks``
names those it compares and their limits.  With ``variant="control"``
the reference with its base matmuls' operands in float8 takes the
program's place (calibration and tests only).
"""

from __future__ import annotations

import functools
import json
import math
import time

import numpy as np

import flops_gossip_lm
import gen
import gen_lm
from reference import gossip_dsv2lite as ref

# One reference run per seed and setting in a process (calibration runs
# several variants of a seed in one process; the reference is deterministic).
_REFERENCE: dict = {}

# The grouped matmuls run once in the forward pass, once more where the
# layer is recomputed for the backward pass, and twice in the backward pass
# (its own recompute of the hidden activations, then the input gradients).
EXPERT_MATMUL_PASSES = 4


def model_config(c: dict):
    """The program's ``ModelConfig`` of a configuration file, whose
    ``routed_scaling_factor`` and ``rms_norm_eps`` the program holds as
    constants."""
    from repro.models import mla
    from repro.models.common import ModelConfig

    if (c["routed_scaling_factor"], c["rms_norm_eps"]) != (1, mla.RMS_NORM_EPS):
        raise ValueError("the program scales no routed gate weight and takes "
                         f"RMSNorm eps {mla.RMS_NORM_EPS}")
    rs = c["rope_scaling"]
    return ModelConfig(
        name=c["model_type"], family="moe", num_layers=c["num_hidden_layers"],
        d_model=c["hidden_size"], num_heads=c["num_attention_heads"],
        num_kv_heads=c["num_key_value_heads"], d_ff=c["intermediate_size"],
        vocab_size=c["vocab_size"], max_seq_len=c["max_position_embeddings"],
        rope_theta=float(c["rope_theta"]), num_experts=c["router_experts"],
        num_experts_per_tok=c["num_experts_per_tok"], norm_topk_prob=c["norm_topk_prob"],
        moe_d_ff=c["moe_intermediate_size"], num_shared_experts=c["n_shared_experts"],
        first_k_dense=c["first_k_dense_replace"], experts_held=c["n_routed_experts"],
        kv_lora_rank=c["kv_lora_rank"], qk_nope_head_dim=c["qk_nope_head_dim"],
        qk_rope_head_dim=c["qk_rope_head_dim"], v_head_dim=c["v_head_dim"],
        rope_yarn=(float(rs["factor"]), rs["original_max_position_embeddings"],
                   float(rs["beta_fast"]), float(rs["beta_slow"]), rs["mscale"],
                   rs["mscale_all_dim"]))


def _leaf_gaps(got: list, want: list) -> list[float]:
    """Each leaf's ‖got - want‖ over the larger of ‖want‖ and the median
    leaf's."""
    norms = [float(np.linalg.norm(w)) for w in want]
    med = float(np.median(norms))
    out = []
    for g, w, n in zip(got, want, norms):
        diff = float(np.linalg.norm(g - w))
        out.append(diff / max(n, med) if math.isfinite(diff) else math.inf)
    return out


def _median(values: list[float]) -> float:
    return math.inf if not all(map(math.isfinite, values)) else float(np.median(values))


class Runner:
    def __init__(self, ctx):
        self.ctx = ctx
        self.cfg = c = ctx.config
        self.tr = ctx.traffic
        self.rank = c["lora"]["rank"]
        self.lora_scale = c["lora"]["alpha"] / self.rank
        ctx.counters.update(
            users=c["users"],
            samples_per_round=c["users"] * self.tr["local_steps"] * c["batch"],
            params_per_user=flops_gossip_lm.adapter_count(c, self.rank),
            train_flops_per_sample=flops_gossip_lm.train_flops_per_sequence(
                c, c["seq_len"], self.rank),
            expert_matmul_passes=EXPERT_MATMUL_PASSES)
        self.dropped = 0

    # -- what the generator makes from the seed --------------------------------
    def _tokens(self):
        c, d = self.cfg, self.cfg["data"]
        return gen_lm.token_data(self.ctx.seed, c["users"], c["sequences_per_user"],
                                 c["seq_len"] + 1, c["vocab_size"], d["topics"],
                                 d["dirichlet_alpha"], d["markov_branch"])

    def _reference(self, precision: str) -> dict:
        c, t = self.cfg, self.tr
        key = (self.ctx.seed, precision, json.dumps(c, sort_keys=True),
               json.dumps(t, sort_keys=True))
        if key not in _REFERENCE:
            tokens = np.asarray(self._tokens())
            base = gen_lm.base_weights(self.ctx.seed, c)
            _REFERENCE[key] = ref.run(
                base, gen_lm.lora_init(self.ctx.seed, c, self.rank), tokens, self.edges, c,
                rounds=t["reference_rounds"], batch=c["batch"] * t["local_steps"],
                lr=c["lr"], momentum=c["momentum"], self_weight=c["self_weight"],
                lora_scale=self.lora_scale, precision=precision)
            del base
        return _REFERENCE[key]

    def setup(self):
        c, ctx, t = self.cfg, self.ctx, self.tr
        if t["local_steps"] != 1:
            raise ValueError("the reference runs one local step a round")
        if t["reference_rounds"] * c["batch"] > c["sequences_per_user"]:
            raise ValueError("the compared rounds must read rows that all differ: "
                             "reference_rounds x batch > sequences_per_user")
        self.edges = gen.gossip_edges(c["graph_seed"], c["users"], *c["out_degree"])
        if ctx.variant == "control":
            self.readings = self._reference(c["control"]["precision"])
            self.trainer = None
            return
        with self._precision():
            self._build()
            self._first_rounds()

    def _precision(self):
        import jax

        return jax.default_matmul_precision(self.cfg["matmul_precision"])

    def _build(self):
        import jax

        from repro.core import TaskGraph
        from repro.data.synthetic import TokenDataset
        from repro.fl.gossip import GossipConfig, GossipTrainer
        from repro.models import mla

        c, t, ctx = self.cfg, self.tr, self.ctx
        mcfg = model_config(c)
        with ctx.part("data and weights"):
            tokens = np.asarray(self._tokens())
            shards = [TokenDataset.from_sequences(tokens[u]) for u in range(c["users"])]
            frozen = gen_lm.base_weights(ctx.seed, c)
            adapters0 = gen_lm.lora_init(ctx.seed, c, self.rank)
            self.leaves0 = [np.asarray(l) for l in jax.tree.leaves(adapters0)]
        gcfg = GossipConfig(
            local_steps=t["local_steps"], batch_size=c["batch"], lr=c["lr"],
            momentum=c["momentum"], aggregate_self_weight=c["self_weight"],
            compressor=None, backend="stacked", mix_backend="auto")
        with ctx.part("build trainer"):
            self.trainer = GossipTrainer(
                TaskGraph(p=np.ones(c["users"]), edges=self.edges),
                lambda _key: adapters0,
                functools.partial(mla.user_loss, cfg=mcfg, lora_scale=self.lora_scale),
                shards, gcfg, seed=c["trainer_seed"], frozen=frozen,
                user_losses=functools.partial(mla.user_losses, cfg=mcfg,
                                              lora_scale=self.lora_scale))

    def _totals(self) -> tuple[np.ndarray, int]:
        """The expert layer's device counters so far (one host read)."""
        aux = self.trainer.aux_totals
        return np.asarray(aux["expert_slots"], np.int64), int(aux["dropped_slots"])

    def _first_rounds(self):
        import jax

        tr = self.trainer
        losses, mom = [], None
        with self.ctx.part("first rounds (compile, reference readings)"):
            for r in range(self.tr["reference_rounds"]):
                losses.append(tr.step_round()["mean_loss"])
                if r == 0:
                    mom = [np.asarray(l) for l in jax.tree.leaves(tr._state[1])]
            change = [np.asarray(l) - l0[None]
                      for l, l0 in zip(jax.tree.leaves(tr._state[0]), self.leaves0)]
            self.slots_before, _ = self._totals()
        self.readings = {"losses": losses, "momentum": mom, "change": change}
        self.ctx.counters["mix_backend"] = tr.mix_backend

    def serve(self, seconds: float):
        ctx, tr = self.ctx, self.trainer
        if tr is None:          # the control has no window
            return
        rounds = bad = 0
        with self._precision():
            t0 = time.perf_counter()
            while time.perf_counter() - t0 < seconds:
                with ctx.span("bench.round"):
                    loss = tr.step_round()["mean_loss"]
                rounds += 1
                bad += not math.isfinite(loss)
            window = time.perf_counter() - t0
        slots, self.dropped = self._totals()
        moe_layers = self.cfg["num_hidden_layers"] - self.cfg["first_k_dense_replace"]
        ctx.records.update(window_s=window, completed=rounds, attempted=rounds,
                           failed=bad, samples=rounds * ctx.counters["samples_per_round"])
        ctx.counters.update(
            rounds=rounds, dispatches_per_round=tr.last_round_dispatches,
            expert_slots=int((slots - self.slots_before).sum()),
            expert_layer_calls=rounds * self.tr["local_steps"] * moe_layers,
            dropped_slots=self.dropped,
            expert_load_max_over_mean=float(slots.max() / max(slots.mean(), 1e-9)))

    def release(self):
        self.trainer = None
        self.leaves0 = None

    def check(self) -> list[tuple[str, float, float | None]]:
        """Every number the comparison reads, each with the limit the
        configuration holds it to (``None``: read, not compared)."""
        lim = self.cfg["checks"][self.tr["checks"]]
        want = self._reference("highest")
        got = self.readings
        loss = [(abs(g - w) / abs(w) if math.isfinite(g) else math.inf)
                for g, w in zip(got["losses"], want["losses"])]
        mom = _leaf_gaps(got["momentum"], want["momentum"])
        change = _leaf_gaps(got["change"], want["change"])
        numbers = {"loss_gap": max(loss), "first_loss_gap": loss[0],
                   "momentum_gap": max(mom), "momentum_gap_median": _median(mom),
                   "change_gap": max(change), "change_gap_median": _median(change),
                   "dropped_slots": float(self.dropped)}
        self.ctx.counters["loss_gap_by_round"] = loss
        self.ctx.counters["momentum_gap_by_leaf"] = mom
        self.ctx.counters["change_gap_by_leaf"] = change
        return [(k, v, lim.get(k)) for k, v in numbers.items()]
