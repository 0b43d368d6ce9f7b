"""Runner of the gossip-FL cells: synchronous rounds of the stacked engine.

Set-up makes the users' data and the CNN's common start from the seed and
the gossip graph from the configuration, builds one ``GossipTrainer``
(stacked engine, ``auto`` mix and compression), and drives it through its
first rounds with ``step_round``, the window's own call, on rows that all
differ.  The round program holds the mixing matrix and the trainer's
reshuffle keys as constants, so the graph and the trainer's seed are fixed
by the configuration: a new seed then finds the round program in the
cache.  Those rounds compile the round program and give the readings the
reference is held to: each round's loss, the momentum after round 1 and
the parameters' change after the last of them.  The window then calls
``step_round`` back to back, a closed loop of rounds.  The counts the
shared readers take (samples a round, parameters a user, training
operations a sample) follow from the configuration's widths, so the
constructor sets them.

The program runs under the matmul precision its configuration states
(``jax.default_matmul_precision``).  After the window the plain reference
(``reference/gossip_cnn.py``) runs the same rounds from the same start on
the same rows in float32 at ``highest`` precision.  The check reads six
numbers from the two: the loss gap (worst round, and round 1 alone), and
the momentum and change gaps (worst leaf, and median leaf).  The
configuration's ``checks`` table, per traffic mix, names those it compares
and their limits; the others are logged as readings.  With
``variant="control"`` the reference at the next precision below (``high``)
takes the program's place (calibration and tests only; the benchmark's
runs never take it).
"""

from __future__ import annotations

import math
import time

import numpy as np

import gen
from reference import gossip_cnn as ref


def _leaf_gaps(got: list[float], want: list[float], keep: list[bool]) -> list[float]:
    """Each kept leaf's |‖got‖ - ‖want‖| over the larger of its reference
    norm and the median leaf's."""
    med = float(np.median(want))
    return [abs(g - w) / max(w, med) if math.isfinite(g) else math.inf
            for g, w, k in zip(got, want, keep) if k]


def _median(values: list[float]) -> float:
    return math.inf if not all(map(math.isfinite, values)) else float(np.median(values))


class Runner:
    def __init__(self, ctx):
        self.ctx = ctx
        self.cfg = c = ctx.config
        self.tr = ctx.traffic
        self.image = tuple(c["image"])
        widths = (self.image, c["classes"], c["channels"], c["hidden"])
        ctx.counters.update(
            users=c["users"],
            samples_per_round=c["users"] * self.tr["local_steps"] * c["batch"],
            params_per_user=ctx.flops.cnn_param_count(*widths),
            train_flops_per_sample=ctx.flops.cnn_train_flops(*widths))

    def _data(self):
        c = self.cfg
        return gen.image_data(self.ctx.seed, c["users"], c["samples_per_user"],
                              self.image, c["classes"], c["noise"])

    def _params0(self):
        c = self.cfg
        return gen.cnn_init(self.ctx.seed, self.image, c["classes"], c["channels"], c["hidden"])

    def _ref_kwargs(self) -> dict:
        c, t = self.cfg, self.tr
        comp = t.get("compressor")
        return dict(rounds=t["reference_rounds"], local_steps=t["local_steps"],
                    batch=c["batch"], lr=c["lr"], momentum=c["momentum"],
                    self_weight=c["self_weight"],
                    topk_fraction=comp["fraction"] if comp else None)

    def setup(self):
        c, ctx, t = self.cfg, self.ctx, self.tr
        if t["reference_rounds"] * t["local_steps"] * c["batch"] > c["samples_per_user"]:
            raise ValueError("the compared rounds must read rows that all differ: "
                             "reference_rounds x local_steps x batch > samples_per_user")
        self.edges = gen.gossip_edges(c["graph_seed"], c["users"], *c["out_degree"])
        if ctx.variant == "control":
            xs, ys = self._data()
            self.readings = ref.run(self._params0(), xs, ys, self.edges,
                                    precision=c["control"]["precision"],
                                    **self._ref_kwargs())
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
        from repro.data.synthetic import ImageDataset
        from repro.fl.cnn import cnn_loss
        from repro.fl.gossip import GossipConfig, GossipTrainer
        from repro.train.compression import TopK

        c, t, ctx = self.cfg, self.tr, self.ctx
        with ctx.part("data and weights"):
            xs, ys = self._data()
            params0 = self._params0()
            xs_np, ys_np = np.asarray(xs), np.asarray(ys)
            del xs, ys
            shards = [ImageDataset(xs_np[u], ys_np[u], c["classes"])
                      for u in range(c["users"])]
            self.params0 = params0
            self.leaves0 = jax.tree.leaves(params0)
        comp = t.get("compressor")
        gcfg = GossipConfig(
            local_steps=t["local_steps"], batch_size=c["batch"], lr=c["lr"],
            momentum=c["momentum"], aggregate_self_weight=c["self_weight"],
            compressor=TopK(comp["fraction"]) if comp else None,
            backend="stacked", mix_backend="auto", compress_backend="auto")
        with ctx.part("build trainer"):
            self.trainer = GossipTrainer(
                TaskGraph(p=np.ones(c["users"]), edges=self.edges),
                lambda _key: params0, cnn_loss, shards, gcfg, seed=c["trainer_seed"])

    def _first_rounds(self):
        import jax
        import jax.numpy as jnp

        tr = self.trainer
        losses, mom = [], None
        with self.ctx.part("first rounds (compile, reference readings)"):
            for r in range(self.tr["reference_rounds"]):
                losses.append(tr.step_round()["mean_loss"])
                if r == 0:
                    mom = [float(jnp.linalg.norm(l.reshape(-1)))
                           for l in jax.tree.leaves(tr._state[1])]
            change = [float(jnp.linalg.norm((l - l0[None]).reshape(-1)))
                      for l, l0 in zip(jax.tree.leaves(tr._state[0]), self.leaves0)]
        self.readings = {"losses": losses, "momentum": mom, "change": change}
        self.ctx.counters["mix_backend"] = tr.mix_backend
        self.ctx.counters["compress_backend"] = tr.compress_backend

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
        ctx.records.update(window_s=window, completed=rounds, attempted=rounds,
                           failed=bad, samples=rounds * ctx.counters["samples_per_round"])
        ctx.counters.update(rounds=rounds, dispatches_per_round=tr.last_round_dispatches)

    def release(self):
        self.trainer = None
        self.params0 = None
        self.leaves0 = None

    def check(self) -> list[tuple[str, float, float | None]]:
        """Every number the comparison reads, each with the limit the
        configuration holds it to (``None``: read, not compared)."""
        lim = self.cfg["checks"][self.tr["checks"]]
        xs, ys = self._data()
        want = ref.run(self._params0(), xs, ys, self.edges, **self._ref_kwargs())
        del xs, ys
        got = self.readings
        # leaves whose reference gradient is nought to rounding (under a
        # thousandth of the median leaf's) move by round-off alone
        med = float(np.median(want["momentum"]))
        keep = [m >= 1e-3 * med for m in want["momentum"]]
        loss = [(abs(g - w) / abs(w) if math.isfinite(g) else math.inf)
                for g, w in zip(got["losses"], want["losses"])]
        mom = _leaf_gaps(got["momentum"], want["momentum"], keep)
        change = _leaf_gaps(got["change"], want["change"], keep)
        numbers = {"loss_gap": max(loss), "first_loss_gap": loss[0],
                   "momentum_gap": max(mom), "momentum_gap_median": _median(mom),
                   "change_gap": max(change), "change_gap_median": _median(change)}
        self.ctx.counters["loss_gap_by_round"] = loss
        self.ctx.counters["momentum_gap_by_leaf"] = mom
        self.ctx.counters["change_gap_by_leaf"] = change
        return [(k, v, lim.get(k)) for k, v in numbers.items()]
