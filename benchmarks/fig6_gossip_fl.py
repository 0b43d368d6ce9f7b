"""Fig. 6 reproduction: gossip-based FL bottleneck time (MNIST / CIFAR-10).

Paper §4.2 setting: N_T = 10 users (degree ~ Unif{6,7}), N_K = 4
homogeneous machines, C ~ Unif(0, 1); CNN = 2 conv + 3 fc.  We report the
per-round bottleneck of HEFT / TP-HEFT / SDP-naive / SDP-randomized plus
the learning curve (accuracy rises while SDP executes rounds fastest).

The FL engine itself runs on the stacked device-resident backend
(DESIGN.md §8); ``sweep()`` records rounds/sec of the stacked engine vs
the per-user reference loop at N_T ∈ {10, 32, 64, 128} into
``BENCH_gossip_fl.json``, and ``stacked_smoke()`` is the CI check that the
single-jit round path took effect.  ``sharded_sweep()`` scales the same
round math to N_T ∈ {128, 1k, 10k} on the mesh-sharded engine
(DESIGN.md §13) and records shard-count invariance, stacked-equivalence,
and halo-exchange volume under the ``sharded`` key; ``sharded_smoke()``
is its CI check (the ``shard_fl_smoke`` target).
"""

from __future__ import annotations

import json
import pathlib
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import Timer, emit
from repro.core.graphs import cluster_task_graph, gossip_task_graph
from repro.data.synthetic import ImageDataset, image_dataset
from repro.fl.cnn import cnn_loss, init_cnn_params
from repro.fl.gossip import GossipConfig, GossipTrainer


def run(quick: bool = True) -> dict:
    """The §4.2 experiment as the registered ``fig6`` scenario preset.

    The preset's ``FLWorkload(paper_setting=True)`` delegates instance
    generation to ``run_fl`` (the legacy code path), so losses and
    bottlenecks are bit-identical to the pre-engine benchmark; full mode
    re-sizes the workload to paper settings and adds cifar10.
    """
    import dataclasses

    from repro.scenarios import get_scenario, run_scenario

    base = get_scenario("fig6")
    out = {}
    datasets = ("mnist",) if quick else ("mnist", "cifar10")
    with Timer() as t:
        for ds in datasets:
            fl = dataclasses.replace(
                base.fl, dataset=ds,
                rounds=3 if quick else 10,
                num_samples=1024 if quick else 4096,
                local_steps=2 if quick else 4,
            )
            sc = dataclasses.replace(base, name=f"fig6_{ds}", fl=fl)
            out[ds] = run_scenario(sc, quick=quick)
    ds0 = datasets[0]
    fl0 = out[ds0]["fl"]
    b = fl0["bottleneck_per_round"]
    emit(
        "fig6_gossip_fl",
        t.seconds * 1e6 / len(datasets),
        f"dataset={ds0};backend={fl0['backend']};"
        f"bottleneck_sdp={b['sdp']:.3f};heft={b['heft']:.3f};"
        f"acc_final={fl0['accuracy_user0'][-1]:.2f}",
    )
    return out


# ---------------------------------------------------------------------------
# Engine throughput: stacked vs reference backend
# ---------------------------------------------------------------------------
#
# The sweep's primary model is a small MLP: the gossip engine's win is
# eliminating per-user/per-edge Python dispatch, which shows in the paper's
# many-users / modest-local-work regime.  The §4.2 CNN is compute-bound on
# this 2-core CPU container (and XLA CPU runs vmapped per-user-weight convs
# as grouped convolutions at a ~1.5x penalty), so it is recorded as an
# auxiliary series — on accelerators the stacked path wins there as well.


def _mlp_init(key, d: int = 784, hidden: int = 64, classes: int = 10) -> dict:
    k1, k2 = jax.random.split(key)
    return {
        "w1": jax.random.normal(k1, (d, hidden)) * np.sqrt(2.0 / d),
        "b1": jnp.zeros(hidden),
        "w2": jax.random.normal(k2, (hidden, classes)) * np.sqrt(2.0 / hidden),
        "b2": jnp.zeros(classes),
    }


def _mlp_loss(params: dict, batch: dict) -> jnp.ndarray:
    x = batch["x"].reshape(batch["x"].shape[0], -1)
    h = jax.nn.relu(x @ params["w1"] + params["b1"])
    logits = h @ params["w2"] + params["b2"]
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, batch["y"][:, None], axis=-1)[:, 0]
    return jnp.mean(logz - gold)


# One source of truth for the sweep's engine settings: _bench_trainer
# consumes it and sweep() persists it into BENCH_gossip_fl.json.
BENCH_CONFIG = {"local_steps": 4, "batch_size": 4, "samples_per_user": 32}


def _bench_trainer(
    n_users: int, backend: str, *, model: str = "mlp", seed: int = 0,
    local_steps: int = BENCH_CONFIG["local_steps"],
    batch_size: int = BENCH_CONFIG["batch_size"],
    samples_per_user: int = BENCH_CONFIG["samples_per_user"],
) -> GossipTrainer:
    rng = np.random.default_rng(seed)
    tg = gossip_task_graph(rng, n_users, degree_low=6, degree_high=7)
    train, _ = image_dataset("mnist", samples_per_user * n_users, seed=seed)
    shards = train.split(n_users, rng)
    cfg = GossipConfig(
        local_steps=local_steps, batch_size=batch_size, backend=backend
    )
    if model == "cnn":
        init = lambda k: init_cnn_params(k, (28, 28, 1), 10)
        loss = cnn_loss
    else:
        init, loss = _mlp_init, _mlp_loss
    return GossipTrainer(tg, init, loss, shards, cfg, seed=seed)


def _sweep_point(n: int, rounds: int, model: str) -> dict:
    row: dict = {"n_users": n, "model": model}
    for backend in ("reference", "stacked"):
        tr = _bench_trainer(n, backend, model=model)
        tr.step_round()                       # warmup: compile + caches
        t0 = time.perf_counter()
        for _ in range(rounds):
            tr.step_round()
        dt = (time.perf_counter() - t0) / rounds
        row[backend] = {
            "round_seconds": dt,
            "rounds_per_sec": 1.0 / dt,
            "dispatches_per_round": tr.last_round_dispatches,
        }
        del tr
    row["speedup"] = (
        row["reference"]["round_seconds"] / row["stacked"]["round_seconds"]
    )
    emit(
        f"gossip_fl_engine_{model}_nt{n}",
        row["stacked"]["round_seconds"] * 1e6,
        f"ref_us={row['reference']['round_seconds'] * 1e6:.0f};"
        f"speedup={row['speedup']:.1f}x;"
        f"dispatch_ref={row['reference']['dispatches_per_round']};"
        f"dispatch_stacked={row['stacked']['dispatches_per_round']}",
    )
    return row


def sweep(
    sizes: tuple[int, ...] = (10, 32, 64, 128),
    rounds: int = 3,
    out_path: str = "BENCH_gossip_fl.json",
    cnn_sizes: tuple[int, ...] = (10, 32),
) -> dict:
    """Rounds/sec of both gossip backends across user counts."""
    points = [_sweep_point(n, rounds, "mlp") for n in sizes]
    points += [_sweep_point(n, rounds, "cnn") for n in cnn_sizes]
    result = {
        "bench": "gossip_fl_engine",
        "device": jax.default_backend(),
        "rounds_timed": rounds,
        "config": BENCH_CONFIG,
        "points": points,
    }
    # Read-modify-write: async_fl_bench records into the same file under
    # its own key; re-running this sweep must not clobber that section.
    path = pathlib.Path(out_path)
    data = json.loads(path.read_text()) if path.exists() else {}
    data.update(result)
    path.write_text(json.dumps(data, indent=2) + "\n")
    return result


# ---------------------------------------------------------------------------
# Population scale: the mesh-sharded engine (DESIGN.md §13)
# ---------------------------------------------------------------------------
#
# The sharded sweep runs the SAME round math partitioned over a 1-D user
# mesh (fake host devices stand in on CPU — launch with
# XLA_FLAGS=--xla_force_host_platform_device_count=8, the `make
# bench-gossip SHARDED=1` / `make smoke` path).  The workload is a
# hierarchical cluster topology (sparse head ring between dense-ish
# clusters) on a tiny MLP, so the halo exchange — the boundary rows the
# engine actually gathers — stays a small fraction of the dense all-pairs
# alternative and N_T = 10k fits a CPU container.

SHARDED_BENCH_CONFIG = {
    "local_steps": 4, "batch_size": 4, "samples_per_user": 16,
    "image_side": 8, "hidden": 16, "inner_degree": 3,
    "users_per_cluster": 64,
}


def _sharded_instance(n_users: int, seed: int = 0):
    """Cluster task graph + tiny synthetic shards for one sweep point.

    Clusters are contiguous by construction, so the engine's contiguous
    shard blocks already respect them (``cluster_shard_permutation`` is
    the identity here) and only head-ring links cross shards.
    """
    c = SHARDED_BENCH_CONFIG
    rng = np.random.default_rng(seed)
    clusters = max(2, n_users // c["users_per_cluster"])
    tg = cluster_task_graph(
        rng, n_users, clusters=clusters, inner_topology="gossip",
        inner_degree=c["inner_degree"], head_topology="ring",
    )
    side = c["image_side"]
    n = n_users * c["samples_per_user"]
    data = ImageDataset(
        x=rng.normal(size=(n, side, side, 1)).astype(np.float32),
        y=rng.integers(0, 10, size=n).astype(np.int64),
        num_classes=10,
    )
    return tg, data.split(n_users, rng)


def sharded_trainer(
    n_users: int, backend: str, *, num_shards: int | None = None,
    seed: int = 0,
) -> GossipTrainer:
    """The sharded benchmark's trainer: cluster topology, 8x8 MLP."""
    c = SHARDED_BENCH_CONFIG
    tg, shards = _sharded_instance(n_users, seed)
    cfg = GossipConfig(
        local_steps=c["local_steps"], batch_size=c["batch_size"],
        backend=backend, num_shards=num_shards,
    )
    d = c["image_side"] ** 2
    init = lambda k: _mlp_init(k, d=d, hidden=c["hidden"])
    return GossipTrainer(tg, init, _mlp_loss, shards, cfg, seed=seed)


def sharded_sweep(
    sizes: tuple[int, ...] = (128, 1000, 10000),
    rounds: int = 2,
    mesh_sizes: tuple[int, ...] = (1, 2, 8),
    stacked_anchor_max: int = 1000,
    out_path: str = "BENCH_gossip_fl.json",
) -> dict:
    """Population-scale sweep of the mesh-sharded engine.

    Per size: rounds/sec at every available mesh size, per-round losses,
    the max loss spread ACROSS mesh sizes (shard-count invariance), the
    max deviation vs the single-device stacked backend on overlapping
    sizes (fp32 equivalence), and the measured halo-exchange volume vs
    the dense all-pairs alternative.  Records under the ``sharded`` key
    of ``BENCH_gossip_fl.json``.
    """
    avail = len(jax.devices())
    meshes = tuple(s for s in mesh_sizes if s <= avail)
    skipped = tuple(s for s in mesh_sizes if s > avail)
    if skipped:
        print(
            f"# sharded_sweep: skipping mesh sizes {skipped} — only {avail} "
            f"device(s); set XLA_FLAGS=--xla_force_host_platform_device_count="
            f"{max(mesh_sizes)}"
        )
    points = []
    for n in sizes:
        row: dict = {"n_users": n, "meshes": {}}
        losses_by_mesh: dict[int, list[float]] = {}
        for s in meshes:
            tr = sharded_trainer(n, "sharded", num_shards=s)
            losses = [tr.step_round()["mean_loss"]]     # warmup: compile
            t0 = time.perf_counter()
            for _ in range(rounds):
                losses.append(tr.step_round()["mean_loss"])
            dt = (time.perf_counter() - t0) / rounds
            assert tr.last_round_dispatches == 1, tr.last_round_dispatches
            losses_by_mesh[s] = losses
            hs = tr.halo_stats
            row["meshes"][str(s)] = {
                "round_seconds": dt,
                "rounds_per_sec": 1.0 / dt,
                "dispatches_per_round": tr.last_round_dispatches,
                "halo_stats": hs,
                # fraction of the dense all-pairs gather each shard receives
                "halo_fraction": (
                    hs["halo_rows_per_shard"] / hs["dense_rows_per_shard"]
                ),
            }
            del tr
        spreads = [
            max(abs(a - b) for a, b in zip(losses_by_mesh[x], losses_by_mesh[y]))
            for x in meshes for y in meshes if x < y
        ]
        row["losses"] = {str(s): losses_by_mesh[s] for s in meshes}
        row["mesh_loss_max_spread"] = max(spreads) if spreads else 0.0
        if n <= stacked_anchor_max:
            tr = sharded_trainer(n, "stacked")
            ref = [tr.step_round()["mean_loss"] for _ in range(rounds + 1)]
            del tr
            row["stacked_losses"] = ref
            row["stacked_loss_max_diff"] = max(
                max(abs(a - b) for a, b in zip(ref, losses_by_mesh[s]))
                for s in meshes
            )
        hs = row["meshes"][str(meshes[-1])]
        emit(
            f"gossip_fl_sharded_nt{n}",
            hs["round_seconds"] * 1e6,
            f"mesh={meshes[-1]};halo_frac={hs['halo_fraction']:.3f};"
            f"mesh_spread={row['mesh_loss_max_spread']:.2e};"
            + (
                f"vs_stacked={row['stacked_loss_max_diff']:.2e}"
                if "stacked_loss_max_diff" in row else "vs_stacked=n/a"
            ),
        )
        points.append(row)
    result = {
        "device": jax.default_backend(),
        "num_devices": avail,
        "mesh_sizes": list(meshes),
        "rounds_timed": rounds,
        "config": SHARDED_BENCH_CONFIG,
        "points": points,
    }
    # Read-modify-write: this file carries several benches' sections.
    path = pathlib.Path(out_path)
    data = json.loads(path.read_text()) if path.exists() else {}
    data["sharded"] = result
    path.write_text(json.dumps(data, indent=2) + "\n")
    return result


def sharded_smoke() -> None:
    """CI smoke (``shard_fl_smoke``): mesh=2 sharded == stacked to fp32.

    Needs >= 2 devices (fake host devices in CI:
    ``XLA_FLAGS=--xla_force_host_platform_device_count=2``).  Asserts the
    sharded engine reproduces the stacked per-round losses on a cluster
    topology, issues exactly ONE jitted dispatch per round, and never
    retraces.
    """
    avail = len(jax.devices())
    assert avail >= 2, (
        f"shard_fl_smoke needs >= 2 devices (got {avail}); set "
        f"XLA_FLAGS=--xla_force_host_platform_device_count=2"
    )
    n = 24
    a = sharded_trainer(n, "stacked")
    b = sharded_trainer(n, "sharded", num_shards=2)
    diffs = []
    for _ in range(3):
        ia, ib = a.step_round(), b.step_round()
        diffs.append(abs(ia["mean_loss"] - ib["mean_loss"]))
        assert b.last_round_dispatches == 1, b.last_round_dispatches
    assert max(diffs) < 2e-5, diffs
    if hasattr(b._round_jit, "_cache_size"):
        assert b._round_jit._cache_size() == 1, b._round_jit._cache_size()
    hs = b.halo_stats
    emit(
        "smoke_shard_fl", 0.0,
        f"mesh=2;rounds=3;max_loss_diff={max(diffs):.2e};"
        f"halo_rows={hs['halo_rows_per_shard']};"
        f"dense_rows={hs['dense_rows_per_shard']}",
    )


def stacked_smoke() -> None:
    """CI smoke: a 2-round stacked MNIST gossip run on the single-jit path.

    Asserts the stacked backend resolved, each round issued exactly ONE
    jitted dispatch (no per-user / per-edge Python dispatch), and the
    round function never retraced.
    """
    tr = _bench_trainer(8, "auto", model="cnn")
    assert tr.backend == "stacked", tr.backend
    losses = [tr.step_round()["mean_loss"] for _ in range(2)]
    assert tr.last_round_dispatches == 1, tr.last_round_dispatches
    if hasattr(tr._round_jit, "_cache_size"):
        assert tr._round_jit._cache_size() == 1, tr._round_jit._cache_size()
    assert all(np.isfinite(losses)), losses
    emit("smoke_gossip_stacked", 0.0,
         f"rounds=2;dispatches_per_round=1;loss_final={losses[-1]:.3f}")


def main(quick: bool = True):
    out = run(quick)
    for ds, res in out.items():
        print(f"# {ds}: bottleneck/round " + ", ".join(
            f"{m}={v:.3f}" for m, v in res["fl"]["bottleneck_per_round"].items()
        ))
        accs = res["fl"]["accuracy_user0"]
        print(f"# {ds}: accuracy " + ", ".join(f"{a:.2f}" for a in accs))
    return out


if __name__ == "__main__":
    import sys

    if "--sharded" in sys.argv:
        # Needs the fake-device count forced before jax's first init:
        # XLA_FLAGS=--xla_force_host_platform_device_count=8
        sharded_sweep()
    else:
        main(quick=False)
        sweep()
