PYTHON ?= python
export PYTHONPATH := src$(if $(PYTHONPATH),:$(PYTHONPATH),)

.PHONY: test smoke churn_smoke async_fl_smoke kernel_diff_smoke shard_fl_smoke ci docs-check bench-scheduler bench-gossip bench-kernels bench-scenarios bench-async bench-churn bench-async-fl

# Tier-1 verification (ROADMAP.md)
test:
	$(PYTHON) -m pytest -x -q

# Fast scheduler smoke benchmark: small-instance backends + a two-point
# scaling sweep exercising both the dense and the factored representation,
# plus the jax-solver smoke (asserts the device SDP path didn't silently
# fall back to numpy), the stacked-gossip smoke (a 2-round stacked MNIST
# gossip run asserting the single-jit round path took effect), and the
# sync-equivalence smoke (asserts the event engine's sync semantics still
# reproduces Eq. 2 round times to 1e-9 — the engine cannot drift from the
# paper's model), the batched-solver smoke (asserts a B=8 stacked SDP
# solve is ONE jitted dispatch with all lanes converged), and the churn
# smoke (a short injected-timeout churn trace: arrivals re-solve, the
# heft fallback activates, regret vs the oracle stays finite), and the
# async-FL smoke (the barrier-free trainer's degenerate anchor reproduces
# the stacked losses to fp32, and a straggler replay mixes stale
# snapshots with zero barrier stalls), and the kernel-diff smoke (every
# fused Pallas kernel matches its jnp oracle in interpret mode, and a
# tiny seeded SDP solve with the fused projection on vs off follows the
# identical iteration trajectory), and the shard-FL smoke (the
# mesh-sharded engine on 2 fake host devices reproduces the stacked
# per-round losses to fp32 with ONE jitted dispatch per round — a fresh
# interpreter because the forced device count must precede jax's first
# init).
smoke:
	$(PYTHON) -c "import benchmarks.scheduler_bench as b; \
	b.small_instance_backends(quick=True); \
	[b.emit('smoke_nt%d' % r['n_tasks'], r['solve_seconds'] * 1e6, \
	        'rep=%s;peak_mb=%.1f' % (r['representation'], r['peak_tensor_bytes'] / 1e6)) \
	 for r in (b._sweep_point(8, 8, max_iters=150, num_samples=256), \
	           b._sweep_point(40, 8, max_iters=60, num_samples=256))]; \
	b.jax_solver_smoke(); \
	b.batched_solver_smoke()"
	$(PYTHON) -c "import benchmarks.fig6_gossip_fl as f; f.stacked_smoke()"
	$(PYTHON) -c "import benchmarks.async_bench as a; a.sync_equivalence_smoke()"
	$(PYTHON) -c "import benchmarks.churn_bench as c; c.churn_smoke()"
	$(PYTHON) -c "import benchmarks.async_fl_bench as a; a.async_fl_smoke()"
	$(PYTHON) -c "import benchmarks.kernels_bench as k; k.kernel_diff_smoke()"
	XLA_FLAGS=--xla_force_host_platform_device_count=2 \
	$(PYTHON) -c "import benchmarks.fig6_gossip_fl as f; f.sharded_smoke()"

# Shard-FL smoke alone: mesh=2 (fake host devices) sharded engine vs the
# stacked backend — per-round loss equivalence to fp32, one dispatch per
# round, no retracing.
shard_fl_smoke:
	XLA_FLAGS=--xla_force_host_platform_device_count=2 \
	$(PYTHON) -c "import benchmarks.fig6_gossip_fl as f; f.sharded_smoke()"

# Churn smoke alone: a short injected-timeout churn trace asserting that
# arrivals trigger elastic re-solves, a stalled SDP degrades to the heft
# fallback instead of wedging the trace, and regret vs the oracle stays
# finite.
churn_smoke:
	$(PYTHON) -c "import benchmarks.churn_bench as c; c.churn_smoke()"

# Async-FL smoke alone: the degenerate anchor (all-active + fresh
# versions + s === 1 reproduces the stacked per-round losses to fp32) and
# a straggler replay that mixes stale snapshots with zero barrier stalls.
async_fl_smoke:
	$(PYTHON) -c "import benchmarks.async_fl_bench as a; a.async_fl_smoke()"

# Kernel-diff smoke alone: every fused Pallas kernel (SDP subspace
# projection, rank-k clip, top-k/int8 delta compression, Eq. 2
# bottleneck evaluation) vs its jnp oracle in interpret mode, plus a
# tiny seeded solve_sdp with kernel_backend on vs off asserting the
# identical iteration trajectory.
kernel_diff_smoke:
	$(PYTHON) -c "import benchmarks.kernels_bench as k; k.kernel_diff_smoke()"

# Docs health: intra-repo markdown links resolve and the documented
# quickstart command still runs (see scripts/check_docs.py).
docs-check:
	$(PYTHON) scripts/check_docs.py

# Regenerate the BENCH_*.json records (schemas: docs/benchmarks.md)
bench-scheduler:
	$(PYTHON) -c "import benchmarks.scheduler_bench as b; \
	b.scaling_sweep(quick=False); b.batch_sweep(quick=False)"

# SHARDED=1 additionally records the population-scale mesh-sharded sweep
# (N_T up to 10k over 8 fake host devices) under the "sharded" key.
bench-gossip:
	$(PYTHON) -c "import benchmarks.fig6_gossip_fl as f; f.sweep()"
ifneq ($(SHARDED),)
	XLA_FLAGS=--xla_force_host_platform_device_count=8 \
	$(PYTHON) -c "import benchmarks.fig6_gossip_fl as f; f.sharded_sweep()"
endif

bench-kernels:
	$(PYTHON) -c "import benchmarks.kernels_bench as k; \
	k.main(quick=False, record_json=True)"
	$(PYTHON) -c "import benchmarks.roofline as r; \
	r.sdp_batch_profile(batch=8, record_json=True)"

bench-scenarios:
	$(PYTHON) -c "import benchmarks.scenarios_bench as s; s.main(quick=True, resume=False)"

bench-async:
	$(PYTHON) -c "import benchmarks.async_bench as a; a.main(quick=True, resume=False)"

bench-churn:
	$(PYTHON) -c "import benchmarks.churn_bench as c; c.main(quick=True, resume=False)"

bench-async-fl:
	$(PYTHON) -c "import benchmarks.async_fl_bench as a; a.main(quick=True)"

ci: test smoke
