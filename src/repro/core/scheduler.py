"""Unified scheduling API — the paper's technique as a first-class feature.

``schedule(task_graph, compute_graph, method=...)`` returns a ``Schedule``
with the assignment, its exact bottleneck time, and method-specific
diagnostics (SDP bounds, sample statistics, solver residuals).

Methods:
  - ``sdp``         : the paper — SDP relaxation + randomized rounding
  - ``sdp_naive``   : SDP relaxation + naive (argmax) rounding
  - ``sdp_ls``      : beyond-paper — ``sdp`` refined by 1-move local search
  - ``heft``        : HEFT on the §4.1.1 DAG rewrite
  - ``tp_heft``     : throughput-HEFT greedy period minimization
  - ``greedy`` / ``random`` / ``round_robin`` / ``sorted`` : simple baselines

SDP methods pick the problem representation automatically: the dense
``BQPData`` oracle for small instances, the matrix-free ``FactoredBQP``
once the dense (|E|, n, n) stacks would cross ``_DENSE_BYTES_LIMIT``
(DESIGN.md §2).  Override with ``representation=`` and observe the choice
in ``Schedule.info["representation"]``.

The SDP solver backend is selected the same way the rounding backend is:
``solver_backend=`` ("auto" | "numpy" | "jax", DESIGN.md §5) — "auto"
moves the Douglas-Rachford hot loop onto the JAX device once the Gram
side crosses ``SDPOptions.jax_above``.  ``warm_start=True`` keeps a
module-level cache of solver states keyed by the (task-graph,
compute-graph) *structural fingerprint*, so repeated ``schedule()`` calls
after incremental topology changes (speed EMA updates, elastic
re-scheduling) resume from the previous (Y, t, s) iterate instead of the
identity.

``Schedule.info`` reports the solver's Eq. 24 value as ``lower_bound``
only when the solve converged (``bound_certified``); an unconverged
iterate's value appears as ``lower_bound_uncertified`` instead — it is
*not* a bound and has historically exceeded the achieved bottleneck at
large n.  The rounding pass's own Eq. 24 re-evaluation is reported
separately as ``rounding_lower_bound``.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import numpy as np

from repro.core import bqp as bqp_mod
from repro.core.graphs import ComputeGraph, TaskGraph
from repro.core.rounding import (
    naive_rounding,
    randomized_rounding,
    randomized_rounding_batch,
)
from repro.core.sdp import SDPOptions, solve_sdp, solve_sdp_batch

METHODS = (
    "sdp",
    "sdp_naive",
    "sdp_ls",
    "heft",
    "tp_heft",
    "greedy",
    "random",
    "round_robin",
    "sorted",
)

REPRESENTATIONS = ("auto", "dense", "factored")

# Auto mode switches to the matrix-free representation once the dense
# Q/Q̃ stacks would exceed this many bytes (~100 MB ≈ N_T·N_K past ~300).
_DENSE_BYTES_LIMIT = 100_000_000

# Warm-start cache: structural fingerprint -> last SDPSolution.state.  The
# fingerprint deliberately excludes weights (p, e, C): an incremental
# topology change keeps the structure, so the previous iterate is a valid —
# and very close — starting point.  Dimension changes (machine failure)
# change the fingerprint and cold-start naturally.  True LRU: hits move
# the entry to the end of the (insertion-ordered) dict, and eviction pops
# the front — a hot fingerprint re-used on every re-solve survives while
# stale ones age out.
_WARM_STARTS: dict[tuple, dict] = {}
_WARM_STARTS_MAX = 8

# Batched warm starts: a tuple of per-instance fingerprints -> the list of
# per-lane solver states from the last ``schedule_batch`` of that exact
# batch composition.  Falls back lane-by-lane to ``_WARM_STARTS`` when the
# composition is new, and writes each lane's state back there after the
# solve so single-instance and batched re-solves stay interoperable.
_WARM_STARTS_BATCH: dict[tuple, list] = {}
_WARM_STARTS_BATCH_MAX = 4


def _warm_fingerprint(task_graph: TaskGraph, compute_graph: ComputeGraph) -> tuple:
    return (
        task_graph.num_tasks,
        compute_graph.num_machines,
        tuple(task_graph.edges),
    )


def clear_warm_start(
    task_graph: TaskGraph | None = None,
    compute_graph: ComputeGraph | None = None,
) -> bool:
    """Drop cached solver state for this problem structure (or all of it).

    The fingerprint deliberately ignores weights, so a later solve of a
    *different* instance with the same structure (e.g. the same ring
    topology under another seed) would otherwise resume from this one's
    iterate.  Callers that need runs reproducible from their own inputs
    alone (the scenario engine's drift simulation) clear the entry first.
    Called with no arguments it wipes BOTH caches wholesale — the churn
    simulation path uses this, since a churn trace re-solves at every
    fleet size and clearing one structure would leave the others warm.
    Returns True if anything was dropped.
    """
    if task_graph is None and compute_graph is None:
        hit = bool(_WARM_STARTS) or bool(_WARM_STARTS_BATCH)
        _WARM_STARTS.clear()
        _WARM_STARTS_BATCH.clear()
        return hit
    fp = _warm_fingerprint(task_graph, compute_graph)
    hit = _WARM_STARTS.pop(fp, None) is not None
    stale = [k for k in _WARM_STARTS_BATCH if fp in k]
    for k in stale:
        del _WARM_STARTS_BATCH[k]
    return hit or bool(stale)


def get_warm_start(
    task_graph: TaskGraph, compute_graph: ComputeGraph
) -> dict | None:
    """Peek the cached solver state for this problem structure (or None).

    With ``seed_warm_start`` this is the control layer's handle on the
    warm-start cache: ``ElasticScheduler`` snapshots the state after each
    re-solve into its own fleet-composition-keyed cache and restores it
    when a composition recurs (fail → rejoin round trips), which the
    structure-only fingerprint cannot distinguish.  Reading does not
    touch LRU recency.
    """
    return _WARM_STARTS.get(_warm_fingerprint(task_graph, compute_graph))


def seed_warm_start(
    task_graph: TaskGraph, compute_graph: ComputeGraph, state: dict
) -> None:
    """Install ``state`` as the warm start for this problem structure.

    The next ``schedule(..., warm_start=True)`` of the same (N_T, N_K,
    edges) structure resumes from it.  Evicts LRU entries as needed, like
    a solve-produced insertion.
    """
    fp = _warm_fingerprint(task_graph, compute_graph)
    _WARM_STARTS.pop(fp, None)
    while len(_WARM_STARTS) >= _WARM_STARTS_MAX:
        _WARM_STARTS.pop(next(iter(_WARM_STARTS)))
    _WARM_STARTS[fp] = state


def _pick_representation(
    task_graph: TaskGraph, compute_graph: ComputeGraph, representation: str
) -> str:
    if representation not in REPRESENTATIONS:
        raise ValueError(
            f"unknown representation {representation!r}; "
            f"choose from {REPRESENTATIONS}"
        )
    if representation != "auto":
        return representation
    dense_bytes = bqp_mod.dense_bytes_estimate(task_graph, compute_graph)
    return "factored" if dense_bytes > _DENSE_BYTES_LIMIT else "dense"


@dataclasses.dataclass
class Schedule:
    """A task→machine assignment with its exact Eq. 2 bottleneck time.

    ``info`` carries method-specific diagnostics; for the sdp family:

      - ``representation`` — "dense" | "factored" (auto-picked, §2 of
        DESIGN.md) and ``solver_backend`` — "numpy" | "jax" (auto-picked
        once the Gram side crosses ``SDPOptions.jax_above``);
      - ``sdp_iterations`` / ``sdp_residual`` / ``sdp_converged`` /
        ``sdp_seconds`` / ``solver_stats`` — solver observability;
      - ``bound_certified`` and exactly ONE of ``lower_bound`` (Eq. 24 at
        a converged solve — a true bound) or ``lower_bound_uncertified``
        (the same value off an unconverged iterate — NOT a bound; it has
        exceeded the achieved bottleneck at large n).  Both always carry
        the SOLVER's value; the rounding pass's re-evaluation of Eq. 24
        on the Y it consumed (device fp32 on the jax backend) is kept
        separately as ``rounding_lower_bound`` and never overwrites it;
      - ``expected_bottleneck`` (Eqs. 22–23), ``upper_bound`` (Eq. 27),
        ``rounding_lower_bound`` (Eq. 24 re-evaluated at rounding),
        ``num_feasible``, ``warm_started`` — rounding diagnostics;
      - ``rounding_evaluator`` — the Eq. 2 evaluator that scored the
        samples ("numpy", "jnp" or "pallas") and ``rounding_bottleneck``
        its score of the chosen sample (f32 on device; ``bottleneck`` is
        the host float64 evaluation of the same assignment); the solver's
        resolved cone-step kernel is ``solver_stats["kernel_backend"]``.
    """

    assignment: np.ndarray
    bottleneck: float
    method: str
    info: dict[str, Any] = dataclasses.field(default_factory=dict)

    def machine_of(self, task: int) -> int:
        return int(self.assignment[task])


def schedule(
    task_graph: TaskGraph,
    compute_graph: ComputeGraph,
    method: str = "sdp",
    *,
    seed: int = 0,
    num_samples: int = 4000,
    sdp_options: SDPOptions | None = None,
    rounding_backend: str = "jax",
    solver_backend: str | None = None,
    representation: str = "auto",
    warm_start: bool = False,
    _sdp_cache: dict | None = None,
) -> Schedule:
    """Compute a task->machine assignment minimizing bottleneck time.

    The sdp family auto-selects its machinery unless overridden:
    ``representation`` ("auto" picks dense vs. matrix-free by instance
    size), ``solver_backend`` (None defers to ``sdp_options.backend``,
    "auto" moves the solve on device past ``SDPOptions.jax_above``), and
    ``rounding_backend`` ("jax" fuses sampling→repair→evaluation into one
    jitted call).  ``warm_start=True`` resumes the solver from a cached
    iterate when the (N_T, N_K, edges) structure was seen before —
    re-schedules after weight-only changes (speed EMA updates, delay
    drift) converge in a fraction of the cold iteration count.  See
    ``Schedule`` for the ``info`` keys, including the certified
    ``lower_bound`` vs ``lower_bound_uncertified`` distinction.
    """
    rng = np.random.default_rng(seed)
    info: dict[str, Any] = {}

    if method in ("sdp", "sdp_naive", "sdp_ls"):
        cache = _sdp_cache if _sdp_cache is not None else {}
        if "sol" not in cache:
            rep = _pick_representation(task_graph, compute_graph, representation)
            if rep == "factored":
                cache["bqp"] = bqp_mod.build_factored_bqp(
                    task_graph, compute_graph
                )
            else:
                cache["bqp"] = bqp_mod.build_bqp(task_graph, compute_graph)
            cache["representation"] = rep
            opts = sdp_options or SDPOptions()
            if solver_backend is not None:
                opts = dataclasses.replace(opts, backend=solver_backend)
            fp = _warm_fingerprint(task_graph, compute_graph)
            ws = _WARM_STARTS.get(fp) if warm_start else None
            if ws is not None:
                # LRU hit: move to end now, so even if the new iterate is
                # rejected below the hot entry keeps its recency
                _WARM_STARTS[fp] = _WARM_STARTS.pop(fp)
            cache["sol"] = solve_sdp(cache["bqp"], opts, warm_start=ws)
            # never cache a diverged iterate — a poisoned state would make
            # every later warm re-solve NaN where a cold start recovers
            state = cache["sol"].state
            if warm_start and np.all(np.isfinite(state.get("w", np.inf))):
                if fp not in _WARM_STARTS:
                    while len(_WARM_STARTS) >= _WARM_STARTS_MAX:
                        _WARM_STARTS.pop(next(iter(_WARM_STARTS)))
                _WARM_STARTS[fp] = state
        data, sol = cache["bqp"], cache["sol"]
        info.update(
            representation=cache["representation"],
            sdp_iterations=sol.iterations,
            sdp_residual=sol.residual,
            sdp_converged=sol.converged,
            sdp_seconds=sol.solve_seconds,
            bound_certified=sol.bound_certified,
            solver_backend=sol.stats.get("solver_backend"),
            warm_started=sol.stats.get("warm_started", False),
            solver_stats=sol.stats,
        )
        # Eq. 24 is a certificate only at the SDP optimum: report the value
        # of an unconverged iterate under a name that can't be mistaken for
        # a bound (it has exceeded the achieved bottleneck at large n).
        bound_key = "lower_bound" if sol.bound_certified else "lower_bound_uncertified"
        info[bound_key] = sol.lower_bound
        if method == "sdp_naive":
            assignment = naive_rounding(data, sol.Y)
        else:
            # ``schedule_batch`` pre-rounds all lanes in one fused dispatch
            # and hands the result down here; sharing it across the sdp /
            # sdp_ls methods matches the sequential path, which redraws the
            # same gaussians from ``default_rng(seed)`` on every call.
            res = cache.get("rounding")
            if res is None:
                res = randomized_rounding(
                    data,
                    task_graph,
                    compute_graph,
                    sol.Y,
                    num_samples=num_samples,
                    rng=rng,
                    backend=rounding_backend,
                    Y_device=sol.Y_device,
                )
            # the rounding pass re-evaluates Eq. 24 on the Y it consumed
            # (possibly on device, in fp32); keep it under its own key —
            # it must not overwrite the solver's certified value
            info.update(
                num_feasible=res.num_feasible,
                expected_bottleneck=res.expected_bottleneck,
                upper_bound=res.upper_bound,
                rounding_lower_bound=res.lower_bound,
                rounding_evaluator=res.evaluator,
                rounding_bottleneck=res.bottleneck,
            )
            assignment = res.assignment
            if method == "sdp_ls":
                from repro.sched.baselines import local_search_refine

                assignment = local_search_refine(
                    task_graph, compute_graph, assignment
                )
    elif method == "heft":
        from repro.sched.heft import heft_assignment

        assignment = heft_assignment(task_graph, compute_graph)
    elif method == "tp_heft":
        from repro.sched.tp_heft import tp_heft_assignment

        assignment = tp_heft_assignment(task_graph, compute_graph)
    elif method == "greedy":
        from repro.sched.baselines import greedy_bottleneck_assignment

        assignment = greedy_bottleneck_assignment(task_graph, compute_graph)
    elif method == "random":
        from repro.sched.baselines import random_assignment

        assignment = random_assignment(task_graph, compute_graph, rng)
    elif method == "round_robin":
        from repro.sched.baselines import round_robin_assignment

        assignment = round_robin_assignment(task_graph, compute_graph)
    elif method == "sorted":
        from repro.sched.baselines import sorted_assignment

        assignment = sorted_assignment(task_graph, compute_graph)
    else:
        raise ValueError(f"unknown method {method!r}; choose from {METHODS}")

    t = bqp_mod.bottleneck_time(task_graph, compute_graph, assignment)
    return Schedule(
        assignment=np.asarray(assignment, dtype=np.int64),
        bottleneck=t,
        method=method,
        info=info,
    )


def schedule_batch(
    task_graphs,
    compute_graphs,
    method: str = "sdp",
    *,
    seed: int = 0,
    num_samples: int = 4000,
    sdp_options: SDPOptions | None = None,
    rounding_backend: str = "jax",
    solver_backend: str | None = None,
    representation: str = "auto",
    warm_start: bool = False,
) -> list[Schedule]:
    """Schedule B same-shape instances with ONE batched SDP solve.

    The scheduler-as-a-service entry point: all B Douglas-Rachford solves
    run as a single jitted dispatch with per-instance convergence masking
    (``solve_sdp_batch``), and the Gaussian roundings run as one fused
    batched dispatch (``randomized_rounding_batch``).  Each returned
    ``Schedule`` matches what B independent ``schedule()`` calls with the
    same ``seed`` would produce (same gaussians per lane, same ``info``
    keys) up to float32 batching noise.

    ``warm_start=True`` keys the B stacked solver states by the tuple of
    per-instance structural fingerprints: re-scheduling the same batch
    composition after weight-only changes (delay drift across a fleet)
    restores all lanes at once, a new composition falls back lane-by-lane
    to the single-instance cache, and the per-lane states are written back
    to it so batched and single re-solves interoperate.

    Instances must share (n_tasks, n_machines, edge count); non-sdp
    methods and empty batches degrade to sequential ``schedule()`` calls.
    """
    B = len(task_graphs)
    if len(compute_graphs) != B:
        raise ValueError("task_graphs and compute_graphs must align")
    if B == 0:
        return []
    if method not in ("sdp", "sdp_naive", "sdp_ls"):
        return [
            schedule(
                tg, cg, method,
                seed=seed,
                num_samples=num_samples,
                sdp_options=sdp_options,
                rounding_backend=rounding_backend,
                solver_backend=solver_backend,
                representation=representation,
                warm_start=warm_start,
            )
            for tg, cg in zip(task_graphs, compute_graphs)
        ]

    reps = {
        _pick_representation(tg, cg, representation)
        for tg, cg in zip(task_graphs, compute_graphs)
    }
    if len(reps) != 1:
        raise ValueError("schedule_batch requires a uniform representation")
    rep = reps.pop()
    build = (
        bqp_mod.build_factored_bqp if rep == "factored" else bqp_mod.build_bqp
    )
    bqps = [build(tg, cg) for tg, cg in zip(task_graphs, compute_graphs)]

    opts = sdp_options or SDPOptions()
    if solver_backend is not None:
        opts = dataclasses.replace(opts, backend=solver_backend)

    fps = [
        _warm_fingerprint(tg, cg)
        for tg, cg in zip(task_graphs, compute_graphs)
    ]
    batch_key = tuple(fps)
    warm_states: list = [None] * B
    if warm_start:
        cached = _WARM_STARTS_BATCH.get(batch_key)
        if cached is not None:
            _WARM_STARTS_BATCH[batch_key] = _WARM_STARTS_BATCH.pop(batch_key)
            warm_states = list(cached)
        else:
            warm_states = [_WARM_STARTS.get(fp) for fp in fps]

    sols = solve_sdp_batch(bqps, opts, warm_starts=warm_states)

    if warm_start:
        states = [s.state for s in sols]
        finite = [
            bool(np.all(np.isfinite(st.get("w", np.inf)))) for st in states
        ]
        if all(finite):
            if batch_key not in _WARM_STARTS_BATCH:
                while len(_WARM_STARTS_BATCH) >= _WARM_STARTS_BATCH_MAX:
                    _WARM_STARTS_BATCH.pop(next(iter(_WARM_STARTS_BATCH)))
            _WARM_STARTS_BATCH[batch_key] = states
        for fp, st, ok in zip(fps, states, finite):
            if not ok:
                continue
            if fp in _WARM_STARTS:
                _WARM_STARTS.pop(fp)
            else:
                while len(_WARM_STARTS) >= _WARM_STARTS_MAX:
                    _WARM_STARTS.pop(next(iter(_WARM_STARTS)))
            _WARM_STARTS[fp] = st

    rounding_results: list = [None] * B
    if method in ("sdp", "sdp_ls"):
        rounding_results = randomized_rounding_batch(
            bqps,
            task_graphs,
            compute_graphs,
            [s.Y for s in sols],
            num_samples=num_samples,
            rngs=[np.random.default_rng(seed) for _ in range(B)],
            backend=rounding_backend,
            Y_devices=[s.Y_device for s in sols],
        )

    out = []
    for tg, cg, bqp, sol, res in zip(
        task_graphs, compute_graphs, bqps, sols, rounding_results
    ):
        cache = {"bqp": bqp, "sol": sol, "representation": rep}
        if res is not None:
            cache["rounding"] = res
        out.append(
            schedule(
                tg, cg, method,
                seed=seed,
                num_samples=num_samples,
                sdp_options=sdp_options,
                rounding_backend=rounding_backend,
                representation=representation,
                _sdp_cache=cache,
            )
        )
    return out


def compare_methods(
    task_graph: TaskGraph,
    compute_graph: ComputeGraph,
    methods: tuple[str, ...] = ("heft", "tp_heft", "sdp_naive", "sdp"),
    _sdp_cache: dict | None = None,
    **kw,
) -> dict[str, Schedule]:
    """Run several schedulers on one instance, sharing one SDP solve."""
    cache: dict = _sdp_cache if _sdp_cache is not None else {}
    out = {}
    for m in methods:
        out[m] = schedule(task_graph, compute_graph, m, _sdp_cache=cache, **kw)
    return out
