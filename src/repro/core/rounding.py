"""Randomized rounding of the SDP solution + the paper's bounds.

Implements:
  - ``randomized_rounding``: sample z ~ N(0, Y*), take sign(z), fold the
    homogenization variable u, repair/filter to feasible assignments, pick
    the best (paper §3, Aspremont-Boyd style).  Two backends:
      * ``numpy`` — the clear float64 reference implementation;
      * ``jax``   — the whole pipeline (sampling, sign folding, repair,
        batched bottleneck evaluation, arg-best selection) fused into ONE
        jitted call, so tens of thousands of samples never leave device
        (§Perf item; DESIGN.md §6).  When the SDP solve also ran on device
        (``SDPSolution.Y_device``), pass it via ``Y_device=`` and the
        covariance square root is taken on device as well — the Gram matrix
        never round-trips to host between solve and rounding.
  - ``naive_rounding``: per-task argmax of the relaxed solution (the paper's
    "SDP with naive rounding" baseline).
  - ``expected_bottleneck``: Eq. (22)-(23) arcsin formula.
  - ``sdp_lower_bound`` / ``optimal_upper_bound``: Eq. (24) and (27).
  - ``analysis_bounds``: all three transforms at once; with a
    device-resident Gram matrix and the matrix-free representation they run
    in one jitted call on device instead of three host O(n²) passes.

All analysis functions accept either the dense ``BQPData`` oracle or the
matrix-free ``FactoredBQP`` (DESIGN.md §2); with the factored form the
arcsin/linear transforms touch only the dense (n+1)² Gram matrix Y — never
an (|E|, n, n) stack.
"""

from __future__ import annotations

import collections
import dataclasses
import functools

import numpy as np

from repro.core.bqp import BQPData, FactoredBQP, bottleneck_time_batch
from repro.core.graphs import ComputeGraph, TaskGraph

AnyBQP = BQPData | FactoredBQP


@dataclasses.dataclass
class RoundingResult:
    assignment: np.ndarray          # (N_T,) machine indices, best sample
    bottleneck: float               # exact bottleneck time of ``assignment``
    num_feasible: int               # samples surviving the feasibility filter
    num_samples: int
    expected_bottleneck: float      # Eq. (22)-(23)
    lower_bound: float              # Eq. (24)  (<= OPT)
    upper_bound: float              # Eq. (27)  (>= OPT, see note in DESIGN.md)
    # Eq. 2 evaluator that scored the samples: "numpy" (host float64),
    # "jnp" (vmapped gathers) or "pallas" (kernels/bottleneck.py)
    evaluator: str = "numpy"


def _covariance_root(Y: np.ndarray) -> np.ndarray:
    """Eigen square root, robust to the slightly indefinite Y that a
    first-order solver returns."""
    w, V = np.linalg.eigh(0.5 * (Y + Y.T))
    return V * np.sqrt(np.clip(w, 0.0, None))


def _sample_signs(
    Y: np.ndarray, num_samples: int, rng: np.random.Generator
) -> np.ndarray:
    """Draw sign(z), z ~ N(0, Y), as ±1 matrix (num_samples, n+1)."""
    root = _covariance_root(Y)
    g = rng.standard_normal((num_samples, Y.shape[0]))
    z = g @ root.T
    s = np.sign(z)
    s[s == 0] = 1.0
    return s, z


def signs_to_assignments(
    signs: np.ndarray, z: np.ndarray, n_tasks: int, n_machines: int
) -> tuple[np.ndarray, np.ndarray]:
    """±1 samples -> (assignments (B, N_T), strict_feasible (B,) bool).

    Folds u (last coordinate), reshapes column-major, and repairs:
      - multiple machines selected for a task: keep the one with the largest
        continuous score z (paper footnote 9 allows dropping duplicates);
      - zero machines selected: strictly infeasible (flagged), repaired to
        the argmax-z machine so every sample yields *some* assignment.
    """
    u = signs[:, -1:]
    x = signs[:, :-1] * u                          # fold homogenization
    zx = z[:, :-1] * u
    B = x.shape[0]
    # column-major vec: index κ·N_T + τ  ->  (machine κ, task τ)
    sel = (x.reshape(B, n_machines, n_tasks) > 0)  # (B, K, T)
    score = zx.reshape(B, n_machines, n_tasks)     # continuous scores
    masked = np.where(sel, score, -np.inf)
    any_sel = sel.any(axis=1)                      # (B, T)
    strict = any_sel.all(axis=1)
    # repair: fall back to raw score where nothing was selected
    choice = np.where(any_sel[:, None, :], masked, score)
    assignments = np.argmax(choice, axis=1)        # (B, T)
    return assignments, strict


def randomized_rounding(
    bqp: AnyBQP,
    task_graph: TaskGraph,
    compute_graph: ComputeGraph,
    Y: np.ndarray,
    *,
    num_samples: int = 2000,
    rng: np.random.Generator | None = None,
    strict: bool = False,
    backend: str = "numpy",
    Y_device: object | None = None,
    kernel_backend: str = "auto",
) -> RoundingResult:
    rng = rng or np.random.default_rng(0)

    evaluator = "numpy"
    if backend == "jax":
        evaluator = _rounding_kernel_backend(kernel_backend)
        assignment, bottleneck, num_feasible = _rounding_fused_jax(
            task_graph,
            compute_graph,
            bqp.n_tasks,
            bqp.n_machines,
            Y,
            num_samples,
            rng,
            strict,
            Y_device=Y_device,
            kernel_backend=evaluator,
        )
    else:
        signs, z = _sample_signs(Y, num_samples, rng)
        assignments, strict_mask = signs_to_assignments(
            signs, z, bqp.n_tasks, bqp.n_machines
        )
        if strict and strict_mask.any():
            # Paper discards infeasible samples; if none survive, fall back
            # to repaired samples (never fail).
            candidate = assignments[strict_mask]
        else:
            candidate = assignments
        times = bottleneck_time_batch(task_graph, compute_graph, candidate)
        best = int(np.argmin(times))
        assignment = candidate[best]
        bottleneck = float(times[best])
        num_feasible = int(strict_mask.sum())

    # The numpy backend is the float64 reference oracle end to end — only
    # the jax backend hands the analysis transforms a device (f32) Y.
    exp_b, lb, ub = analysis_bounds(
        bqp, Y, Y_device=Y_device if backend == "jax" else None
    )
    return RoundingResult(
        assignment=assignment,
        bottleneck=bottleneck,
        num_feasible=num_feasible,
        num_samples=num_samples,
        expected_bottleneck=exp_b,
        lower_bound=lb,
        upper_bound=ub,
        evaluator=evaluator,
    )


def naive_rounding(bqp: AnyBQP, Y: np.ndarray) -> np.ndarray:
    """Paper's 'SDP with naive rounding': round the relaxed solution.

    The relaxed x is read off the u-column of the Gram matrix
    (Y[:n, -1] ≈ E[x·u]); per task we pick the machine with the largest
    relaxed indicator (equivalent to rounding to the closest feasible
    integer point).
    """
    x_relaxed = Y[:-1, -1]
    m_relaxed = (x_relaxed + 1.0) / 2.0
    M = m_relaxed.reshape(bqp.n_machines, bqp.n_tasks)  # column-major
    return np.argmax(M, axis=0)


# ---------------------------------------------------------------------------
# Paper analysis: expectation and bounds
# ---------------------------------------------------------------------------


def _edge_inner(bqp: AnyBQP, F: np.ndarray) -> np.ndarray:
    """<Q̃_e, F> for all constraint edges, dense oracle or matrix-free."""
    if isinstance(bqp, FactoredBQP):
        return bqp.inner(F)
    return np.einsum("eij,ij->e", bqp.Q_tilde, F)


def expected_bottleneck(bqp: AnyBQP, Y: np.ndarray) -> float:
    """Eq. (22)-(23): max_e (1/4) E[ẑᵀ Q̃_e ẑ] via the arcsin identity."""
    asin = np.arcsin(np.clip(Y, -1.0, 1.0))
    vals = _edge_inner(bqp, asin) * (2.0 / np.pi)
    return float(np.max(vals) / 4.0)


def sdp_lower_bound(bqp: AnyBQP, Y: np.ndarray) -> float:
    """Eq. (24): the SDP objective max_e <Q̃_e, Y*>/4 lower-bounds OPT."""
    vals = _edge_inner(bqp, Y)
    return float(np.max(vals) / 4.0)


def optimal_upper_bound(bqp: AnyBQP, Y: np.ndarray) -> float:
    """Eq. (26)-(27): OPT <= max_e (1/4) Σ Q̃_e ∘ (0.112 + 0.878 Y).

    (The paper's Eq. 27 omits the 1/4 of Eq. 25; we keep it so the bound is
    in bottleneck-time units and comparable with Fig. 4/5.)
    """
    lin = 0.112 + 0.878 * np.clip(Y, -1.0, 1.0)
    vals = _edge_inner(bqp, lin)
    return float(np.max(vals) / 4.0)


def analysis_bounds(
    bqp: AnyBQP, Y: np.ndarray, *, Y_device=None
) -> tuple[float, float, float]:
    """(expected_bottleneck, sdp_lower_bound, optimal_upper_bound) in one go.

    With a device-resident Gram matrix (``SDPSolution.Y_device``) and the
    matrix-free representation, all three Eq. (22)-(24)/(27) transforms run
    in ONE jitted call on device — the host otherwise pays three O(n²)
    arcsin/linear passes plus the factored inner products per ``schedule()``
    even after a device-resident solve.  Dense instances (small by
    construction, DESIGN.md §2) keep the float64 host path.
    """
    if Y_device is not None and isinstance(bqp, FactoredBQP):
        fn = _device_analysis_fn(bqp)
        exp_b, lb, ub = fn(Y_device)
        return float(exp_b), float(lb), float(ub)
    return (
        expected_bottleneck(bqp, Y),
        sdp_lower_bound(bqp, Y),
        optimal_upper_bound(bqp, Y),
    )


_ANALYSIS_CACHE: collections.OrderedDict = collections.OrderedDict()
_ANALYSIS_CACHE_MAX = 8


def _device_analysis_fn(bqp: FactoredBQP):
    """Jitted (expected, lower, upper) from a device Y, keyed on content."""
    import jax
    import jax.numpy as jnp

    from repro.core.sdp import DOT_PRECISION

    key = (
        bqp.p.tobytes(),
        bqp.d.tobytes(),
        bqp.C.tobytes(),
        bqp.src.tobytes(),
        bqp.dst.tobytes(),
    )
    fn = _cache_lookup(_ANALYSIS_CACHE, key)
    if fn is not None:
        return fn

    K, T, n = bqp.n_machines, bqp.n_tasks, bqp.n
    p = jnp.asarray(bqp.p, jnp.float32)
    d = jnp.asarray(bqp.d, jnp.float32)
    C = jnp.asarray(bqp.C, jnp.float32)
    src = jnp.asarray(bqp.src, jnp.int32)
    dst = jnp.asarray(bqp.dst, jnp.int32)
    C1 = jnp.asarray(bqp._C1, jnp.float32)
    Ct1 = jnp.asarray(bqp._Ct1, jnp.float32)
    P = jnp.float32(bqp._P)
    corner = jnp.float32(bqp.corner)
    einsum = functools.partial(jnp.einsum, precision=DOT_PRECISION)

    def inner(F):
        """Device twin of ``FactoredBQP.inner`` (same closed forms)."""
        F = 0.5 * (F + F.T)
        Fxx = F[:n, :n].reshape(K, T, K, T)
        f = F[:n, -1].reshape(K, T)
        comp = einsum("k,t,ktks->s", d, p, Fxx)
        blocks = Fxx.transpose(1, 3, 0, 2)[src, dst]      # (|E|, K, K)
        comm = einsum("ekl,kl->e", blocks, C)
        base = einsum("k,t,kt->", d, p, f)
        u_i = jnp.dot(C1 + P * d, f, precision=DOT_PRECISION)
        u_j = jnp.dot(Ct1, f, precision=DOT_PRECISION)
        q1f = 0.5 * (base + u_i[src] + u_j[dst])
        return comp[src] + comm + 2.0 * q1f + corner * F[-1, -1]

    @jax.jit
    def analysis(Y):
        Yc = jnp.clip(Y, -1.0, 1.0)
        exp_b = jnp.max(inner(jnp.arcsin(Yc)) * (2.0 / jnp.pi)) / 4.0
        lb = jnp.max(inner(Y)) / 4.0
        ub = jnp.max(inner(0.112 + 0.878 * Yc)) / 4.0
        return exp_b, lb, ub

    _cache_insert(_ANALYSIS_CACHE, key, analysis, _ANALYSIS_CACHE_MAX)
    return analysis


# ---------------------------------------------------------------------------
# Fused JAX rounding (beyond-paper §Perf optimization)
# ---------------------------------------------------------------------------
#
# One jitted call per (instance, strict) pair: z = g·rootᵀ, sign fold,
# duplicate/empty repair, batched bottleneck evaluation, and best-sample
# selection all stay on device.  Gaussians g come from the caller's numpy
# rng so the two backends draw identical samples.

_JAX_CACHE: collections.OrderedDict = collections.OrderedDict()
_JAX_CACHE_MAX = 32


def _cache_lookup(cache: collections.OrderedDict, key):
    """LRU read: refresh recency so hot closures survive eviction."""
    val = cache.get(key)
    if val is not None:
        cache.move_to_end(key)
    return val


def _cache_insert(cache: collections.OrderedDict, key, val, max_size: int):
    """LRU insert with SINGLE-entry eviction: a cache-capacity+1-th instance
    evicts only the least-recently-used closure instead of wiping the whole
    cache (which would recompile every cached instance on its next use)."""
    while len(cache) >= max_size:
        cache.popitem(last=False)
    cache[key] = val


def _rounding_kernel_backend(kernel_backend: str) -> str:
    """"auto" = the Pallas batched bottleneck evaluator on TPU, the vmapped
    gather evaluator elsewhere (interpret mode is exact but slow on CPU)."""
    if kernel_backend not in ("auto", "jnp", "pallas"):
        raise ValueError(
            f"unknown kernel backend {kernel_backend!r}; "
            "choose from ('auto', 'jnp', 'pallas')"
        )
    if kernel_backend == "auto":
        from repro.kernels.ops import interpret_mode

        return "jnp" if interpret_mode() else "pallas"
    return kernel_backend


def _fused_rounding_fn(
    task_graph: TaskGraph, compute_graph: ComputeGraph, n_tasks: int,
    n_machines: int, strict: bool, kernel_backend: str = "jnp",
):
    import jax
    import jax.numpy as jnp

    # Key on instance *content*, not object identity: ids get reused after
    # GC and would silently hand back a closure baked with another
    # instance's workloads/speeds/edges.
    key = (
        task_graph.p.tobytes(),
        task_graph.edges,
        compute_graph.e.tobytes(),
        compute_graph.C.tobytes(),
        n_tasks,
        n_machines,
        strict,
        kernel_backend,
    )
    fn = _cache_lookup(_JAX_CACHE, key)
    if fn is not None:
        return fn

    p = jnp.asarray(task_graph.p, dtype=jnp.float32)
    e = jnp.asarray(compute_graph.e, dtype=jnp.float32)
    C = jnp.asarray(compute_graph.C, dtype=jnp.float32)
    if task_graph.edges:
        src = jnp.asarray([i for (i, _) in task_graph.edges])
        dst = jnp.asarray([j for (_, j) in task_graph.edges])
    else:
        src = dst = jnp.zeros((0,), dtype=jnp.int32)

    if kernel_backend == "pallas":
        from repro.kernels.bottleneck import bottleneck_eval_fwd
        from repro.kernels.ops import interpret_mode

        eval_times = functools.partial(
            bottleneck_eval_fwd, p=p, e=e, C=C, src=src, dst=dst,
            interpret=interpret_mode(),
        )
    else:
        from repro.kernels.ref import bottleneck_eval_ref

        eval_times = functools.partial(
            bottleneck_eval_ref, p=p, e=e, C=C, src=src, dst=dst
        )

    @jax.jit
    def rounding(root, g):
        B = g.shape[0]
        z = g @ root.T                                  # (B, n+1)
        s = jnp.where(z >= 0, 1.0, -1.0)                # sign with 0 -> +1
        u = s[:, -1:]
        zx = (z[:, :-1] * u).reshape(B, n_machines, n_tasks)
        sel = (s[:, :-1] * u).reshape(B, n_machines, n_tasks) > 0
        masked = jnp.where(sel, zx, -jnp.inf)
        any_sel = sel.any(axis=1)                       # (B, T)
        strict_mask = any_sel.all(axis=1)               # (B,)
        choice = jnp.where(any_sel[:, None, :], masked, zx)
        assignments = jnp.argmax(choice, axis=1)        # (B, T)
        times = eval_times(assignments)                 # (B,)
        if strict:
            times = jnp.where(
                strict_mask.any(),
                jnp.where(strict_mask, times, jnp.inf),
                times,
            )
        best = jnp.argmin(times)
        return assignments[best], times[best], strict_mask.sum()

    _cache_insert(_JAX_CACHE, key, rounding, _JAX_CACHE_MAX)
    return rounding


def _fused_rounding_batch_fn(
    B: int, n_tasks: int, n_machines: int, n_edges: int, strict: bool,
    kernel_backend: str = "jnp",
):
    """Batched twin of ``_fused_rounding_fn``: B instances, one dispatch.

    Keyed on *shape* only — the per-instance weights (p, e, C, src, dst)
    are traced arguments, so one closure serves every same-shape batch.
    The leading ``"batch"`` tag plus the batch dimension ``B`` keep batched
    and single-instance closures of the same instance shape from evicting
    each other out of the shared ``_JAX_CACHE`` LRU.
    """
    import jax
    import jax.numpy as jnp

    key = ("batch", B, n_tasks, n_machines, n_edges, strict, kernel_backend)
    fn = _cache_lookup(_JAX_CACHE, key)
    if fn is not None:
        return fn

    if kernel_backend == "pallas":
        from repro.kernels.bottleneck import bottleneck_eval_fwd
        from repro.kernels.ops import interpret_mode

        evaluator = functools.partial(
            bottleneck_eval_fwd, interpret=interpret_mode()
        )
    else:
        from repro.kernels.ref import bottleneck_eval_ref as evaluator

    def round_one(p, e, C, src, dst, root, g):
        S = g.shape[0]
        z = g @ root.T                                  # (S, n+1)
        s = jnp.where(z >= 0, 1.0, -1.0)                # sign with 0 -> +1
        u = s[:, -1:]
        zx = (z[:, :-1] * u).reshape(S, n_machines, n_tasks)
        sel = (s[:, :-1] * u).reshape(S, n_machines, n_tasks) > 0
        masked = jnp.where(sel, zx, -jnp.inf)
        any_sel = sel.any(axis=1)                       # (S, T)
        strict_mask = any_sel.all(axis=1)               # (S,)
        choice = jnp.where(any_sel[:, None, :], masked, zx)
        assignments = jnp.argmax(choice, axis=1)        # (S, T)
        times = evaluator(assignments, p=p, e=e, C=C, src=src, dst=dst)
        if strict:
            times = jnp.where(
                strict_mask.any(),
                jnp.where(strict_mask, times, jnp.inf),
                times,
            )
        best = jnp.argmin(times)
        return assignments[best], times[best], strict_mask.sum()

    rounding = jax.jit(jax.vmap(round_one))
    _cache_insert(_JAX_CACHE, key, rounding, _JAX_CACHE_MAX)
    return rounding


_DEVICE_ROOT_FN = None


def _device_covariance_root(Y_device):
    """Eigen square root of a device-resident Y — the solve→rounding hand-off
    path: the covariance stays on device end to end."""
    global _DEVICE_ROOT_FN
    if _DEVICE_ROOT_FN is None:
        import jax
        import jax.numpy as jnp

        @jax.jit
        def _root(Y):
            Y = 0.5 * (Y + Y.T)
            w, V = jnp.linalg.eigh(Y)
            return V * jnp.sqrt(jnp.clip(w, 0.0, None))

        _DEVICE_ROOT_FN = _root
    return _DEVICE_ROOT_FN(Y_device)


def _rounding_fused_jax(
    task_graph: TaskGraph,
    compute_graph: ComputeGraph,
    n_tasks: int,
    n_machines: int,
    Y: np.ndarray,
    num_samples: int,
    rng: np.random.Generator,
    strict: bool,
    Y_device=None,
    kernel_backend: str = "auto",
) -> tuple[np.ndarray, float, int]:
    fn = _fused_rounding_fn(
        task_graph, compute_graph, n_tasks, n_machines, strict,
        _rounding_kernel_backend(kernel_backend),
    )
    if Y_device is not None:
        root = _device_covariance_root(Y_device)
    else:
        root = _covariance_root(Y).astype(np.float32)
    g = rng.standard_normal((num_samples, Y.shape[0])).astype(np.float32)
    assignment, t_best, n_feasible = fn(root, g)
    return (
        np.asarray(assignment, dtype=np.int64),
        float(t_best),
        int(n_feasible),
    )


_DEVICE_ROOT_BATCH_FN = None


def _device_covariance_root_batch(Y_stack):
    """Batched eigen square roots of B stacked device covariances."""
    global _DEVICE_ROOT_BATCH_FN
    if _DEVICE_ROOT_BATCH_FN is None:
        import jax
        import jax.numpy as jnp

        @jax.jit
        def _root(Ys):
            Ys = 0.5 * (Ys + jnp.transpose(Ys, (0, 2, 1)))
            w, V = jnp.linalg.eigh(Ys)
            return V * jnp.sqrt(jnp.clip(w, 0.0, None))[:, None, :]

        _DEVICE_ROOT_BATCH_FN = _root
    return _DEVICE_ROOT_BATCH_FN(Y_stack)


def randomized_rounding_batch(
    bqps,
    task_graphs,
    compute_graphs,
    Ys,
    *,
    num_samples: int = 2000,
    rngs=None,
    strict: bool = False,
    backend: str = "jax",
    Y_devices=None,
    kernel_backend: str = "auto",
) -> list[RoundingResult]:
    """Round B same-shape SDP solutions in ONE fused jitted dispatch.

    The per-instance pipeline is identical to ``randomized_rounding``'s jax
    backend (same gaussians from each instance's rng, same repair and
    selection), vmapped over the batch: sampling, sign folding, repair,
    bottleneck evaluation, and arg-best selection for all B instances run
    on device together.  When every instance carries a device-resident
    covariance (``Y_devices``), the B square roots are also taken in one
    batched ``eigh``.

    The Eq. (22)-(24)/(27) analysis bounds are computed per instance on the
    float64 host path — it is exact and avoids compiling B content-keyed
    device-analysis closures for instances that are typically seen once.

    Falls back to B sequential numpy-backend calls when jax is unavailable
    or ``backend`` is not "jax".
    """
    from repro import compat

    B = len(bqps)
    if not (len(task_graphs) == len(compute_graphs) == len(Ys) == B):
        raise ValueError("bqps, task_graphs, compute_graphs, Ys must align")
    if B == 0:
        return []
    if rngs is None:
        rngs = [None] * B
    if Y_devices is None:
        Y_devices = [None] * B

    T, K = bqps[0].n_tasks, bqps[0].n_machines
    n_e = len(task_graphs[0].edges)
    for bqp, tg in zip(bqps, task_graphs):
        if (bqp.n_tasks, bqp.n_machines, len(tg.edges)) != (T, K, n_e):
            raise ValueError(
                "randomized_rounding_batch requires same-shape instances "
                "(same n_tasks, n_machines, and task-graph edge count)"
            )

    if backend != "jax" or not compat.jax_available():
        return [
            randomized_rounding(
                bqp,
                tg,
                cg,
                Y,
                num_samples=num_samples,
                rng=rng,
                strict=strict,
                backend="numpy",
            )
            for bqp, tg, cg, Y, rng in zip(
                bqps, task_graphs, compute_graphs, Ys, rngs
            )
        ]

    p_s = np.stack([np.asarray(tg.p, np.float32) for tg in task_graphs])
    e_s = np.stack([np.asarray(cg.e, np.float32) for cg in compute_graphs])
    C_s = np.stack([np.asarray(cg.C, np.float32) for cg in compute_graphs])
    if n_e:
        src_s = np.stack(
            [np.asarray([i for (i, _) in tg.edges], np.int32) for tg in task_graphs]
        )
        dst_s = np.stack(
            [np.asarray([j for (_, j) in tg.edges], np.int32) for tg in task_graphs]
        )
    else:
        src_s = dst_s = np.zeros((B, 0), np.int32)

    if all(yd is not None for yd in Y_devices):
        import jax.numpy as jnp

        roots = _device_covariance_root_batch(jnp.stack(Y_devices))
    else:
        roots = np.stack(
            [_covariance_root(Y).astype(np.float32) for Y in Ys]
        )
    g = np.stack(
        [
            (rng or np.random.default_rng(0))
            .standard_normal((num_samples, Y.shape[0]))
            .astype(np.float32)
            for rng, Y in zip(rngs, Ys)
        ]
    )

    evaluator = _rounding_kernel_backend(kernel_backend)
    fn = _fused_rounding_batch_fn(B, T, K, n_e, strict, evaluator)
    assignments, times, feas = fn(p_s, e_s, C_s, src_s, dst_s, roots, g)

    out = []
    for i in range(B):
        exp_b, lb, ub = analysis_bounds(bqps[i], Ys[i])
        out.append(
            RoundingResult(
                assignment=np.asarray(assignments[i], dtype=np.int64),
                bottleneck=float(times[i]),
                num_feasible=int(feas[i]),
                num_samples=num_samples,
                expected_bottleneck=exp_b,
                lower_bound=lb,
                upper_bound=ub,
                evaluator=evaluator,
            )
        )
    return out
