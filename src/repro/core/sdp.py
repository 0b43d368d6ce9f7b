"""From-scratch SDP solver for the relaxed bottleneck-time problem (Eq. 20).

No off-the-shelf SDP solver (cvxpy/scs/mosek) exists in this environment, so
we implement Douglas-Rachford splitting on the conic form

    min  t
    s.t. <Q̃_e, Y> - 4 t + s_e = 0      for every constraint edge e   (s_e >= 0)
         <A_i, Y> = 0                   i = 1..N_T
         diag(Y) = 1
         Y ⪰ 0                          Y ∈ S^{n+1},  n = N_T · N_K

over the stacked variable  v = (vec(Y), t, s):

    f(v) = t + indicator{L v = b}       prox_f = affine projection of v - ρ·c
    g(v) = indicator{Y ⪰ 0, s >= 0}     prox_g = eigenvalue clip + relu

Two constraint-operator representations (DESIGN.md §5):

  - ``BQPData`` (dense oracle): rows assembled from the materialized Q̃
    stacks, Gram inverse precomputed — the reference path for small n.
  - ``FactoredBQP`` (matrix-free): CSR rows and the Gram matrix are
    assembled directly from the Kronecker factors via
    ``FactoredBQP.constraint_row`` — no dense L and no (|E|, n, n) stack
    ever exists.  For large row counts the Gram solve uses a Cholesky
    factorization instead of an explicit inverse.

Two solver backends, selected by ``SDPOptions.backend`` (parallel to the
rounding backends in ``rounding.py``):

  - ``numpy`` — the float64 host reference: one eigendecomposition of Y per
    iteration, scipy/LAPACK affine projection.  Ground truth for tests.
  - ``jax``   — the device-resident hot loop: the whole DR iteration
    (CSR constraint matvecs via ``segment_sum``, Cholesky triangular solves
    for the affine projection, cone projection) runs inside ONE jitted
    ``lax.while_loop``; residuals are evaluated every ``check_every``
    iterations *on device*, so the loop never round-trips to host.  The
    O(n³) full eigendecomposition is replaced by a *partial-spectrum*
    projection: near convergence Y has only a handful of negative
    eigenvalues, so the solver tracks their subspace across iterations with
    a warm-started shifted subspace iteration (O(n²·k) per step) and clips
    only the negative Ritz pairs, falling back to a full ``eigh`` whenever
    the tracked subspace saturates (``num_neg == k``), its Ritz residual
    stalls above ``eig_tol``, or the periodic ``eig_refresh`` resync fires.
  - ``auto``  — ``jax`` once n+1 exceeds ``jax_above`` (where the device
    loop wins even on CPU backends) and JAX is importable, else ``numpy``.

``solve_sdp`` additionally accepts a ``warm_start`` payload — the
``SDPSolution.state`` of a previous solve.  Re-solves after incremental
topology changes (elastic re-scheduling, gossip-FL speed updates) resume
from the previous (Y, t, s) iterate instead of the identity and converge in
far fewer iterations; ``scheduler.schedule(..., warm_start=True)`` keeps a
fingerprint-keyed cache of these payloads.

The solver is generic enough to be exercised on MAXCUT-style test SDPs.
"""

from __future__ import annotations

import dataclasses
import functools
import time
from typing import Any

import numpy as np

from repro import compat
from repro.core.bqp import BQPData, FactoredBQP

# Precision of every dot the device solver issues.  On TPU the default f32
# dot is ONE bf16 pass (relative error ~2e-3, in XLA and in Mosaic kernels
# alike): measured on a v5e, the DR loop then stops after 225 of the 1025
# iterations the float64 reference needs at n1 = 1025, with a meaningless
# bound, and on a 49-dim instance never reaches tol 1e-4 at all.  HIGHEST
# reproduces the float64 iteration count (PERF.md, Findings).
DOT_PRECISION = "highest"


@dataclasses.dataclass(frozen=True)
class SDPOptions:
    max_iters: int = 6000
    tol: float = 1e-6
    rho: float = 3.0            # prox step on the linear objective
    over_relax: float = 1.7     # DR relaxation parameter λ ∈ (0, 2)
    check_every: int = 25
    verbose: bool = False
    # §Perf (beyond-paper): the constraint rows are ~97% sparse (each Q̃_e
    # touches one task's column block + one machine block + borders), so the
    # affine projection runs on a CSR representation.  False reproduces the
    # dense paper-faithful baseline (same iterates, slower matvec); ignored
    # for ``FactoredBQP`` inputs, which are always CSR.
    sparse: bool = True
    # Above this many constraint rows the Gram solve switches from a
    # precomputed inverse to a Cholesky factorization (better conditioned,
    # and the triangular solves cost the same O(m²) as the inverse matvec).
    cholesky_above: int = 768
    # -- backend selection --------------------------------------------------
    # "numpy" (float64 host reference), "jax" (jitted device loop, float32),
    # or "auto": jax once n+1 > jax_above and JAX imports.
    backend: str = "auto"
    jax_above: int = 512
    # -- jax backend: partial-spectrum cone projection ----------------------
    # Size of the tracked negative-eigenspace basis (clamped to n+1); the
    # per-iteration cone projection costs O(n²·eig_k) instead of O(n³).
    eig_k: int = 16
    # Shifted subspace-iteration sweeps refining the tracked basis per DR
    # iteration (warm-started from the previous iteration's basis).
    eig_iters: int = 4
    # Ritz-residual threshold (relative to ‖Y‖_F) above which the tracked
    # subspace is declared stalled and the step falls back to a full eigh.
    eig_tol: float = 1e-3
    # Force a full-eigh resync every this many iterations (0 = only at the
    # first iteration); insurance against negative directions emerging
    # outside the tracked subspace.
    eig_refresh: int = 100
    # -- jax backend: Pallas fused-projection kernels (DESIGN.md §12) -------
    # "pallas" streams the dense iterate Y once per subspace sweep through
    # ``kernels.sdp_proj`` (fused matvec + Rayleigh-Ritz Gram + ‖Y‖², and a
    # fused rank-k clip update) — the memory-bound win recorded by
    # ``roofline.py::sdp_batch_profile``.  "jnp" keeps the plain-XLA cone
    # projection; "auto" picks pallas on an accelerator and jnp on CPU (on
    # CPU the kernels run in interpret mode — exact but slow, tests only).
    kernel_backend: str = "auto"


@dataclasses.dataclass
class SDPSolution:
    """Result of the SDP relaxation.

    Y: (n+1, n+1) PSD matrix with unit diagonal — the Gram matrix of the
       homogenized ±1 variables (last row/column is the homogenization
       variable u).
    t: epigraph value in *normalized* units; multiply by ``q_scale`` for the
       paper's units.  ``lower_bound`` is already rescaled.
    bound_certified: ``lower_bound`` is the Eq. 24 certificate only when the
       solver converged; when False the recorded value is the *unconverged
       iterate's* objective and must not be reported as a bound (it can
       exceed the achieved bottleneck — see BENCH_scheduler_scaling.json
       history at n=1664).
    Y_device: jax backend only — the normalized Y resident on device
       (float32), handed to the fused rounding backend so the covariance
       never leaves device between solve and rounding.
    state: warm-start payload (raw DR iterate ``w`` over (vec(Y), t, s) and,
       for the jax backend, the tracked eigenbasis ``V``); pass it back via
       ``solve_sdp(..., warm_start=...)`` to resume after an incremental
       topology change.
    """

    Y: np.ndarray
    t: float
    lower_bound: float
    iterations: int
    residual: float
    converged: bool
    solve_seconds: float
    bound_certified: bool = False
    # representation / memory diagnostics (constraint rows m, CSR nnz,
    # bytes of the largest tensor the solver materialized, solver backend,
    # full-vs-partial eigendecomposition counts)
    stats: dict = dataclasses.field(default_factory=dict)
    Y_device: Any = None
    state: dict = dataclasses.field(default_factory=dict, repr=False)


def _flatten_sym(mat: np.ndarray) -> np.ndarray:
    return mat.reshape(-1)


class _CSR:
    """Minimal CSR matrix for the constraint operator (numpy only)."""

    def __init__(self, rows: list[np.ndarray], dim: int):
        idx_list, val_list, ptr = [], [], [0]
        for r in rows:
            nz = np.nonzero(r)[0]
            idx_list.append(nz)
            val_list.append(r[nz])
            ptr.append(ptr[-1] + nz.size)
        self.indices = np.concatenate(idx_list)
        self.values = np.concatenate(val_list)
        self.indptr = np.asarray(ptr)
        self.row_of = np.repeat(
            np.arange(len(rows)), np.diff(self.indptr)
        )
        self.shape = (len(rows), dim)

    def matvec(self, v: np.ndarray) -> np.ndarray:
        prod = self.values * v[self.indices]
        return np.bincount(self.row_of, weights=prod, minlength=self.shape[0])

    def rmatvec(self, y: np.ndarray) -> np.ndarray:
        return np.bincount(
            self.indices,
            weights=self.values * y[self.row_of],
            minlength=self.shape[1],
        )


class _AffineProjector:
    """Projection onto {v : L v = b} with L built once from the BQP data.

    Accepts either the dense ``BQPData`` oracle (rows taken from the
    materialized Q̃ stack) or the matrix-free ``FactoredBQP`` (CSR rows and
    the Gram matrix assembled straight from the Kronecker factors).

    With ``keep_gram=True`` the host-side solve machinery (inverse /
    cho_factor) is skipped and the regularized Gram matrix is retained so
    the jax backend can export a clean lower Cholesky factor plus the raw
    CSR triplets (``export_csr`` / ``cholesky_lower``) to device.
    """

    def __init__(
        self,
        bqp: BQPData | FactoredBQP,
        sparse: bool = True,
        cholesky_above: int = 768,
        keep_gram: bool = False,
    ):
        n1 = bqp.n + 1                      # side of Y
        self.n1 = n1
        n_edges = len(bqp.edges)
        self.dim = n1 * n1 + 1 + n_edges    # Y_flat, t, s
        self.n_edges = n_edges
        self.m = n1 + bqp.n_tasks + n_edges
        self.stats: dict = {"constraint_rows": self.m}

        if isinstance(bqp, FactoredBQP):
            self._init_factored(bqp)
        else:
            self._init_dense(bqp, sparse)

        G = self._gram()
        G[np.diag_indices_from(G)] += 1e-10
        self.stats["gram_bytes"] = int(G.nbytes)
        self._G_keep = G if keep_gram else None
        if keep_gram:
            self._chol = False
            return
        self._chol = self.m > cholesky_above
        if self._chol:
            # Cholesky path for large m: two O(m²) triangular solves per
            # iteration; avoids forming (and squaring the conditioning of)
            # an explicit inverse.
            import scipy.linalg as sla

            self._G_factor = sla.cho_factor(G, lower=True)
            self._cho_solve = sla.cho_solve
        else:
            # G is fixed across iterations: precompute G⁻¹ once (m ≤ a few
            # hundred) — a dense matvec per iteration instead of two LU
            # solves (§Perf: the solves were 40% of iteration time).
            self._Ginv = np.linalg.inv(G)

    # -- construction -------------------------------------------------------
    def _init_dense(self, bqp: BQPData, sparse: bool):
        n1 = self.n1
        rows: list[np.ndarray] = []
        b: list[float] = []

        # diag(Y) = 1
        for d in range(n1):
            r = np.zeros(self.dim)
            r[d * n1 + d] = 1.0
            rows.append(r)
            b.append(1.0)

        # <A_i, Y> = 0
        for i in range(bqp.n_tasks):
            r = np.zeros(self.dim)
            r[: n1 * n1] = _flatten_sym(bqp.A[i])
            rows.append(r)
            b.append(0.0)

        # <Q̃_e, Y> - 4 t + s_e = 0   (normalized Q)
        qn = bqp.Q_tilde / bqp.q_scale
        for k in range(self.n_edges):
            r = np.zeros(self.dim)
            r[: n1 * n1] = _flatten_sym(qn[k])
            r[n1 * n1] = -4.0
            r[n1 * n1 + 1 + k] = 1.0
            rows.append(r)
            b.append(0.0)

        self.b = np.asarray(b)
        self._sparse = sparse
        L = np.stack(rows)                            # (m, dim)
        self._G = L @ L.T
        # rows list + stacked L coexist here: that transient is the dense
        # path's true build-time peak, recorded for the scaling benchmark.
        self.stats["build_peak_bytes"] = int(2 * L.nbytes)
        if sparse:
            self.L = _CSR(rows, self.dim)             # dense L is discarded
        else:
            self.L = L
        self.stats["representation"] = "dense"

    def _init_factored(self, fbqp: FactoredBQP):
        import scipy.sparse as sp

        n1, n = self.n1, fbqp.n
        n_t, n_k = fbqp.n_tasks, fbqp.n_machines
        cols: list[np.ndarray] = []
        vals: list[np.ndarray] = []
        rows: list[np.ndarray] = []
        b = np.zeros(self.m)

        # diag(Y) = 1
        diag_idx = np.arange(n1)
        rows.append(diag_idx)
        cols.append(diag_idx * n1 + diag_idx)
        vals.append(np.ones(n1))
        b[:n1] = 1.0

        # <A_i, Y> = 0: border h/2 on row & column of u, corner n_k - 2.
        # h selects (task i, machine κ) for all κ: vec indices i + κ·N_T.
        for i in range(n_t):
            h_idx = i + np.arange(n_k) * n_t
            r = n1 + i
            rows.append(np.full(2 * n_k + 1, r))
            cols.append(
                np.concatenate([h_idx * n1 + n, n * n1 + h_idx, [n * n1 + n]])
            )
            vals.append(
                np.concatenate([np.full(2 * n_k, 0.5), [n_k - 2.0]])
            )

        # <Q̃_e, Y> - 4 t + s_e = 0 with Q̃_e rows straight from the factors
        for k in range(self.n_edges):
            q_cols, q_vals = fbqp.constraint_row(k)
            r = n1 + n_t + k
            rows.append(np.full(q_cols.size + 2, r))
            cols.append(
                np.concatenate([q_cols, [n1 * n1, n1 * n1 + 1 + k]])
            )
            vals.append(
                np.concatenate([q_vals / fbqp.q_scale, [-4.0, 1.0]])
            )

        self.b = b
        self.L = sp.csr_matrix(
            (
                np.concatenate(vals),
                (np.concatenate(rows).astype(np.int64), np.concatenate(cols)),
            ),
            shape=(self.m, self.dim),
        )
        self._sparse = True
        self.stats["representation"] = "factored"
        self.stats["csr_nnz"] = int(self.L.nnz)

    def _gram(self) -> np.ndarray:
        if self.stats.get("representation") == "factored":
            return np.asarray((self.L @ self.L.T).todense())
        G = self._G
        del self._G
        return G

    # -- device export ------------------------------------------------------
    def export_csr(self) -> tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
        """(rows, cols, vals, b) COO triplets of L for the device backend."""
        if self.stats.get("representation") == "factored":
            coo = self.L.tocoo()
            return coo.row, coo.col, coo.data, self.b
        if isinstance(self.L, _CSR):
            return self.L.row_of, self.L.indices, self.L.values, self.b
        rows, cols = np.nonzero(self.L)
        return rows, cols, self.L[rows, cols], self.b

    def cholesky_lower(self) -> np.ndarray:
        """Lower Cholesky factor of the (regularized) Gram matrix.

        Only available under ``keep_gram=True`` — computed once on host in
        float64, then shipped to device for the per-iteration triangular
        solves.
        """
        if self._G_keep is None:
            raise RuntimeError("construct _AffineProjector with keep_gram=True")
        return np.linalg.cholesky(self._G_keep)

    # -- application --------------------------------------------------------
    def _solve_gram(self, resid: np.ndarray) -> np.ndarray:
        if self._chol:
            return self._cho_solve(self._G_factor, resid)
        return self._Ginv @ resid

    def __call__(self, v: np.ndarray) -> np.ndarray:
        if self.stats.get("representation") == "factored":
            resid = self.L @ v - self.b
            return v - self.L.T @ self._solve_gram(resid)
        if self._sparse:
            resid = self.L.matvec(v) - self.b
        else:
            resid = self.L @ v - self.b
        y = self._solve_gram(resid)
        if self._sparse:
            return v - self.L.rmatvec(y)
        return v - self.L.T @ y


def _project_cone(v: np.ndarray, n1: int, n_edges: int) -> np.ndarray:
    """Π onto {Y ⪰ 0 (symmetric), t free, s >= 0}."""
    out = v.copy()
    Y = v[: n1 * n1].reshape(n1, n1)
    Y = 0.5 * (Y + Y.T)
    w, V = np.linalg.eigh(Y)
    w = np.maximum(w, 0.0)
    out[: n1 * n1] = ((V * w) @ V.T).reshape(-1)
    if n_edges:
        s = v[n1 * n1 + 1 :]
        out[n1 * n1 + 1 :] = np.maximum(s, 0.0)
    return out


def _identity_start(n1: int, dim: int) -> np.ndarray:
    """Cold-start DR state: identity Gram matrix (feasible for diag & PSD)."""
    w = np.zeros(dim)
    w[: n1 * n1] = np.eye(n1).reshape(-1)
    return w


def _warm_w(warm_start: dict | None, dim: int) -> np.ndarray | None:
    """Validated warm-start iterate; None when absent, shape-mismatched, or
    non-finite (a diverged solve must not poison subsequent re-solves)."""
    if not warm_start:
        return None
    w = warm_start.get("w")
    if w is None:
        return None
    w = np.asarray(w, dtype=np.float64)
    if w.shape != (dim,) or not np.all(np.isfinite(w)):
        return None
    return w


# ---------------------------------------------------------------------------
# numpy backend (float64 host reference)
# ---------------------------------------------------------------------------


def _solve_numpy(
    bqp, opts: SDPOptions, proj: _AffineProjector, warm_start: dict | None
):
    n1, n_edges, dim = proj.n1, proj.n_edges, proj.dim

    c = np.zeros(dim)
    c[n1 * n1] = 1.0                     # objective: min t
    rho_c = opts.rho * c

    w = _warm_w(warm_start, dim)
    warm = w is not None
    if w is None:
        w = _identity_start(n1, dim)

    v_cone = w
    residual = np.inf
    it = 0
    lam = opts.over_relax
    for it in range(1, opts.max_iters + 1):
        v_aff = proj(w - rho_c)
        v_cone = _project_cone(2.0 * v_aff - w, n1, n_edges)
        step = v_cone - v_aff
        w = w + lam * step
        if it % opts.check_every == 0 or it == opts.max_iters:
            residual = float(np.linalg.norm(step) / np.sqrt(dim))
            if opts.verbose and it % (opts.check_every * 10) == 0:
                print(f"  sdp iter {it:5d} residual {residual:.3e}")
            if residual < opts.tol:
                break

    stats = {"solver_backend": "numpy", "warm_started": warm}
    state = {"w": w.copy()}
    return v_cone, it, residual, stats, state, None


# ---------------------------------------------------------------------------
# jax backend (jitted device-resident loop, partial-spectrum projection)
# ---------------------------------------------------------------------------
#
# One jit per (shape, static-option) signature, cached below.  The whole
# Douglas-Rachford iteration lives inside a ``lax.while_loop`` whose body
# runs ``check_every`` steps through a ``lax.fori_loop`` and then evaluates
# the residual — so a full solve is a single device computation with no host
# round-trips.  Scalars (rho, λ, tolerances, max_iters) are traced array
# arguments, so retuning them does not recompile.
#
# Two constraint-operator kinds mirror the host representations:
#
#   - "csr":      generic L·v / Lᵀ·y via ``segment_sum`` over the COO
#                 triplets — works for any projector (dense oracle, duck-
#                 typed test SDPs).  XLA lowers the transpose product to a
#                 serial scatter-add, so this is the small-instance path.
#   - "factored": L·v and Lᵀ·y assembled *structurally* from the Kronecker
#                 factors (p, d, C, src, dst) — the device analogue of
#                 ``FactoredBQP.inner``/``constraint_row``.  Everything is
#                 dense einsum/outer-product passes over the (K, T, K, T)
#                 grid plus O(|E|)-sized ``segment_sum`` aggregations, so no
#                 million-element scatter ever runs.  This is what makes the
#                 n ≥ 1024 hot loop fast on CPU devices too.


def _make_device_ops(kind: str, operands, n1: int, n_tasks: int, n_machines: int):
    """Constraint-operator closures (matvec, rmatvec, b) for ONE instance.

    Shared by the single-instance jit and — per vmapped lane — the batched
    solver: the operand arrays may be traced, so one builder serves both
    paths.  ``kind`` selects the generic COO/``segment_sum`` form ("csr")
    or the structural Kronecker-factor form ("factored").
    """
    import jax.numpy as jnp
    from jax.ops import segment_sum

    einsum = functools.partial(jnp.einsum, precision=DOT_PRECISION)
    dot = functools.partial(jnp.dot, precision=DOT_PRECISION)
    idx_t = n1 * n1

    if kind == "csr":
        Lval, Lrow, Lcol, b = operands
        m = b.shape[0]

        def matvec(v):
            return segment_sum(Lval * v[Lcol], Lrow, num_segments=m)

        def rmatvec(y, dim):
            return segment_sum(Lval * y[Lrow], Lcol, num_segments=dim)

        return matvec, rmatvec, b

    # Device analogue of the host CSR built by ``_init_factored``: row
    # r of L dotted with v (matvec) and Σ_r y_r · row_r (rmatvec), both
    # in closed form from the Kronecker factors.  Row layout:
    # [diag (n1) | A (n_tasks) | Q̃/q_scale with -4t + s (|E|)].
    p, d, C, src, dst, qs = operands
    T, K = n_tasks, n_machines
    n = T * K
    n_e = src.shape[0]
    C1 = jnp.sum(C, axis=1)
    Ct1 = jnp.sum(C, axis=0)
    P = jnp.sum(p)
    corner = jnp.sum(d) * P + jnp.sum(C)
    dp = jnp.outer(d, p)                       # (K, T) grid of d⊗p
    eyeK = jnp.eye(K, dtype=C.dtype)
    b = jnp.concatenate(
        [jnp.ones(n1, C.dtype), jnp.zeros(T + n_e, C.dtype)]
    )

    def matvec(v):
        F = v[:idx_t].reshape(n1, n1)
        Fs = 0.5 * (F + F.T)
        r_diag = jnp.diagonal(F)
        f_row = F[:n, n].reshape(K, T)
        f_col = F[n, :n].reshape(K, T)
        r_a = 0.5 * (f_row.sum(0) + f_col.sum(0)) + (K - 2.0) * F[n, n]
        # <Q̃_e, sym(F)> — same contraction as FactoredBQP.inner
        Fxx = Fs[:n, :n].reshape(K, T, K, T)
        f = Fs[:n, n].reshape(K, T)
        comp = einsum("k,t,ktks->s", d, p, Fxx)
        blocks = Fxx.transpose(1, 3, 0, 2)[src, dst]       # (|E|, K, K)
        comm = einsum("ekl,kl->e", blocks, C)
        base = einsum("k,t,kt->", d, p, f)
        u_i = dot(C1 + P * d, f)
        u_j = dot(Ct1, f)
        q1f = 0.5 * (base + u_i[src] + u_j[dst])
        inner = comp[src] + comm + 2.0 * q1f + corner * Fs[n, n]
        r_q = inner / qs - 4.0 * v[idx_t] + v[idx_t + 1 :]
        return jnp.concatenate([r_diag, r_a, r_q])

    def rmatvec(y, dim):
        y_d = y[:n1]
        y_a = y[n1 : n1 + T]
        y_raw = y[n1 + T :]
        y_q = y_raw / qs
        S = jnp.sum(y_q)
        c_i = segment_sum(y_q, src, num_segments=T)
        c_j = segment_sum(y_q, dst, num_segments=T)
        W2 = segment_sum(y_q, src * T + dst, num_segments=T * T)
        W2 = W2.reshape(T, T)
        # X-X block: Σ_e y_e · sym(D ⊗ (p δ_iᵀ) + C ⊗ (δ_i δ_jᵀ))
        M = 0.5 * (jnp.outer(p, c_i) + jnp.outer(c_i, p))
        Z = einsum("kl,k,ts->ktls", eyeK, d, M)
        T1 = einsum("kl,ts->ktls", C, W2)
        Z = Z + 0.5 * (T1 + T1.transpose(2, 3, 0, 1))
        # borders: Σ_e y_e q1_e + the A-row borders (0.5 per machine)
        g = 0.5 * (
            S * dp
            + jnp.outer(C1 + P * d, c_i)
            + jnp.outer(Ct1, c_j)
            + jnp.broadcast_to(y_a[None, :], (K, T))
        )
        g = g.reshape(-1)
        corner_y = S * corner + (K - 2.0) * jnp.sum(y_a)
        Y1 = jnp.zeros((n1, n1), y.dtype)
        Y1 = Y1.at[:n, :n].set(Z.reshape(n, n))
        Y1 = Y1.at[:n, n].add(g)
        Y1 = Y1.at[n, :n].add(g)
        Y1 = Y1.at[n, n].add(corner_y)
        di = jnp.arange(n1)
        Y1 = Y1.at[di, di].add(y_d)
        return jnp.concatenate(
            [Y1.reshape(-1), -4.0 * jnp.sum(y_raw)[None], y_raw]
        )

    return matvec, rmatvec, b




@functools.lru_cache(maxsize=16)
def _cone_fns(k: int, eig_iters: int, kernel_backend: str = "jnp"):
    """PSD-cone projection pair shared by the single and batched loops.

    ``cone_full`` is the O(n³) reference ``eigh`` and reseeds the tracked
    basis with the k most-negative eigenvectors; ``cone_partial`` refines a
    warm basis with ``eig_iters`` shifted subspace-iteration sweeps and
    clips only the negative Ritz pairs, reporting ``ok=False`` when the
    tracked subspace saturates or its Ritz residual exceeds eig_tol·σ.

    ``kernel_backend="pallas"`` runs the same sweep/Rayleigh-Ritz/clip
    sequence through the fused projection kernels (``kernels.sdp_proj``,
    DESIGN.md §12): each sweep's matvec also yields the small Gram and the
    shift norm from ONE stream of Y, and the rank-k clip never materializes
    its outer product — eig_iters+2 streams of Y per call instead of
    eig_iters+3 (plus the update temp).  The small solves (qr/eigh) are
    identical, so iterates agree with the jnp path to f32 roundoff.
    """
    import jax.numpy as jnp
    from jax import lax

    mm = functools.partial(jnp.matmul, precision=DOT_PRECISION)

    def cone_full(Y):
        ew, EV = jnp.linalg.eigh(Y)
        Yp = mm(EV * jnp.maximum(ew, 0.0), EV.T)
        return Yp, EV[:, :k]          # basis <- k most-negative eigvecs

    def _epilogue(Y, V, YV, G, sigma, eig_tol, clip_update):
        theta, U = jnp.linalg.eigh(G)            # Ritz values, ascending
        W = mm(V, U)
        neg = theta < 0.0
        # Ritz residual of the negative pairs: ‖Y w - θ w‖ certifies the
        # clip; saturation (num_neg == k) means negatives may extend
        # beyond the tracked subspace — both force the full-eigh path.
        R = mm(YV, U) - W * theta
        res = jnp.sqrt(jnp.sum(jnp.where(neg, jnp.sum(R * R, axis=0), 0.0)))
        ok = (jnp.sum(neg) < k) & (res <= eig_tol * jnp.maximum(sigma, 1.0))
        Yp = clip_update(Y, W, jnp.where(neg, theta, 0.0))
        return ok, Yp, W

    def cone_partial(Y, V, eig_tol):
        # Shifted subspace iteration on (σI - Y): its top-k invariant
        # subspace is Y's bottom-k.  σ = ‖Y‖_F ≥ λ_max keeps the shift
        # positive; the basis is warm (last iteration's), so a few
        # sweeps suffice near convergence.
        sigma = jnp.linalg.norm(Y)

        def sweep(_, Vc):
            Q, _ = jnp.linalg.qr(sigma * Vc - mm(Y, Vc))
            return Q

        V = lax.fori_loop(0, eig_iters, sweep, V)
        YV = mm(Y, V)
        return _epilogue(
            Y, V, YV, mm(V.T, YV), sigma, eig_tol,
            lambda Y, W, th: Y - mm(W * th, W.T),
        )

    def cone_partial_pallas(Y, V, eig_tol):
        from repro.kernels.ops import interpret_mode
        from repro.kernels.sdp_proj import rank_k_update_fwd, sdp_subspace_fwd

        interp = interpret_mode()
        YV, G, ss = sdp_subspace_fwd(Y, V, interpret=interp)
        sigma = jnp.sqrt(ss)

        def sweep(_, carry):
            Vc, YVc, _ = carry
            Q, _ = jnp.linalg.qr(sigma * Vc - YVc)
            return (Q,) + sdp_subspace_fwd(Y, Q, interpret=interp)[:2]

        V, YV, G = lax.fori_loop(0, eig_iters, sweep, (V, YV, G))
        return _epilogue(
            Y, V, YV, G, sigma, eig_tol,
            lambda Y, W, th: rank_k_update_fwd(Y, W * th, W, interpret=interp),
        )

    if kernel_backend == "pallas":
        return cone_full, cone_partial_pallas
    return cone_full, cone_partial


@functools.lru_cache(maxsize=32)
def _dr_jax_fn(
    n1: int,
    check_every: int,
    k: int,
    eig_iters: int,
    eig_refresh: int,
    kind: str,
    n_tasks: int,
    n_machines: int,
    kernel_backend: str = "jnp",
):
    """Build + jit the whole single-instance DR loop for one problem shape.

    Everything that changes the traced graph is in the cache key; scalars
    (rho, lam, tol, eig_tol, max_iters) stay traced arguments so retuning
    them never recompiles.
    """
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.scipy.linalg import solve_triangular

    idx_t = n1 * n1
    cone_full, cone_partial = _cone_fns(k, eig_iters, kernel_backend)

    def run(w0, V0, operands, CL, rho, lam, tol, eig_tol, max_iters):
        dim = w0.shape[0]
        matvec, rmatvec, b = _make_device_ops(
            kind, operands, n1, n_tasks, n_machines
        )

        def affine(v):
            resid = matvec(v) - b
            z = solve_triangular(CL, resid, lower=True)
            y = solve_triangular(CL.T, z, lower=False)
            return v - rmatvec(y, dim)

        def chunk(state):
            w, V, vc, it, res, nf, npart = state
            nsteps = jnp.minimum(check_every, max_iters - it)

            def body(j, carry):
                w, V, vc, nf, npart, _ = carry
                git = it + j
                if eig_refresh > 0:
                    force = git % eig_refresh == 0
                else:
                    force = git == 0
                v_aff = affine(w.at[idx_t].add(-rho))
                y = 2.0 * v_aff - w
                Y = y[:idx_t].reshape(n1, n1)
                Y = 0.5 * (Y + Y.T)
                ok, Yp_p, V_p = cone_partial(Y, V, eig_tol)
                use_full = force | ~ok
                Yp, Vn = lax.cond(
                    use_full,
                    lambda _: cone_full(Y),
                    lambda _: (Yp_p, V_p),
                    operand=None,
                )
                v_cone = jnp.concatenate(
                    [
                        Yp.reshape(-1),
                        y[idx_t : idx_t + 1],
                        jnp.maximum(y[idx_t + 1 :], 0.0),
                    ]
                )
                step = v_cone - v_aff
                w = w + lam * step
                nf = nf + use_full.astype(jnp.int32)
                npart = npart + (~use_full).astype(jnp.int32)
                return w, Vn, v_cone, nf, npart, jnp.sum(step * step)

            w, V, vc, nf, npart, sn = lax.fori_loop(
                0, nsteps, body, (w, V, vc, nf, npart, jnp.zeros((), w.dtype))
            )
            it = it + nsteps
            res = jnp.sqrt(sn / dim)
            return w, V, vc, it, res, nf, npart

        def cond(state):
            it, res = state[3], state[4]
            return (it < max_iters) & (res >= tol)

        zero = jnp.zeros((), jnp.int32)
        state = (w0, V0, w0, zero, jnp.asarray(jnp.inf, w0.dtype), zero, zero)
        return lax.while_loop(cond, chunk, state)

    return jax.jit(run)


@functools.lru_cache(maxsize=16)
def _dr_jax_batch_fn(
    n1: int,
    check_every: int,
    k: int,
    eig_iters: int,
    eig_refresh: int,
    kind: str,
    n_tasks: int,
    n_machines: int,
    kernel_backend: str = "jnp",
):
    """Build + jit the BATCHED DR loop: B same-shape instances, one dispatch.

    The per-instance math (constraint matvecs, affine projection, partial
    cone projection) is vmapped, but the loop itself is written manually
    rather than vmapping the single-instance body: under ``vmap`` a
    ``lax.cond`` lowers to a select that executes BOTH branches, which
    would run the O(n³) full eigh for the whole batch on every iteration.
    Instead the full-eigh fallback is a ``lax.scan`` over lanes with a
    per-lane ``lax.cond`` — under scan (unlike vmap) ``cond`` stays real
    control flow, so each step runs the full ``eigh`` for exactly the
    lanes that need it and no others (see the comment at the scan).  The
    ``eig_refresh`` schedule is batch-uniform, so each instance's
    full/partial decisions (and hence its iterates) match its own
    sequential solve.

    Per-instance convergence masking: every ``check_every`` steps the
    chunk's end state is merged with ``jnp.where(done, old, new)`` so
    converged instances freeze, ``it_conv`` records the iteration count at
    which each instance's residual first crossed ``tol`` (the sequential
    path's reported ``iterations``), and the while_loop exits once all
    instances are done or ``max_iters`` hits.
    """
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.scipy.linalg import solve_triangular

    idx_t = n1 * n1
    cone_full, cone_partial = _cone_fns(k, eig_iters, kernel_backend)

    def run(w0, V0, operands, CL, rho, lam, tol, eig_tol, max_iters):
        B, dim = w0.shape

        def one_affine(w_i, ops_i, CL_i):
            matvec, rmatvec, b = _make_device_ops(
                kind, ops_i, n1, n_tasks, n_machines
            )
            resid = matvec(w_i) - b
            z = solve_triangular(CL_i, resid, lower=True)
            y = solve_triangular(CL_i.T, z, lower=False)
            return w_i - rmatvec(y, dim)

        affine_b = jax.vmap(one_affine, in_axes=(0, 0, 0))
        cone_partial_b = jax.vmap(cone_partial, in_axes=(0, 0, None))

        def chunk(state):
            w, V, vc, it, res, done, it_conv, nf, npart = state
            nsteps = jnp.minimum(check_every, max_iters - it)

            def body(j, carry):
                w, V, vc, nf, npart, _ = carry
                git = it + j
                if eig_refresh > 0:
                    force = git % eig_refresh == 0
                else:
                    force = git == 0
                v_aff = affine_b(w.at[:, idx_t].add(-rho), operands, CL)
                y = 2.0 * v_aff - w
                Y = y[:, :idx_t].reshape(B, n1, n1)
                Y = 0.5 * (Y + jnp.transpose(Y, (0, 2, 1)))
                ok, Yp_p, V_p = cone_partial_b(Y, V, eig_tol)
                use_full = force | ~ok                        # (B,)

                # Per-lane full-eigh fallback WITHOUT batch amplification.
                # Under vmap a cond lowers to a select that evaluates both
                # branches, and a batch-level cond(any(use_full)) charges
                # the O(n1³) batched eigh to every lane whenever ONE lane
                # fails — with B lanes failing independently at rate p the
                # trigger fires at rate 1-(1-p)^B ≈ 1, so the "fallback"
                # becomes the steady state.  A lax.scan over lanes keeps
                # cond as real control flow (scan bodies run sequentially),
                # so each step pays the full projection for exactly the
                # lanes that need it — the same cost profile as B
                # sequential solves.  The scan itself still re-stacks
                # (Yp, V) for all B lanes, so an outer batch-level cond
                # skips it entirely on the common no-failure iteration
                # (identity: the scan with use_full all-False returns
                # exactly (Yp_p, V_p)).
                def lane(_, xs):
                    Y_i, Yp_i, V_i, uf = xs
                    Yp_i, V_i = lax.cond(
                        uf, lambda: cone_full(Y_i), lambda: (Yp_i, V_i)
                    )
                    return None, (Yp_i, V_i)

                def scan_lanes():
                    _, out = lax.scan(
                        lane, None, (Y, Yp_p, V_p, use_full)
                    )
                    return out

                Yp, Vn = lax.cond(
                    jnp.any(use_full), scan_lanes, lambda: (Yp_p, V_p)
                )
                v_cone = jnp.concatenate(
                    [
                        Yp.reshape(B, -1),
                        y[:, idx_t : idx_t + 1],
                        jnp.maximum(y[:, idx_t + 1 :], 0.0),
                    ],
                    axis=1,
                )
                step = v_cone - v_aff
                w = w + lam * step
                nf = nf + use_full.astype(jnp.int32)
                npart = npart + (~use_full).astype(jnp.int32)
                return w, Vn, v_cone, nf, npart, jnp.sum(step * step, axis=1)

            w2, V2, vc2, nf2, npart2, sn = lax.fori_loop(
                0,
                nsteps,
                body,
                (w, V, vc, nf, npart, jnp.zeros((B,), w.dtype)),
            )
            it2 = it + nsteps
            res_b = jnp.sqrt(sn / dim)
            # Freeze converged instances: their iterate, basis, residual,
            # and eig counters keep the values they had at first crossing.
            keep = done[:, None]
            w = jnp.where(keep, w, w2)
            V = jnp.where(done[:, None, None], V, V2)
            vc = jnp.where(keep, vc, vc2)
            nf = jnp.where(done, nf, nf2)
            npart = jnp.where(done, npart, npart2)
            res = jnp.where(done, res, res_b)
            newly = (~done) & (res_b < tol)
            it_conv = jnp.where(newly, it2, it_conv)
            done = done | newly
            return w, V, vc, it2, res, done, it_conv, nf, npart

        def cond(state):
            it, done = state[3], state[5]
            return (it < max_iters) & ~jnp.all(done)

        zero_b = jnp.zeros((B,), jnp.int32)
        state = (
            w0,
            V0,
            w0,
            jnp.zeros((), jnp.int32),
            jnp.full((B,), jnp.inf, w0.dtype),
            jnp.zeros((B,), bool),
            zero_b,
            zero_b,
            zero_b,
        )
        return lax.while_loop(cond, chunk, state)

    return jax.jit(run)


@functools.lru_cache(maxsize=8)
def _normalize_y_fn(n1: int):
    import jax
    import jax.numpy as jnp

    @jax.jit
    def normalize(vc):
        Y = vc[: n1 * n1].reshape(n1, n1)
        Y = 0.5 * (Y + Y.T)
        d = jnp.sqrt(jnp.clip(jnp.diag(Y), 1e-12, None))
        Y = Y / jnp.outer(d, d)
        eye = jnp.eye(n1, dtype=bool)
        return jnp.where(eye, 1.0, Y)

    return normalize


@functools.lru_cache(maxsize=8)
def _normalize_y_batch_fn(n1: int):
    import jax
    import jax.numpy as jnp

    @jax.jit
    def normalize(vc):                                    # vc: (B, dim)
        Y = vc[:, : n1 * n1].reshape(-1, n1, n1)
        Y = 0.5 * (Y + jnp.transpose(Y, (0, 2, 1)))
        d = jnp.sqrt(jnp.clip(jnp.diagonal(Y, axis1=1, axis2=2), 1e-12, None))
        Y = Y / (d[:, :, None] * d[:, None, :])
        eye = jnp.eye(n1, dtype=bool)
        return jnp.where(eye[None], 1.0, Y)

    return normalize


def _host_operands(bqp, proj: _AffineProjector):
    """Host-side operand arrays for ``_make_device_ops``.

    Returns ``(kind, n_tasks, n_machines, arrays)`` with float32/int32
    numpy leaves so a single solve can push them straight to device and a
    batched solve can ``np.stack`` the per-instance leaves first.
    """
    if isinstance(bqp, FactoredBQP):
        arrays = (
            np.asarray(bqp.p, np.float32),
            np.asarray(bqp.d, np.float32),
            np.asarray(bqp.C, np.float32),
            np.asarray(bqp.src, np.int32),
            np.asarray(bqp.dst, np.int32),
            np.asarray(bqp.q_scale, np.float32),
        )
        return "factored", bqp.n_tasks, bqp.n_machines, arrays
    rows, cols, vals, b = proj.export_csr()
    arrays = (
        np.asarray(vals, np.float32),
        np.asarray(rows, np.int32),
        np.asarray(cols, np.int32),
        np.asarray(b, np.float32),
    )
    return "csr", 0, 0, arrays


def _solve_jax(bqp, opts: SDPOptions, proj: _AffineProjector, warm_start: dict | None):
    import jax.numpy as jnp

    n1, dim = proj.n1, proj.dim
    CL = proj.cholesky_lower()
    k = min(opts.eig_k, n1)
    dtype = jnp.float32

    kind, n_t, n_k, host_ops = _host_operands(bqp, proj)
    operands = tuple(jnp.asarray(a) for a in host_ops)

    w_np = _warm_w(warm_start, dim)
    warm = w_np is not None
    if w_np is None:
        w_np = _identity_start(n1, dim)
    V_np = warm_start.get("V") if warm_start else None
    if V_np is None or np.asarray(V_np).shape != (n1, k):
        V_np = np.eye(n1, k)   # placeholder; iteration 0 full-eigh reseeds it

    kernel_backend = _resolve_kernel_backend(opts)
    run = _dr_jax_fn(
        n1, opts.check_every, k, opts.eig_iters, opts.eig_refresh, kind, n_t,
        n_k, kernel_backend
    )
    w, V, v_cone, it, residual, n_full, n_partial = run(
        jnp.asarray(w_np, dtype),
        jnp.asarray(V_np, dtype),
        operands,
        jnp.asarray(CL, dtype),
        jnp.asarray(opts.rho, dtype),
        jnp.asarray(opts.over_relax, dtype),
        jnp.asarray(opts.tol, dtype),
        jnp.asarray(opts.eig_tol, dtype),
        jnp.asarray(opts.max_iters, jnp.int32),
    )
    Y_device = _normalize_y_fn(n1)(v_cone)

    stats = {
        "solver_backend": "jax",
        "solver_dtype": "float32",
        "kernel_backend": kernel_backend,
        "constraint_kind": kind,
        "warm_started": warm,
        "eig_full": int(n_full),
        "eig_partial": int(n_partial),
        "eig_k": k,
    }
    state = {"w": np.asarray(w, np.float64), "V": np.asarray(V, np.float64)}
    v_cone_host = np.asarray(v_cone, np.float64)
    return v_cone_host, int(it), float(residual), stats, state, Y_device


# Count of batched jit dispatches — smoke tests assert a B-instance solve
# increments this by exactly one (i.e. the batch really was ONE dispatch).
_BATCH_RUN_CALLS = 0


class _BatchShapeError(ValueError):
    """Same-shape instances whose device operands still disagree in shape

    (e.g. CSR exports with different sparsity counts) — the caller falls
    back to sequential solves instead of crashing.
    """


def _solve_jax_batch(bqps, opts: SDPOptions, projs, warm_starts):
    """Stack B same-shape instances and run the batched DR jit ONCE."""
    import jax.numpy as jnp

    global _BATCH_RUN_CALLS
    B = len(bqps)
    n1, dim = projs[0].n1, projs[0].dim
    k = min(opts.eig_k, n1)
    dtype = jnp.float32

    host = [_host_operands(bqp, proj) for bqp, proj in zip(bqps, projs)]
    kind, n_t, n_k, _ = host[0]
    for kk, tt, mm, arrays in host[1:]:
        if (kk, tt, mm) != (kind, n_t, n_k) or any(
            a.shape != a0.shape for a, a0 in zip(arrays, host[0][3])
        ):
            raise _BatchShapeError(
                "instance device operands disagree in kind or shape"
            )
    operands = tuple(
        jnp.asarray(np.stack([h[3][i] for h in host]))
        for i in range(len(host[0][3]))
    )
    CL = jnp.asarray(np.stack([p.cholesky_lower() for p in projs]), dtype)

    w_stack, V_stack, warm_flags = [], [], []
    for ws in warm_starts:
        w_np = _warm_w(ws, dim)
        warm_flags.append(w_np is not None)
        if w_np is None:
            w_np = _identity_start(n1, dim)
        V_np = ws.get("V") if ws else None
        if V_np is None or np.asarray(V_np).shape != (n1, k):
            V_np = np.eye(n1, k)   # placeholder; iteration 0 full-eigh reseeds
        w_stack.append(np.asarray(w_np, np.float32))
        V_stack.append(np.asarray(V_np, np.float32))

    kernel_backend = _resolve_kernel_backend(opts)
    run = _dr_jax_batch_fn(
        n1, opts.check_every, k, opts.eig_iters, opts.eig_refresh, kind, n_t,
        n_k, kernel_backend
    )
    _BATCH_RUN_CALLS += 1
    w, V, v_cone, it, res, done, it_conv, n_full, n_partial = run(
        jnp.asarray(np.stack(w_stack)),
        jnp.asarray(np.stack(V_stack)),
        operands,
        CL,
        jnp.asarray(opts.rho, dtype),
        jnp.asarray(opts.over_relax, dtype),
        jnp.asarray(opts.tol, dtype),
        jnp.asarray(opts.eig_tol, dtype),
        jnp.asarray(opts.max_iters, jnp.int32),
    )
    Y_device = _normalize_y_batch_fn(n1)(v_cone)

    it_total = int(it)
    out = []
    for i in range(B):
        stats = {
            "solver_backend": "jax",
            "solver_dtype": "float32",
            "kernel_backend": kernel_backend,
            "constraint_kind": kind,
            "warm_started": warm_flags[i],
            "eig_full": int(n_full[i]),
            "eig_partial": int(n_partial[i]),
            "eig_k": k,
            "batch": B,
            "batch_index": i,
            "batch_dispatches": 1,
        }
        state = {
            "w": np.asarray(w[i], np.float64),
            "V": np.asarray(V[i], np.float64),
        }
        # A converged instance reports the iteration at which its residual
        # first crossed tol (it froze there), NOT the global loop count.
        it_i = int(it_conv[i]) if bool(done[i]) else it_total
        out.append(
            (
                np.asarray(v_cone[i], np.float64),
                it_i,
                float(res[i]),
                stats,
                state,
                Y_device[i],
            )
        )
    return out


# ---------------------------------------------------------------------------
# dispatcher
# ---------------------------------------------------------------------------


def _resolve_backend(opts: SDPOptions, n1: int) -> str:
    if opts.backend == "auto":
        if n1 > opts.jax_above and compat.jax_available():
            return "jax"
        return "numpy"
    if opts.backend not in ("numpy", "jax"):
        raise ValueError(
            f"unknown SDP backend {opts.backend!r}; "
            "choose from ('auto', 'numpy', 'jax')"
        )
    return opts.backend


def _resolve_kernel_backend(opts: SDPOptions) -> str:
    """Pick the cone-projection kernel lane for the jax backend.

    "auto" = the fused Pallas kernels on an accelerator, plain XLA on CPU
    (in interpret mode the kernels are exact but orders of magnitude
    slower, so CPU only runs them when asked explicitly — tests and the
    differential harness do).
    """
    kb = opts.kernel_backend
    if kb not in ("auto", "jnp", "pallas"):
        raise ValueError(
            f"unknown kernel backend {kb!r}; "
            "choose from ('auto', 'jnp', 'pallas')"
        )
    if kb == "auto":
        from repro.kernels.ops import interpret_mode

        return "jnp" if interpret_mode() else "pallas"
    return kb


def solve_sdp(
    bqp: BQPData | FactoredBQP,
    options: SDPOptions | None = None,
    warm_start: dict | None = None,
) -> SDPSolution:
    """Douglas-Rachford splitting for the relaxed problem (20).

    ``warm_start`` takes the ``state`` payload of a previous ``SDPSolution``
    (same problem dimensions); mismatched payloads are silently ignored and
    the solve cold-starts from the identity.
    """
    opts = options or SDPOptions()
    t0 = time.perf_counter()
    backend = _resolve_backend(opts, bqp.n + 1)
    if backend == "jax" and not compat.jax_available():
        # "auto" already degraded to numpy in _resolve_backend; an *explicit*
        # jax request must fail loudly rather than silently run the host
        # loop at a fraction of the speed.
        raise ImportError(
            "SDPOptions(backend='jax') requested but jax is not importable; "
            "use backend='auto' (or 'numpy') for a host fallback"
        )

    proj = _AffineProjector(
        bqp,
        sparse=opts.sparse,
        cholesky_above=opts.cholesky_above,
        keep_gram=backend == "jax",
    )
    if backend == "jax":
        v_cone, it, residual, bstats, state, Y_device = _solve_jax(
            bqp, opts, proj, warm_start
        )
    else:
        v_cone, it, residual, bstats, state, Y_device = _solve_numpy(
            bqp, opts, proj, warm_start
        )
    return _finish_solution(
        bqp, opts, proj, v_cone, it, residual, bstats, state, Y_device,
        time.perf_counter() - t0,
    )


def _finish_solution(
    bqp,
    opts: SDPOptions,
    proj: _AffineProjector,
    v_cone: np.ndarray,
    it: int,
    residual: float,
    bstats: dict,
    state: dict,
    Y_device,
    seconds: float,
) -> SDPSolution:
    """Host post-processing shared by single and batched solves."""
    n1 = proj.n1

    # Extract Y from the cone side (guaranteed PSD up to the projection
    # tolerance), renormalize diagonal to 1 so it is a valid Gaussian
    # covariance for rounding.
    Y = v_cone[: n1 * n1].reshape(n1, n1)
    Y = 0.5 * (Y + Y.T)
    d = np.sqrt(np.clip(np.diag(Y), 1e-12, None))
    Y = Y / np.outer(d, d)
    np.fill_diagonal(Y, 1.0)

    t_val = float(v_cone[n1 * n1])
    # SDP bound on OPT (Eq. 24): at the optimum t* = max_e <Q̃_e, Y*>/4.
    # NOTE: a first-order iterate only *approximates* the SDP optimum, so
    # this is a certified lower bound only once ``converged`` — the
    # ``bound_certified`` flag records exactly that, and callers
    # (Schedule.info, benchmarks) must not report uncertified values.
    if isinstance(bqp, FactoredBQP):
        t_from_y = float(np.max(bqp.inner(Y)) / bqp.q_scale / 4.0)
    else:
        qn = bqp.Q_tilde / bqp.q_scale
        t_from_y = float(np.max(np.einsum("eij,ij->e", qn, Y)) / 4.0)
    lower = max(t_val, 0.0) * bqp.q_scale

    stats = dict(proj.stats)
    stats.update(bstats)
    # largest tensor the solve touched: the stacked DR variable dominates
    # for factored instances; the constraint-matrix build and the Q̃ stack
    # dominate dense ones.
    itemsize = 4 if stats.get("solver_backend") == "jax" else 8
    peak = max(
        3 * proj.dim * itemsize,
        stats.get("gram_bytes", 0),
        stats.get("build_peak_bytes", 0),
    )
    if isinstance(bqp, BQPData):
        peak = max(peak, int(bqp.Q_tilde.nbytes + bqp.Q.nbytes))
    stats["peak_tensor_bytes"] = int(peak)

    converged = residual < opts.tol
    return SDPSolution(
        Y=Y,
        t=max(t_val, t_from_y),
        lower_bound=lower,
        iterations=it,
        residual=residual,
        converged=converged,
        bound_certified=converged,
        solve_seconds=seconds,
        stats=stats,
        Y_device=Y_device,
        state=state,
    )


def solve_sdp_batch(
    bqps,
    options: SDPOptions | None = None,
    warm_starts=None,
) -> list[SDPSolution]:
    """Solve B same-shape instances in ONE jitted batched DR dispatch.

    All instances must share representation type, ``n``, ``n_tasks``,
    ``n_machines``, and constraint-edge count; their weights (p, d, C,
    q_scale / CSR values) are free to differ — they become the vmapped
    batch axis.  Per-instance convergence masking freezes instances the
    moment their residual crosses ``tol`` while stragglers keep iterating,
    so each returned ``SDPSolution`` matches its own sequential
    ``solve_sdp`` call (iterate, residual, iteration count) to float32
    tolerance.

    ``warm_starts`` is an optional list of per-instance ``state`` payloads
    (``None`` entries cold-start that lane).  Backend resolution differs
    from ``solve_sdp``: "auto" takes the batched jax path whenever JAX is
    importable regardless of ``jax_above`` — amortizing dispatch overhead
    across the batch is the whole point — while "numpy" (or a missing JAX
    under "auto") degrades to B sequential host solves.

    Per-instance ``solve_seconds`` is the batch wall time divided by B;
    the full wall time is in ``stats["batch_seconds"]``.
    """
    opts = options or SDPOptions()
    bqps = list(bqps)
    if not bqps:
        return []
    if warm_starts is None:
        warm_starts = [None] * len(bqps)
    warm_starts = list(warm_starts)
    if len(warm_starts) != len(bqps):
        raise ValueError("warm_starts must have one entry per instance")

    first = bqps[0]
    for b in bqps[1:]:
        if (
            type(b) is not type(first)
            or b.n != first.n
            or b.n_tasks != first.n_tasks
            or b.n_machines != first.n_machines
            or len(b.edges) != len(first.edges)
        ):
            raise ValueError(
                "solve_sdp_batch requires same-shape instances "
                "(same type, n, n_tasks, n_machines, and edge count)"
            )

    if opts.backend == "jax" and not compat.jax_available():
        raise ImportError(
            "SDPOptions(backend='jax') requested but jax is not importable; "
            "use backend='auto' (or 'numpy') for a host fallback"
        )
    if opts.backend == "numpy" or not compat.jax_available():
        return [solve_sdp(b, opts, ws) for b, ws in zip(bqps, warm_starts)]

    t0 = time.perf_counter()
    projs = [
        _AffineProjector(
            b,
            sparse=opts.sparse,
            cholesky_above=opts.cholesky_above,
            keep_gram=True,
        )
        for b in bqps
    ]
    try:
        raw = _solve_jax_batch(bqps, opts, projs, warm_starts)
    except _BatchShapeError:
        return [solve_sdp(b, opts, ws) for b, ws in zip(bqps, warm_starts)]
    total = time.perf_counter() - t0

    sols = []
    for bqp, proj, (v_cone, it, residual, bstats, state, Y_dev) in zip(
        bqps, projs, raw
    ):
        bstats = dict(bstats)
        bstats["batch_seconds"] = total
        sols.append(
            _finish_solution(
                bqp, opts, proj, v_cone, it, residual, bstats, state, Y_dev,
                total / len(bqps),
            )
        )
    return sols
