"""Differential kernel harness: fused Pallas ops vs pure-jnp oracles.

Every fused kernel behind the scheduler/FL backend switches is pinned
three ways:

  1. ``assert_kernel_matches_ref`` sweeps shapes (block-ragged sizes,
     B ∈ {1, 8}, k ∈ {1, small, n}), dtypes (f32/bf16), and degenerate
     inputs (zero matrices, rank-1 Y, all-negative spectrum) against the
     oracles in ``repro.kernels.ref``;
  2. seeded end-to-end regressions: ``solve_sdp`` / ``solve_sdp_batch``
     with ``kernel_backend="pallas"`` reproduce the jnp path's iteration
     count and projection decisions exactly and the iterate to f32
     tolerance (mirroring ``tests/test_sdp_batch.py``), and the fused
     rounding with the bottleneck kernel returns the identical
     assignment;
  3. randomized-shape property tests live in ``tests/test_property.py``.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    ComputeGraph,
    SDPOptions,
    TaskGraph,
    build_factored_bqp,
    random_compute_graph,
    random_task_graph,
    randomized_rounding,
    solve_sdp,
    solve_sdp_batch,
)
from repro.kernels import ref as kref
from repro.kernels.bottleneck import bottleneck_eval_fwd
from repro.kernels.compress import int8_roundtrip_fwd, topk_mask_fwd
from repro.kernels.sdp_proj import rank_k_update_fwd, sdp_subspace_fwd

# float32 loop, two lowerings: agreement at a converged iterate is a few
# f32 ulps over n²-sized contractions (same constant as test_sdp_batch)
F32_ATOL = 1e-3

rng = np.random.default_rng(0)


def assert_kernel_matches_ref(kernel_fn, ref_fn, args, *, atol=1e-5,
                              rtol=1e-5, exact=False, kwargs=None):
    """Run kernel and oracle on ``args``; compare every output in f32.

    ``kwargs`` go to the kernel only (block sizes, ``interpret=True``);
    the oracle takes the math inputs alone.  ``exact=True`` demands
    bit-equality (selection/masking kernels have no roundoff freedom).
    """
    got = kernel_fn(*args, **(kwargs or {}))
    want = ref_fn(*args)
    if not isinstance(got, tuple):
        got, want = (got,), (want,)
    assert len(got) == len(want)
    for idx, (g, w) in enumerate(zip(got, want)):
        g = np.asarray(jnp.asarray(g).astype(jnp.float32))
        w = np.asarray(jnp.asarray(w).astype(jnp.float32))
        assert g.shape == w.shape, (idx, g.shape, w.shape)
        assert np.all(np.isfinite(w)), f"oracle output {idx} not finite"
        if exact:
            np.testing.assert_array_equal(g, w, err_msg=f"output {idx}")
        else:
            np.testing.assert_allclose(
                g, w, atol=atol, rtol=rtol, err_msg=f"output {idx}"
            )


def t(shape, dt=jnp.float32):
    return jnp.asarray(rng.standard_normal(shape), dt)


# ---------------------------------------------------------------------------
# (a) SDP fused subspace projection + rank-k clip
# ---------------------------------------------------------------------------

SDP_SHAPES = [
    # (n, k, block_rows): ragged and aligned blockings, k ∈ {1, small, n}
    (5, 1, 2),
    (8, 3, 3),
    (16, 16, 16),
    (33, 4, 8),
    (7, 7, 256),   # block larger than the matrix
]


@pytest.mark.parametrize("n,k,bn", SDP_SHAPES)
@pytest.mark.parametrize("dt", [jnp.float32, jnp.bfloat16])
def test_sdp_subspace_shapes(n, k, bn, dt):
    Y = t((n, n), dt)
    Y = Y + Y.T
    V = jnp.asarray(
        np.linalg.qr(rng.standard_normal((n, k)))[0], dt
    )
    assert_kernel_matches_ref(
        sdp_subspace_fwd, kref.sdp_subspace_ref, (Y, V),
        atol=1e-4 * n, rtol=1e-4,
        kwargs=dict(block_rows=bn, interpret=True),
    )


@pytest.mark.parametrize("n,k,bn", SDP_SHAPES)
@pytest.mark.parametrize("dt", [jnp.float32, jnp.bfloat16])
def test_rank_k_update_shapes(n, k, bn, dt):
    Y, A, B = t((n, n), dt), t((n, k), dt), t((n, k), dt)
    atol = 0.05 if dt == jnp.bfloat16 else 1e-5
    assert_kernel_matches_ref(
        rank_k_update_fwd, kref.rank_k_update_ref, (Y, A, B),
        atol=atol, rtol=1e-4,
        kwargs=dict(block_rows=bn, interpret=True),
    )


def _degenerate_Y(kind, n):
    if kind == "zero":
        return jnp.zeros((n, n), jnp.float32)
    if kind == "rank1":
        u = rng.standard_normal(n)
        return jnp.asarray(np.outer(u, u), jnp.float32)
    # all-negative spectrum: -A Aᵀ - I forces every Ritz value negative
    A = rng.standard_normal((n, n))
    return jnp.asarray(-A @ A.T - np.eye(n), jnp.float32)


@pytest.mark.parametrize("kind", ["zero", "rank1", "negative"])
def test_sdp_subspace_degenerate(kind):
    n, k = 12, 3
    Y = _degenerate_Y(kind, n)
    V = jnp.asarray(np.linalg.qr(rng.standard_normal((n, k)))[0],
                    jnp.float32)
    assert_kernel_matches_ref(
        sdp_subspace_fwd, kref.sdp_subspace_ref, (Y, V),
        atol=1e-3, rtol=1e-4,
        kwargs=dict(block_rows=5, interpret=True),
    )
    assert_kernel_matches_ref(
        rank_k_update_fwd, kref.rank_k_update_ref, (Y, V, V),
        atol=1e-4, rtol=1e-4,
        kwargs=dict(block_rows=5, interpret=True),
    )


# ---------------------------------------------------------------------------
# (b) fused delta compression with error feedback
# ---------------------------------------------------------------------------

COMPRESS_SHAPES = [
    # (n_users, L, block_len): ragged tails, B ∈ {1, 8}, single-element L
    (1, 7, 3),
    (8, 100, 64),
    (8, 64, 64),
    (3, 1, 4),
]


@pytest.mark.parametrize("n,l,bl", COMPRESS_SHAPES)
@pytest.mark.parametrize("dt", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("kk", ["one", "small", "all"])
def test_topk_mask_shapes(n, l, bl, dt, kk):
    X = t((n, l), dt)
    kk = {"one": 1, "small": max(1, l // 10), "all": l}[kk]
    vals, _ = jax.lax.top_k(jnp.abs(X.astype(jnp.float32)), kk)
    thresh = vals[:, -1]
    # pure selection: the fused kernel must be bit-equal to the oracle
    assert_kernel_matches_ref(
        topk_mask_fwd, kref.topk_mask_ref, (X, thresh), exact=True,
        kwargs=dict(block_len=bl, interpret=True),
    )


@pytest.mark.parametrize("n,l,bl", COMPRESS_SHAPES)
@pytest.mark.parametrize("dt", [jnp.float32, jnp.bfloat16])
def test_int8_roundtrip_shapes(n, l, bl, dt):
    X = t((n, l), dt)
    scale = (
        jnp.maximum(jnp.max(jnp.abs(X.astype(jnp.float32)), axis=1), 1e-12)
        / 127.0
    )
    # msgs bit-equal; the residual may differ by 1 ulp of |x| (FMA
    # contraction of q·scale into the subtraction — see compress.py)
    got = int8_roundtrip_fwd(X, scale, block_len=bl, interpret=True)
    want = kref.int8_roundtrip_ref(X, scale)
    np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(want[0]))
    atol = 0.05 if dt == jnp.bfloat16 else 2e-7
    np.testing.assert_allclose(
        np.asarray(got[1], np.float32), np.asarray(want[1], np.float32),
        atol=atol,
    )


def test_compress_degenerate_zero():
    X = jnp.zeros((4, 10), jnp.float32)
    assert_kernel_matches_ref(
        topk_mask_fwd, kref.topk_mask_ref, (X, jnp.zeros(4)), exact=True,
        kwargs=dict(block_len=4, interpret=True),
    )
    assert_kernel_matches_ref(
        int8_roundtrip_fwd, kref.int8_roundtrip_ref,
        (X, jnp.full(4, 1e-12 / 127.0)), exact=True,
        kwargs=dict(block_len=4, interpret=True),
    )


# ---------------------------------------------------------------------------
# (c) batched bottleneck evaluation (Eq. 2)
# ---------------------------------------------------------------------------


def _bottleneck_args(s, n_t, n_k, n_edges, seed=0):
    r = np.random.default_rng(seed)
    a = jnp.asarray(r.integers(0, n_k, size=(s, n_t)), jnp.int32)
    p = jnp.asarray(r.uniform(0.1, 5.0, n_t), jnp.float32)
    e = jnp.asarray(r.uniform(0.5, 4.0, n_k), jnp.float32)
    C = jnp.asarray(r.uniform(0.0, 3.0, (n_k, n_k)), jnp.float32)
    src = jnp.asarray(r.integers(0, n_t, n_edges), jnp.int32)
    dst = jnp.asarray(r.integers(0, n_t, n_edges), jnp.int32)
    return (a, p, e, C, src, dst)


BOTTLENECK_SHAPES = [
    # (samples, tasks, machines, edges, block_samples)
    (1, 3, 2, 4, 1),
    (8, 7, 4, 14, 3),     # ragged sample padding
    (8, 5, 1, 10, 8),     # single machine: comm delays all C[0,0]=0
    (8, 6, 3, 0, 4),      # edge-free task graph (E = 0)
]


@pytest.mark.parametrize("s,n_t,n_k,n_e,bs", BOTTLENECK_SHAPES)
def test_bottleneck_eval_shapes(s, n_t, n_k, n_e, bs):
    args = _bottleneck_args(s, n_t, n_k, n_e)
    assert_kernel_matches_ref(
        bottleneck_eval_fwd, kref.bottleneck_eval_ref, args,
        atol=1e-5, rtol=1e-5,
        kwargs=dict(block_samples=bs, interpret=True),
    )


# ---------------------------------------------------------------------------
# Seeded end-to-end regressions: kernels on == kernels off
# ---------------------------------------------------------------------------

jax_backend = pytest.importorskip("jax")

# converging settings on a size where the partial-spectrum (kernel) path
# carries most iterations
E2E_OPTS = dict(max_iters=3000, check_every=50, tol=1e-4, backend="jax")


@pytest.fixture(scope="module")
def sdp_instance():
    r = np.random.default_rng(7)
    tg = random_task_graph(r, 12, degree_low=2, degree_high=4)
    cg = random_compute_graph(r, 4)
    return tg, cg


@pytest.fixture(scope="module")
def e2e_solutions(sdp_instance):
    tg, cg = sdp_instance
    bqp = build_factored_bqp(tg, cg)
    return bqp, {
        kb: solve_sdp(bqp, SDPOptions(**E2E_OPTS, kernel_backend=kb))
        for kb in ("jnp", "pallas")
    }


def test_solve_sdp_kernel_backend_regression(e2e_solutions):
    """Fused projection on/off: identical trajectory, same iterate."""
    _, sols = e2e_solutions
    a, b = sols["jnp"], sols["pallas"]
    assert a.converged and b.converged
    assert a.iterations == b.iterations
    assert a.stats["eig_full"] == b.stats["eig_full"]
    assert a.stats["eig_partial"] == b.stats["eig_partial"]
    # the partial (kernel) path must actually carry iterations, else this
    # test pins nothing
    assert a.stats["eig_partial"] > 0
    np.testing.assert_allclose(b.Y, a.Y, atol=F32_ATOL)
    assert np.isclose(b.residual, a.residual, atol=F32_ATOL)


def test_solve_sdp_batch_kernel_backend_regression(sdp_instance):
    """Batched lanes inherit the same on/off equivalence, lane by lane."""
    tg, _ = sdp_instance
    cgs = [random_compute_graph(np.random.default_rng(100 + i), 4)
           for i in range(2)]
    bqps = [build_factored_bqp(tg, cg) for cg in cgs]
    sols = {
        kb: solve_sdp_batch(bqps, SDPOptions(**E2E_OPTS, kernel_backend=kb))
        for kb in ("jnp", "pallas")
    }
    for a, b in zip(sols["jnp"], sols["pallas"]):
        assert a.iterations == b.iterations
        assert a.stats["eig_full"] == b.stats["eig_full"]
        assert a.stats["eig_partial"] == b.stats["eig_partial"]
        np.testing.assert_allclose(b.Y, a.Y, atol=F32_ATOL)


def test_rounding_kernel_backend_parity(e2e_solutions, sdp_instance):
    """The bottleneck kernel scores every sample like the gather path:
    identical argmin assignment and feasibility count."""
    tg, cg = sdp_instance
    bqp, sols = e2e_solutions
    sol = sols["jnp"]
    results = {
        kb: randomized_rounding(
            bqp, tg, cg, sol.Y, num_samples=256,
            rng=np.random.default_rng(0), backend="jax",
            Y_device=sol.Y_device, kernel_backend=kb,
        )
        for kb in ("jnp", "pallas")
    }
    a, b = results["jnp"], results["pallas"]
    np.testing.assert_array_equal(a.assignment, b.assignment)
    assert np.isclose(a.bottleneck, b.bottleneck, rtol=1e-6)
    assert a.num_feasible == b.num_feasible


def test_rounding_kernel_backend_parity_edge_free():
    """E = 0 lane: the kernel skips its edge loop and changes nothing."""
    r = np.random.default_rng(3)
    tg = TaskGraph(p=r.uniform(0.5, 3.0, 6), edges=())
    cg = random_compute_graph(r, 3)
    bqp = build_factored_bqp(tg, cg)
    sol = solve_sdp(bqp, SDPOptions(max_iters=1500, check_every=50,
                                    tol=1e-4, backend="jax"))
    results = {
        kb: randomized_rounding(
            bqp, tg, cg, sol.Y, num_samples=128,
            rng=np.random.default_rng(0), backend="jax",
            Y_device=sol.Y_device, kernel_backend=kb,
        )
        for kb in ("jnp", "pallas")
    }
    a, b = results["jnp"], results["pallas"]
    np.testing.assert_array_equal(a.assignment, b.assignment)
    assert np.isclose(a.bottleneck, b.bottleneck, rtol=1e-6)


def test_kernel_backend_rejects_unknown(sdp_instance):
    tg, cg = sdp_instance
    bqp = build_factored_bqp(tg, cg)
    with pytest.raises(ValueError, match="kernel.?backend"):
        solve_sdp(bqp, SDPOptions(**E2E_OPTS, kernel_backend="cuda"))
    with pytest.raises(ValueError, match="kernel.?backend"):
        randomized_rounding(
            bqp, tg, cg,
            np.eye(tg.num_tasks * cg.num_machines + 1),
            num_samples=8, rng=np.random.default_rng(0), backend="jax",
            kernel_backend="cuda",
        )
