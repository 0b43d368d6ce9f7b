"""Property tests on scheduling, FL, and kernel invariants.

Uses the real ``hypothesis`` library when installed; otherwise falls back
to the seeded shim in ``tests/_minihypothesis.py`` (same API subset, no
shrinking) so the module runs everywhere instead of skipping.
"""

import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st

    HAVE_HYPOTHESIS = True
except ImportError:  # pragma: no cover - depends on the environment
    from _minihypothesis import given, settings, strategies as st

    HAVE_HYPOTHESIS = False

from repro.core import ComputeGraph, TaskGraph, bottleneck_time
from repro.core.bqp import bottleneck_time_batch, build_bqp, task_times
from repro.core.rounding import signs_to_assignments


@st.composite
def instances(draw):
    n_t = draw(st.integers(2, 8))
    n_k = draw(st.integers(2, 4))
    p = draw(
        st.lists(st.floats(0.01, 50.0), min_size=n_t, max_size=n_t)
    )
    e = draw(st.lists(st.floats(0.1, 20.0), min_size=n_k, max_size=n_k))
    edges = draw(
        st.lists(
            st.tuples(st.integers(0, n_t - 1), st.integers(0, n_t - 1)),
            max_size=n_t * 2,
        )
    )
    edges = tuple(sorted({(i, j) for (i, j) in edges if i != j}))
    c_seed = draw(st.integers(0, 2**31 - 1))
    C = np.random.default_rng(c_seed).uniform(0, 5, size=(n_k, n_k))
    np.fill_diagonal(C, 0.0)
    tg = TaskGraph(p=np.asarray(p), edges=edges)
    cg = ComputeGraph(e=np.asarray(e), C=C)
    a = np.asarray(
        draw(st.lists(st.integers(0, n_k - 1), min_size=n_t, max_size=n_t))
    )
    return tg, cg, a


@given(instances())
@settings(max_examples=60, deadline=None)
def test_batch_matches_scalar(inst):
    tg, cg, a = inst
    assert np.isclose(
        bottleneck_time(tg, cg, a), bottleneck_time_batch(tg, cg, a[None])[0]
    )


@given(instances(), st.floats(1.1, 4.0))
@settings(max_examples=40, deadline=None)
def test_speedup_monotone(inst, factor):
    """Uniformly faster machines can't increase the bottleneck (fixed A)."""
    tg, cg, a = inst
    t0 = bottleneck_time(tg, cg, a)
    faster = ComputeGraph(e=cg.e * factor, C=cg.C)
    assert bottleneck_time(tg, faster, a) <= t0 + 1e-9


@given(instances())
@settings(max_examples=40, deadline=None)
def test_extra_edge_monotone(inst):
    """Adding a dependency can only increase the bottleneck (fixed A)."""
    tg, cg, a = inst
    t0 = bottleneck_time(tg, cg, a)
    cand = [(i, j) for i in range(tg.num_tasks) for j in range(tg.num_tasks)
            if i != j and (i, j) not in tg.edges]
    if not cand:
        return
    tg2 = TaskGraph(p=tg.p, edges=tg.edges + (cand[0],))
    assert bottleneck_time(tg2, cg, a) >= t0 - 1e-9


@given(instances())
@settings(max_examples=40, deadline=None)
def test_comp_time_equals_machine_load(inst):
    tg, cg, a = inst
    t_comp, _ = task_times(tg, cg, a)
    loads = np.zeros(cg.num_machines)
    np.add.at(loads, a, tg.p)
    for i in range(tg.num_tasks):
        assert np.isclose(t_comp[i], loads[a[i]] / cg.e[a[i]])


@given(st.integers(2, 6), st.integers(2, 4), st.integers(0, 1000))
@settings(max_examples=40, deadline=None)
def test_rounding_repair_always_feasible(n_t, n_k, seed):
    """Any ±1 sample maps to a feasible one-machine-per-task assignment."""
    rng = np.random.default_rng(seed)
    z = rng.standard_normal((16, n_t * n_k + 1))
    signs = np.sign(z)
    signs[signs == 0] = 1
    assignments, _ = signs_to_assignments(signs, z, n_t, n_k)
    assert assignments.shape == (16, n_t)
    assert np.all((0 <= assignments) & (assignments < n_k))


@given(instances())
@settings(max_examples=30, deadline=None)
def test_bqp_scale_invariance(inst):
    """Scaling all Q̃ by q_scale must leave quadratic bottlenecks consistent."""
    tg, cg, a = inst
    data = build_bqp(tg, cg)
    assert data.q_scale > 0
    assert np.isfinite(data.Q_tilde).all()


# ---------------------------------------------------------------------------
# Barrier-free FL invariants: staleness weights and token-account flow
# ---------------------------------------------------------------------------


@st.composite
def staleness_weights(draw):
    from repro.fl.staleness import STALENESS_KINDS, StalenessWeights

    kind = draw(st.sampled_from(STALENESS_KINDS))
    a = draw(st.floats(0.0, 10.0, allow_nan=False))
    b = draw(st.integers(0, 10)) if kind == "hinge" else 0
    return StalenessWeights(kind=kind, a=a, b=b)


@given(staleness_weights())
@settings(max_examples=60, deadline=None)
def test_staleness_fresh_snapshot_has_unit_weight(sw):
    """s(0) = 1 for every kind/parameterization — the degenerate anchor."""
    assert sw(np.array([0]))[0] == 1.0
    # negative lags (clock skew artifacts) clamp to the fresh weight
    assert sw(np.array([-3]))[0] == 1.0


@given(staleness_weights())
@settings(max_examples=60, deadline=None)
def test_staleness_monotone_nonincreasing_and_bounded(sw):
    lags = np.arange(0, 25)
    w = sw(lags)
    assert np.all(w <= 1.0 + 1e-12) and np.all(w > 0.0)
    assert np.all(np.diff(w) <= 1e-12), (sw, w)
    # the jax path computes the same weights (float32 roundoff)
    jw = np.asarray(sw.jax_weights(lags))
    np.testing.assert_allclose(jw, w.astype(np.float32), rtol=1e-6, atol=1e-7)


def test_staleness_rejects_bad_params():
    from repro.fl.staleness import StalenessWeights

    with pytest.raises(ValueError, match="kind"):
        StalenessWeights(kind="exp")
    with pytest.raises(ValueError, match="a"):
        StalenessWeights(kind="poly", a=-0.5)
    with pytest.raises(ValueError, match="b"):
        StalenessWeights(kind="hinge", b=-1)


@given(
    st.floats(1.0, 16.0, allow_nan=False),
    st.floats(0.0, 8.0, allow_nan=False),
    st.lists(st.sampled_from(["send", "replenish"]), max_size=60),
)
@settings(max_examples=60, deadline=None)
def test_token_account_invariants(capacity, refill, ops):
    """0 <= tokens <= capacity always; between any two replenishes at most
    floor(capacity) sends succeed; every try_send is tallied."""
    from repro.sim.flow import TokenAccount

    acct = TokenAccount(capacity=capacity, refill=refill)
    assert acct.tokens == capacity
    sends_since_replenish = 0
    tries = 0
    for op in ops:
        if op == "send":
            tries += 1
            if acct.try_send():
                sends_since_replenish += 1
            assert sends_since_replenish <= int(np.floor(capacity))
        else:
            acct.replenish()
            sends_since_replenish = 0
        assert 0.0 <= acct.tokens <= capacity + 1e-12
    assert acct.sent + acct.skipped == tries


def test_token_account_rejects_bad_config():
    from repro.sim.flow import TokenAccount

    with pytest.raises(ValueError, match="capacity"):
        TokenAccount(capacity=0.5)
    with pytest.raises(ValueError, match="refill"):
        TokenAccount(capacity=2.0, refill=-1.0)


# ---------------------------------------------------------------------------
# Fused-kernel invariants: the Pallas ops agree with Eq. 2 / the compressors
# on randomized shapes, not just the hand-picked sweeps in test_kernel_diff
# ---------------------------------------------------------------------------


@given(instances())
@settings(max_examples=25, deadline=None)
def test_kernel_bottleneck_matches_eq2(inst):
    """The bottleneck kernel == the float64 host evaluator of Eq. 2."""
    import jax.numpy as jnp

    from repro.kernels.bottleneck import bottleneck_eval_fwd
    from repro.kernels.ref import bottleneck_eval_ref

    tg, cg, a = inst
    n_k = cg.num_machines
    batch = np.stack([a, (a + 1) % n_k, (a + 2) % n_k])
    gold = bottleneck_time_batch(tg, cg, batch)

    src = jnp.asarray([i for i, _ in tg.edges], jnp.int32)
    dst = jnp.asarray([j for _, j in tg.edges], jnp.int32)
    args = (jnp.asarray(batch, jnp.int32), jnp.asarray(tg.p),
            jnp.asarray(cg.e), jnp.asarray(cg.C), src, dst)
    got = bottleneck_eval_fwd(*args, interpret=True)
    want = bottleneck_eval_ref(*args)
    np.testing.assert_allclose(np.asarray(got), gold, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-6)


@given(st.integers(0, 2**31 - 1), st.integers(1, 6), st.integers(1, 200),
       st.floats(0.01, 1.0))
@settings(max_examples=25, deadline=None)
def test_kernel_compress_error_feedback(seed, n, l, frac):
    """Fused compress kernels: msgs + residual == delta (lossless feedback),
    top-k keeps >= k entries, int8 residual bounded by half a quantum."""
    import jax
    import jax.numpy as jnp

    from repro.kernels.compress import int8_roundtrip_fwd, topk_mask_fwd
    from repro.kernels.ref import int8_roundtrip_ref, topk_mask_ref

    rng = np.random.default_rng(seed)
    delta = jnp.asarray(rng.standard_normal((n, l)), jnp.float32)
    bl = max(1, l // 3)  # force a ragged final block most of the time

    kk = max(1, int(frac * l))
    vals, _ = jax.lax.top_k(jnp.abs(delta), kk)
    thresh = vals[:, -1]
    msg, resid = topk_mask_fwd(delta, thresh, block_len=bl, interpret=True)
    rmsg, rresid = topk_mask_ref(delta, thresh)
    assert np.array_equal(np.asarray(msg), np.asarray(rmsg))
    assert np.array_equal(np.asarray(resid), np.asarray(rresid))
    assert np.array_equal(np.asarray(msg) + np.asarray(resid),
                          np.asarray(delta))
    assert np.all(np.count_nonzero(np.asarray(msg), axis=1) >= kk)

    scale = jnp.maximum(jnp.max(jnp.abs(delta), axis=1), 1e-12) / 127.0
    msg, resid = int8_roundtrip_fwd(delta, scale, block_len=bl,
                                    interpret=True)
    rmsg, rresid = int8_roundtrip_ref(delta, scale)
    assert np.array_equal(np.asarray(msg), np.asarray(rmsg))
    # DESIGN §12: the residual may differ by 1 ulp of |delta| (FMA
    # contraction of q·scale into the subtraction on either path)
    ulp = np.spacing(np.abs(np.asarray(delta)))
    assert np.all(np.abs(np.asarray(resid) - np.asarray(rresid)) <= ulp)
    assert np.all(np.abs(np.asarray(resid))
                  <= np.asarray(scale)[:, None] * 0.5 + 1e-7)


@given(st.integers(0, 2**31 - 1), st.integers(2, 24), st.integers(1, 6))
@settings(max_examples=25, deadline=None)
def test_kernel_sdp_subspace_matches_ref(seed, n, k):
    """Fused subspace matvec + Gram + ||Y||^2 agree with the jnp oracle on
    random (n, k) including block-ragged n; rank-k downdate is exact."""
    import jax.numpy as jnp

    from repro.kernels.sdp_proj import rank_k_update_fwd, sdp_subspace_fwd
    from repro.kernels.ref import rank_k_update_ref, sdp_subspace_ref

    k = min(k, n)
    rng = np.random.default_rng(seed)
    Y = rng.standard_normal((n, n))
    Y = jnp.asarray(Y + Y.T, jnp.float32)
    V = jnp.asarray(np.linalg.qr(rng.standard_normal((n, k)))[0], jnp.float32)
    yv, g, ss = sdp_subspace_fwd(Y, V, block_rows=5, interpret=True)
    ryv, rg, rss = sdp_subspace_ref(Y, V)
    np.testing.assert_allclose(np.asarray(yv), np.asarray(ryv),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(g), np.asarray(rg),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(float(ss), float(rss), rtol=1e-5)

    A = jnp.asarray(rng.standard_normal((n, k)), jnp.float32)
    got = rank_k_update_fwd(Y, A, V, block_rows=5, interpret=True)
    want = rank_k_update_ref(Y, A, V)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
