"""Kernel micro-benchmarks.

On this CPU-only container wall-clock of interpret-mode Pallas is
meaningless, so per kernel we measure the jnp reference path (CPU µs) and
DERIVE the projected v5e time from the roofline model (bytes / 819 GB/s vs
flops / 197 TFLOP/s) — the same constants as §Roofline.
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit

PEAK_FLOPS = 197e12
HBM_BW = 819e9


def _time(fn, *args, iters=5) -> float:
    fn(*args)[0].block_until_ready() if isinstance(fn(*args), tuple) else None
    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters * 1e6


def main(quick: bool = True, record_json: bool = False):
    from repro.kernels import ref as kref

    rng = np.random.default_rng(0)
    t = lambda s, d=jnp.bfloat16: jnp.asarray(rng.standard_normal(s), d)

    # flash attention: B=1, S=2048, H=16, D=128 (scaled-down train block)
    b, s, h, hkv, d = 1, 2048 if not quick else 1024, 16, 8, 128
    q, k, v = t((b, h, s, d)), t((b, hkv, s, d)), t((b, hkv, s, d))
    us = _time(jax.jit(lambda q, k, v: kref.flash_attention_ref(q, k, v)), q, k, v)
    flops = 2 * 2 * b * h * s * s * d / 2
    emit("kernel_flash_attention_ref", us,
         f"S={s};proj_v5e_us={flops / PEAK_FLOPS * 1e6:.1f}")

    # decode attention: B=8, S=32768 cache
    s_c = 32768 if not quick else 8192
    q1, kc, vc = t((8, h, d)), t((8, s_c, hkv, d)), t((8, s_c, hkv, d))
    vl = jnp.full((8,), s_c, jnp.int32)
    us = _time(jax.jit(kref.decode_attention_ref), q1, kc, vc, vl)
    bytes_ = 2 * 8 * s_c * hkv * d * 2
    emit("kernel_decode_attention_ref", us,
         f"S={s_c};proj_v5e_us={bytes_ / HBM_BW * 1e6:.1f} (memory-bound)")

    # rmsnorm
    x, w = t((8192, 4096)), t((4096,), jnp.float32)
    us = _time(jax.jit(kref.rmsnorm_ref), x, w)
    bytes_ = 2 * x.size * 2
    emit("kernel_rmsnorm_ref", us,
         f"rows=8192;proj_v5e_us={bytes_ / HBM_BW * 1e6:.1f}")

    # gossip mix: 9 neighbors x 16M params
    n, l = 9, (1 << 24) if not quick else (1 << 21)
    st_, ww = t((n, l), jnp.float32), jnp.ones((n,), jnp.float32) / n
    us = _time(jax.jit(kref.gossip_mix_ref), st_, ww)
    bytes_ = (n + 1) * l * 4
    naive_bytes = 2 * (n - 1) * l * 4 + 2 * l * 4
    emit(
        "kernel_gossip_mix_ref", us,
        f"N={n};proj_v5e_us={bytes_ / HBM_BW * 1e6:.1f};"
        f"naive_axpy_us={naive_bytes / HBM_BW * 1e6:.1f}",
    )

    # all-receivers batched mix (the stacked FL exchange, DESIGN.md §8):
    # N_T users, out-degree-6 random scatter W (sender-normalized 1/deg
    # entries; receiver row sums vary with in-degree — same sparsity and
    # cost shape as the production mixing matrix, not its normalization),
    # vs the (|E|, L) gather + segment_sum reference.  On CPU the Pallas
    # kernel runs in interpret mode (wall-clock meaningless), so it is
    # verified on a small slab and the perf record is the jnp reference
    # timing + the roofline projection.
    from repro.kernels.gossip_mix import gossip_mix_all_fwd

    nt, l2, deg = 64, (1 << 21) if not quick else (1 << 18), 6
    src = jnp.asarray(np.repeat(np.arange(nt), deg), jnp.int32)
    dst = jnp.asarray(rng.integers(0, nt, size=nt * deg), jnp.int32)
    w_e = jnp.full((nt * deg,), 1.0 / deg, jnp.float32)
    W = jnp.zeros((nt, nt), jnp.float32).at[dst, src].add(w_e)
    x_all = t((nt, l2), jnp.float32)

    us_seg = _time(
        jax.jit(lambda s: kref.gossip_mix_segment_ref(s, src, dst, w_e, nt)), x_all
    )
    us_dense = _time(jax.jit(kref.gossip_mix_all_ref), x_all, W)

    on_cpu = jax.default_backend() == "cpu"
    small = x_all[:, : (1 << 16)]
    got = gossip_mix_all_fwd(small, W, block_len=1 << 14, interpret=on_cpu)
    np.testing.assert_allclose(
        got, kref.gossip_mix_all_ref(small, W), atol=2e-4
    )

    kern_bytes = 2 * nt * l2 * 4                    # stream slab once, write once
    seg_bytes = (2 * nt * deg + nt) * l2 * 4        # gather + scatter + write
    emit(
        "kernel_gossip_mix_all", us_dense,
        f"NT={nt};deg={deg};segment_sum_us={us_seg:.1f};"
        f"proj_v5e_us={kern_bytes / HBM_BW * 1e6:.1f};"
        f"segment_proj_v5e_us={seg_bytes / HBM_BW * 1e6:.1f};"
        f"pallas={'interpret_ok' if on_cpu else 'compiled_ok'}",
    )

    # ------------------------------------------------------------------
    # Fused scheduler/FL kernels (DESIGN.md §12).  Same measurement
    # discipline as above: time the jnp reference composition on this
    # host, VERIFY the Pallas kernel in interpret mode on a small slab,
    # and project v5e before/after from the traffic model.  Interpret-mode
    # wall-clock is never reported as a speedup.
    # ------------------------------------------------------------------
    from repro.kernels.bottleneck import bottleneck_eval_fwd
    from repro.kernels.compress import int8_roundtrip_fwd, topk_mask_fwd
    from repro.kernels.sdp_proj import sdp_subspace_fwd

    verified = "interpret_ok" if on_cpu else "compiled_ok"
    rows: dict[str, dict] = {}

    # (a) SDP fused subspace projection: one stream of Y yields the
    # matvec + Rayleigh-Ritz Gram + shift norm (jnp: matvec stream + norm
    # stream; the Gram rides on the small YV).
    n1, kk = (1025, 16) if not quick else (513, 16)
    Ys = t((n1, n1), jnp.float32)
    Ys = Ys + Ys.T
    Vs = t((n1, kk), jnp.float32)
    us_ref = _time(jax.jit(kref.sdp_subspace_ref), Ys, Vs)
    sm = 97                                          # ragged vs block 64
    got = sdp_subspace_fwd(Ys[:sm, :sm], Vs[:sm], block_rows=64,
                           interpret=on_cpu)
    want = kref.sdp_subspace_ref(Ys[:sm, :sm], Vs[:sm])
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=1e-4, atol=1e-3)
    bytes_before = 2 * n1 * n1 * 4                  # matvec + norm streams
    bytes_after = n1 * n1 * 4                       # one fused stream
    rows["sdp_subspace"] = {
        "n": n1, "k": kk, "cpu_ref_us": us_ref,
        "proj_v5e_us_before": bytes_before / HBM_BW * 1e6,
        "proj_v5e_us_after": bytes_after / HBM_BW * 1e6,
        "traffic_ratio": bytes_before / bytes_after,
        "pallas": verified,
    }
    emit("kernel_sdp_subspace_ref", us_ref,
         f"n={n1};k={kk};"
         f"proj_v5e_us={bytes_before / HBM_BW * 1e6:.1f};"
         f"fused_proj_v5e_us={bytes_after / HBM_BW * 1e6:.1f};"
         f"pallas={verified}")

    # (b) fused delta compression with error feedback: jnp roundtrip +
    # subtract moves ~5 (N, L) slabs (read/write msgs, re-read delta and
    # msgs, write residual); the fused kernel reads once, writes both.
    nc, lc = 64, (1 << 21) if not quick else (1 << 18)
    delta = t((nc, lc), jnp.float32)
    vals, _ = jax.lax.top_k(jnp.abs(delta), max(1, lc // 100))
    thr = vals[:, -1]
    scale = jnp.maximum(jnp.max(jnp.abs(delta), axis=1), 1e-12) / 127.0
    us_topk = _time(jax.jit(kref.topk_mask_ref), delta, thr)
    us_int8 = _time(jax.jit(kref.int8_roundtrip_ref), delta, scale)
    sm_d = delta[:, : (1 << 14)]
    got = topk_mask_fwd(sm_d, thr, block_len=1 << 12, interpret=on_cpu)
    want = kref.topk_mask_ref(sm_d, thr)
    np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(want[0]))
    got = int8_roundtrip_fwd(sm_d, scale, block_len=1 << 12,
                             interpret=on_cpu)
    want = kref.int8_roundtrip_ref(sm_d, scale)
    np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(want[0]))
    cb_before = 5 * nc * lc * 4
    cb_after = 3 * nc * lc * 4
    rows["compress"] = {
        "n_users": nc, "params": lc,
        "cpu_topk_ref_us": us_topk, "cpu_int8_ref_us": us_int8,
        "proj_v5e_us_before": cb_before / HBM_BW * 1e6,
        "proj_v5e_us_after": cb_after / HBM_BW * 1e6,
        "traffic_ratio": cb_before / cb_after,
        "pallas": verified,
    }
    emit("kernel_compress_ref", us_topk,
         f"N={nc};L={lc};int8_us={us_int8:.1f};"
         f"proj_v5e_us={cb_before / HBM_BW * 1e6:.1f};"
         f"fused_proj_v5e_us={cb_after / HBM_BW * 1e6:.1f};"
         f"pallas={verified}")

    # (c) batched bottleneck evaluation (Eq. 2) over rounding samples:
    # the kernel reads each (T, bs) assignment block once for the loads,
    # the compute times and the per-edge delays; the projection counts
    # the jnp gather path as 4 passes over the same slab.
    ss_, tt, kk2 = (512, 128, 8) if not quick else (256, 64, 4)
    ne = 3 * tt
    aa = jnp.asarray(rng.integers(0, kk2, size=(ss_, tt)), jnp.int32)
    pp = jnp.abs(t((tt,), jnp.float32))
    ee = jnp.abs(t((kk2,), jnp.float32)) + 0.1
    cc = jnp.abs(t((kk2, kk2), jnp.float32))
    src_ = jnp.asarray(rng.integers(0, tt, size=ne), jnp.int32)
    dst_ = jnp.asarray(rng.integers(0, tt, size=ne), jnp.int32)
    us_bot = _time(jax.jit(kref.bottleneck_eval_ref), aa, pp, ee, cc,
                   src_, dst_)
    got = bottleneck_eval_fwd(aa[:16], pp, ee, cc, src_, dst_,
                              block_samples=5, interpret=on_cpu)
    want = kref.bottleneck_eval_ref(aa[:16], pp, ee, cc, src_, dst_)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-6)
    flops = ss_ * (4 * tt * kk2 + 2 * ne * kk2 * kk2)
    slab = ss_ * tt * 4
    rows["bottleneck_eval"] = {
        "samples": ss_, "tasks": tt, "machines": kk2, "edges": ne,
        "cpu_ref_us": us_bot,
        "proj_v5e_us_before": 4 * slab / HBM_BW * 1e6,
        "proj_v5e_us_after": max(slab / HBM_BW, flops / PEAK_FLOPS) * 1e6,
        "traffic_ratio": 4.0,
        "pallas": verified,
    }
    emit("kernel_bottleneck_eval_ref", us_bot,
         f"S={ss_};T={tt};K={kk2};"
         f"proj_v5e_us={4 * slab / HBM_BW * 1e6:.1f};"
         f"fused_proj_v5e_us="
         f"{max(slab / HBM_BW, flops / PEAK_FLOPS) * 1e6:.1f};"
         f"pallas={verified}")

    if record_json:
        import json
        import pathlib
        import time as _t

        path = pathlib.Path(__file__).resolve().parent.parent / (
            "BENCH_scheduler_scaling.json"
        )
        # read-modify-write: other suites own the other keys
        record = json.loads(path.read_text()) if path.exists() else {}
        record["kernels"] = rows
        record["kernels_generated_unix"] = _t.time()
        path.write_text(json.dumps(record, indent=2) + "\n")
    return rows


def kernel_diff_smoke():
    """CI gate: every fused scheduler/FL kernel matches its jnp oracle.

    Interpret-mode differential check on block-ragged small slabs (the
    full sweep lives in ``tests/test_kernel_diff.py``), plus one tiny
    seeded ``solve_sdp`` with the fused projection on vs off asserting
    the iteration trajectory is identical — the property that lets
    ``kernel_backend="auto"`` switch per host without changing results.
    """
    import jax
    import jax.numpy as jnp

    from repro.core import (
        SDPOptions,
        build_factored_bqp,
        random_compute_graph,
        random_task_graph,
        solve_sdp,
    )
    from repro.kernels import ref as kref
    from repro.kernels.bottleneck import bottleneck_eval_fwd
    from repro.kernels.compress import int8_roundtrip_fwd, topk_mask_fwd
    from repro.kernels.sdp_proj import rank_k_update_fwd, sdp_subspace_fwd

    from repro.kernels.ops import interpret_mode

    t0 = time.perf_counter()
    rng = np.random.default_rng(0)
    interp = interpret_mode()

    # (a) fused subspace projection + rank-k clip, ragged blocking
    n, k = 33, 4
    Y = rng.standard_normal((n, n)).astype(np.float32)
    Y = jnp.asarray(Y + Y.T)
    V = jnp.asarray(np.linalg.qr(rng.standard_normal((n, k)))[0],
                    jnp.float32)
    got = sdp_subspace_fwd(Y, V, block_rows=8, interpret=interp)
    want = kref.sdp_subspace_ref(Y, V)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=1e-4, atol=1e-3)
    np.testing.assert_allclose(
        np.asarray(rank_k_update_fwd(Y, V, V, block_rows=8,
                                     interpret=interp)),
        np.asarray(kref.rank_k_update_ref(Y, V, V)),
        rtol=1e-5, atol=1e-5,
    )

    # (b) fused compression with error feedback, ragged tail
    X = jnp.asarray(rng.standard_normal((8, 100)), jnp.float32)
    vals, _ = jax.lax.top_k(jnp.abs(X), 10)
    m, r = topk_mask_fwd(X, vals[:, -1], block_len=64, interpret=interp)
    rm, rr = kref.topk_mask_ref(X, vals[:, -1])
    np.testing.assert_array_equal(np.asarray(m), np.asarray(rm))
    np.testing.assert_array_equal(np.asarray(r), np.asarray(rr))
    scale = jnp.maximum(jnp.max(jnp.abs(X), axis=1), 1e-12) / 127.0
    m, r = int8_roundtrip_fwd(X, scale, block_len=64, interpret=interp)
    rm, rr = kref.int8_roundtrip_ref(X, scale)
    np.testing.assert_array_equal(np.asarray(m), np.asarray(rm))
    np.testing.assert_allclose(np.asarray(r), np.asarray(rr), atol=2e-7)

    # (c) bottleneck evaluation, ragged sample padding + E=0
    for n_e in (14, 0):
        aa = jnp.asarray(rng.integers(0, 4, size=(8, 7)), jnp.int32)
        pp = jnp.asarray(rng.uniform(0.1, 5.0, 7), jnp.float32)
        ee = jnp.asarray(rng.uniform(0.5, 4.0, 4), jnp.float32)
        cc = jnp.asarray(rng.uniform(0.0, 3.0, (4, 4)), jnp.float32)
        src_ = jnp.asarray(rng.integers(0, 7, n_e), jnp.int32)
        dst_ = jnp.asarray(rng.integers(0, 7, n_e), jnp.int32)
        args = (aa, pp, ee, cc, src_, dst_)
        np.testing.assert_allclose(
            np.asarray(bottleneck_eval_fwd(*args, block_samples=3,
                                           interpret=interp)),
            np.asarray(kref.bottleneck_eval_ref(*args)),
            rtol=1e-5, atol=1e-6,
        )

    # (d) tiny seeded e2e: fused projection on == off
    r5 = np.random.default_rng(5)
    tg = random_task_graph(r5, 6, degree_low=1, degree_high=3)
    cg = random_compute_graph(r5, 3)
    bqp = build_factored_bqp(tg, cg)
    sols = {
        kb: solve_sdp(bqp, SDPOptions(max_iters=2000, check_every=50,
                                      tol=1e-4, backend="jax",
                                      kernel_backend=kb))
        for kb in ("jnp", "pallas")
    }
    assert sols["jnp"].iterations == sols["pallas"].iterations
    assert (sols["jnp"].stats["eig_partial"]
            == sols["pallas"].stats["eig_partial"])
    np.testing.assert_allclose(sols["pallas"].Y, sols["jnp"].Y, atol=1e-3)

    emit(
        "kernel_diff_smoke",
        (time.perf_counter() - t0) * 1e6,
        f"kernels=5;e2e_iters={sols['pallas'].iterations};"
        f"mode={'interpret' if interp else 'compiled'};ok=1",
    )


if __name__ == "__main__":
    main(quick=False, record_json=True)
