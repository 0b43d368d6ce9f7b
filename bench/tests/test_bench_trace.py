"""The trace reduction on a small synthetic trace (``data/trace_small.json``)."""

import json
import pathlib

import pytest

import flops
import trace_reduce

DATA = pathlib.Path(__file__).resolve().parent / "data" / "trace_small.json"
NS = 1e-9
CIFAR = (32, 32, 3)


@pytest.fixture()
def tr():
    raw = json.loads(DATA.read_text())
    return trace_reduce.Trace(
        ops={d: [tuple(e) for e in evs] for d, evs in raw["ops"].items()},
        modules={d: [tuple(e) for e in evs] for d, evs in raw["modules"].items()},
        host=[tuple(e) for e in raw["host"]],
    )


def test_merge():
    assert trace_reduce.merge([(5, 9), (0, 3), (2, 4), (9, 10)]) == [(0, 4), (5, 10)]


def test_window_busy_union_and_idle_share(tr):
    assert tr.window_s == pytest.approx(1000 * NS)
    # [0,100] + [150,300] + [400,700]; nested and overlapping ops count once,
    # the op after the window not at all
    assert tr.busy_s() == pytest.approx(550 * NS)
    assert tr.idle_share() == pytest.approx(0.45)


def _seconds(spans):
    return sum(e - s for _, s, e in spans) * NS


def test_kernel_time_by_name(tr):
    assert _seconds(tr.ops_matching(r"_subspace_kernel")) == pytest.approx(100 * NS)
    assert tr.ops_matching(r"no_such_kernel") == []
    assert _seconds(tr.ops_matching(r"^fusion\.[0-9]$")) == pytest.approx(250 * NS)


def test_idle_gaps_are_named_by_the_covering_span(tr):
    gaps = tr.idle_gaps(3)
    assert [g[0] for g in gaps] == ["bench.request", "bench.wait", "bench.request"]
    assert [g[1] for g in gaps] == pytest.approx([300 * NS, 100 * NS, 50 * NS])


def test_idle_gaps_are_named_by_the_programs_host_spans():
    # two round programs with the host reading the first round's loss back
    # between them, under the benchmark's round span and the program's own
    ops = {"/device:TPU:0": [("fusion.1", 0, 400), ("fusion.2", 600, 1000)]}
    host = [("bench.window", 0, 1000), ("bench.round", 0, 560), ("bench.round", 560, 1000),
            ("fl.round", 0, 550), ("fl.round.dispatch", 10, 30),
            ("fl.round.readback", 30, 540), ("fl.round", 560, 1000)]
    tr = trace_reduce.Trace(ops=ops, modules={}, host=host)
    assert tr.idle_gaps(1) == [["fl.round.readback", pytest.approx(200 * NS)]]
    assert len(tr.spans("bench.round")) == 2 and tr.window_s == pytest.approx(1000 * NS)


def test_load_keeps_the_benchmarks_and_the_programs_host_spans(tmp_path):
    import jax
    import jax.numpy as jnp

    with jax.profiler.trace(str(tmp_path)):
        with jax.profiler.TraceAnnotation("bench.round"):
            with jax.profiler.TraceAnnotation("fl.round.readback"):
                jnp.ones(8).sum().block_until_ready()
        with jax.profiler.TraceAnnotation("other.span"):
            pass
    names = {sp[0] for sp in trace_reduce.load(str(tmp_path)).host}
    assert {"bench.round", "fl.round.readback"} <= names
    assert "other.span" not in names


def test_top_ops_sum_time_per_name(tr):
    top = dict((name.split()[0], sec) for name, sec in tr.top_ops(10))
    assert top["while.3"] == pytest.approx(300 * NS)
    assert "fusion.9" not in top


def test_roofline_share_of_a_kernel(tr):
    ops, nbytes = flops.mix_cost(64, 552_714)
    seconds = _seconds(tr.ops_matching(r"_subspace_kernel"))
    share, bound = flops.roofline_share(ops, nbytes, seconds, 197e12, 819e9)
    assert bound == "memory"
    assert share == pytest.approx(100.0 * nbytes / 819e9 / seconds)
    share, bound = flops.roofline_share(1e9 * ops, nbytes, seconds, 197e12, 819e9)
    assert bound == "compute"


def _reader(name):
    import run

    return run.load_module(run.BENCH / "metrics" / f"{name}.py", "t_" + name.replace(".", "_"))


class _Ctx:
    def __init__(self, tr, counters):
        self.tr, self.counters, self.flops = tr, counters, flops
        self.peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
        self.devices = [None]


def _kernel_trace(names):
    """Each named kernel (labels recorded from a v5e trace) once, 1000 ns,
    inside a window that also holds two rounds/requests."""
    k = json.loads(DATA.read_text())["kernels"]
    ops = [(k[n], 1000 * i, 1000 * i + 1000) for i, n in enumerate(names)]
    host = [("bench.window", 0, 100_000), ("bench.round", 0, 50_000),
            ("bench.round", 50_000, 100_000)]
    return trace_reduce.Trace(ops={"/device:TPU:0": ops}, modules={}, host=host)


def test_kernels_are_found_by_their_signatures():
    tr = _kernel_trace(["mix", "mask", "topk_sort", "perm_sort"])
    ctx = _Ctx(tr, {"users": 64, "params_per_user": 552_714})
    ops, nbytes = flops.mix_cost(64, 552_714)
    assert _reader("mix_roofline.fl").read(ctx) == pytest.approx(100 * nbytes / 819e9 / 1e-6)


def test_step_mfu_reads_the_runners_count_as_the_old_formula_did():
    tr = _kernel_trace(["mix"])
    train_flops = flops.cnn_train_flops(CIFAR, 10, [32, 64], [128, 64])
    ctx = _Ctx(tr, {"samples_per_round": 2048, "train_flops_per_sample": train_flops})
    # the formula before the count became the runner's counter
    old = 100.0 * train_flops * (2 * 2048 / tr.window_s) / 197e12
    assert _reader("fl_step_mfu").read(ctx) == old
    assert old == pytest.approx(100 * 35_049_216 * 2 * 2048 / 100e-6 / 197e12)


def test_a_reader_that_finds_nothing_returns_nothing():
    tr = _kernel_trace(["perm_sort"])
    ctx = _Ctx(tr, {"users": 64, "params_per_user": 552_714})
    assert _reader("mix_roofline.fl").read(ctx) is None
