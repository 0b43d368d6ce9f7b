"""The program's stage scopes and round spans, reduced on a small synthetic
trace (``data/program_trace_small.json``), and the traced path of a run on
the CPU."""

import json
import math
import pathlib
import struct

import jax
import pytest

import program_trace
import run
import trace_reduce

DATA = pathlib.Path(__file__).resolve().parent / "data" / "program_trace_small.json"
NEW = ("local_ms_per_round.fl", "compress_stage_ms_per_round.fl", "round_gap_ms.fl",
       "dispatch_ms_per_round.fl")


def _pt(**change):
    raw = json.loads(DATA.read_text())
    raw.update(change)
    t0, t1 = raw["window"]
    return program_trace.ProgramTrace(
        ops={d: [tuple(e) for e in evs] for d, evs in raw["ops"].items()},
        op_names=raw["op_names"],
        modules={d: [tuple(e) for e in evs] for d, evs in raw["modules"].items()},
        host=[tuple(e) for e in raw["host"]], t0=t0, t1=t1)


@pytest.mark.parametrize("path, stage", [
    ("jit(round_fn)/fl.local/while/body/vmap(jvp())/conv_general_dilated", "fl.local"),
    ("jit(round_fn)/transpose(jvp(fl.local))/mul", "fl.local"),
    ("jit(round_fn)/fl.mix/fl.mix.halo/all_gather", "fl.mix.halo"),
    ("xs", None),
    ("", None),
])
def test_stage_is_the_innermost_fl_scope(path, stage):
    assert program_trace.stage_of(path) == stage


def test_ops_are_attributed_to_their_stage_and_clipped_to_the_window():
    secs = _pt().stage_seconds()
    # the op that starts before the window counts from t0; the op after the
    # window and the while loop that holds other ops do not count
    assert secs["fl.local"] == pytest.approx(3500e-9)
    assert secs["fl.compress"] == pytest.approx(1400e-9)
    assert secs["fl.mix"] == pytest.approx(800e-9)
    assert secs["fl.mix.halo"] == pytest.approx(200e-9)
    assert secs["fl.aggregate"] == pytest.approx(400e-9)
    assert secs[None] == pytest.approx(200e-9)
    assert _pt().unscoped() == [("%copy.6", pytest.approx(200e-9))]


def test_stage_time_is_divided_by_the_rounds_in_the_window():
    pt = _pt()
    assert pt.rounds() == 2
    assert pt.stage_ms_per_round("fl.local") == pytest.approx(1750e-6)
    assert pt.stage_ms_per_round("fl.compress") == pytest.approx(700e-6)
    assert pt.stage_ms_per_round("fl.mix.halo") == pytest.approx(100e-6)
    assert pt.stage_ms_per_round("fl.no_such_stage") is None


def test_gap_between_round_programs_and_what_the_host_did_in_it():
    pt = _pt()
    # the program before the window and the one outside any round are not
    # round programs
    assert pt.round_gaps() == [(4000, 5000)]
    assert pt.gap_ms() == pytest.approx(1000e-6)
    split = pt.gap_split()
    assert split == {"fl.round.dispatch": pytest.approx(600e-9),
                     "fl.round.readback": pytest.approx(200e-9),
                     "other": pytest.approx(200e-9)}
    assert pt.dispatch_ms_per_round() == pytest.approx(425e-6)


class _Ctx:
    def __init__(self, pt):
        self.tr = trace_reduce.Trace(ops=pt.ops, modules=pt.modules,
                                     host=[("bench.window", pt.t0, pt.t1)])
        self.program_trace = pt


def _reader(name):
    return run.load_module(run.BENCH / "metrics" / f"{name}.py", "t_" + name.replace(".", "_"))


def test_readers_read_the_loaded_trace():
    ctx = _Ctx(_pt())
    got = {name: _reader(name).read(ctx) for name in NEW}
    assert got == {"local_ms_per_round.fl": pytest.approx(1750e-6),
                   "compress_stage_ms_per_round.fl": pytest.approx(700e-6),
                   "round_gap_ms.fl": pytest.approx(1000e-6),
                   "dispatch_ms_per_round.fl": pytest.approx(425e-6)}
    assert ctx.tr.window_s == pytest.approx(10000e-9)


@pytest.mark.parametrize("change", [
    {"ops": {}},                                       # a CPU run: no device ops
    {"host": [["bench.round", 900, 4300]]},            # a program with no spans
])
def test_a_reader_with_nothing_to_read_returns_none(change):
    ctx = _Ctx(_pt(**change))
    assert all(_reader(name).read(ctx) is None for name in NEW)


def test_every_reader_of_a_traced_cpu_run_returns_a_number_or_none():
    spec = json.loads((run.ROOT / "BENCHMARK.json").read_text())
    cell = run.make_cell({"name": "fl_cnn64.dpsgd", "chips": 1},
                         run.BENCH / "configs" / "fl_cnn_cifar10_u64.json", "fl_dpsgd")
    cell["config_data"].update(users=4, samples_per_user=128, batch=16)
    cell["per_layer"] = spec["per_layer"]
    out = run.run_cell(cell, 2**31 + 515_151, 0.5, True, jax.devices())
    assert set(NEW) <= {m["name"] for m in spec["per_layer"]}
    assert set(out["metrics"]) <= {m["name"] for m in spec["per_layer"]}
    assert all(math.isfinite(m["value"]) for m in out["metrics"].values())
    assert out["correct"], out["checks"]


# -- the wire format: a serialized XSpace built by hand ----------------------

def _varint(n):
    out = b""
    while True:
        out += bytes([(n & 0x7F) | (0x80 if n > 0x7F else 0)])
        n >>= 7
        if not n:
            return out


def _msg(*fields):
    """A protobuf message of ``(field number, int | float | str | bytes)``
    fields."""
    out = b""
    for num, v in fields:
        if isinstance(v, float):
            out += _varint(num << 3 | 1) + struct.pack("<d", v)
        elif isinstance(v, int):
            out += _varint(num << 3) + _varint(v)
        else:
            v = v.encode() if isinstance(v, str) else v
            out += _varint(num << 3 | 2) + _varint(len(v)) + v
    return out


def _entry(field, key, value):
    return field, _msg((1, key), (2, value))


def test_scope_paths_come_from_op_metadata_or_the_programs_hlo():
    # computation 1 is fused into instruction fusion.2 of computation 2,
    # which carries no scope of its own
    mul = _msg((1, "mul.1"), (7, _msg((2, "jit(f)/fl.mix/mul"))))
    fusion = _msg((1, "fusion.2"), (38, _varint(1)))
    copy = _msg((1, "copy.3"), (7, _msg((2, "xs"))))
    module = _msg((3, _msg((5, 1), (2, mul))), (3, _msg((5, 2), (2, fusion), (2, copy))))
    hlo = _msg((1, module))
    device = _msg(
        (2, "/device:TPU:0"),
        _entry(5, 1, _msg((1, 1), (2, "tf_op"))),
        _entry(5, 2, _msg((1, 2), (2, "program_id"))),
        _entry(4, 10, _msg((1, 10), (2, "%mul.9 = f32[8] multiply(...)"),
                           (5, _msg((1, 1), (5, "jit(f)/transpose(jvp(fl.local))/mul:"))))),
        _entry(4, 11, _msg((1, 11), (2, "%fusion.2 = f32[8] fusion(...)"),
                           (5, _msg((1, 2), (4, 7))))),
        _entry(4, 12, _msg((1, 12), (2, "%copy.3 = f32[8] copy(...)"),
                           (5, _msg((1, 2), (4, 7))), (5, _msg((1, 1), (5, "xs:"))))),
        (6, _msg((1, 2), (2, 1.5))))        # a plane stat of fixed width, skipped
    meta = _msg((2, "/host:metadata"), _entry(5, 1, _msg((1, 1), (2, "Hlo Proto"))),
                _entry(4, 7, _msg((1, 7), (2, "jit_f(7)"), (5, _msg((1, 1), (6, hlo))))))
    host = _msg((2, "/host:CPU"), _entry(4, 1, _msg((1, 1), (2, "%not.a.device.op = x"))))
    space = _msg((1, device), (1, meta), (1, host))
    assert program_trace.op_scopes(space) == {
        "%mul.9 = f32[8] multiply(...)": "jit(f)/transpose(jvp(fl.local))/mul",
        "%fusion.2 = f32[8] fusion(...)": "jit(f)/fl.mix/mul",
        "%copy.3 = f32[8] copy(...)": "xs",
    }
