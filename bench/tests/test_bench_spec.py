"""BENCHMARK.json against the benchmark's contract, the files it names,
the result line, and a run without the accelerator."""

import copy
import inspect
import json
import pathlib
import re

import pytest

import run

ROOT = pathlib.Path(__file__).resolve().parents[2]
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def test_top_level_keys_and_limits():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs", "workloads",
                         "end_to_end", "per_layer"}
    assert SPEC["command"][:2] == ["python3", "bench/run.py"]
    assert SPEC["paths"] == ["bench"]
    assert 1 <= SPEC["run_seconds"] <= 51
    runs = 2 + 14 * 24
    assert runs * (SPEC["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200
    assert len((ROOT / "BENCHMARK.json").read_bytes()) <= 64 * 1024


def test_names_units_and_files():
    names = set()
    for c in SPEC["configs"]:
        assert NAME.match(c["name"]) and (ROOT / c["file"]).is_file()
        assert c["file"].startswith("bench/")
        names.add(c["name"])
    metrics = SPEC["end_to_end"] + SPEC["per_layer"]
    assert len({m["name"] for m in metrics}) == len(metrics)
    for m in metrics:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert (ROOT / "bench" / "metrics" / f"{m['name']}.py").is_file()
    for w in SPEC["workloads"]:
        assert NAME.match(w["name"]) and w["config"] in names and w["chips"] in (1, 4)
        assert (ROOT / "bench" / "traffic" / f"{w['traffic']}.json").is_file()
        assert len(w["why"]) <= 200


def test_bounds_and_sources():
    e2e = {m["name"]: m for m in SPEC["end_to_end"]}
    assert e2e["setup_s"]["bound"] <= 0.25
    for m in SPEC["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25 and m["source"] in ("host_clock", "device_trace")
    for m in SPEC["per_layer"]:
        assert m["moves"] in e2e
        assert m["source"] in ("device_trace", "program_span", "program_counter", "host_clock")


@pytest.mark.parametrize("cell", [w["name"] for w in SPEC["workloads"]])
def test_every_cell_reports_setup_another_end_to_end_and_a_layer(cell):
    c = run.load_cell(cell, SPEC)
    names = {m["name"] for m in c["end_to_end"]}
    assert "setup_s" in names and len(names) >= 2
    assert c["per_layer"]
    for m in c["per_layer"]:
        assert m["moves"] in names
    assert_runner_contract(c["config_data"]["kind"])


def assert_runner_contract(kind):
    """What the harness relies on in a runner (``run.py``'s docstring):
    ``bench/runners/<kind>.py`` defines ``Runner(ctx)`` with ``setup()``,
    ``serve(seconds)``, ``release()`` and ``check()``."""
    assert (ROOT / "bench" / "runners" / f"{kind}.py").is_file(), kind
    runner = run.load_runner(kind).Runner
    inspect.signature(runner).bind(object())
    for method, args in (("setup", 0), ("serve", 1), ("release", 0), ("check", 0)):
        assert callable(getattr(runner, method, None)), (kind, method)
        inspect.signature(getattr(runner, method)).bind(object(), *range(args))


@pytest.mark.parametrize("kind", sorted(
    p.stem for p in (ROOT / "bench" / "runners").glob("*.py")))
def test_every_runner_keeps_the_runner_contract(kind):
    assert_runner_contract(kind)


def _metric_sets(cell):
    return ({m["name"] for m in cell["end_to_end"]}, {m["name"] for m in cell["per_layer"]})


# what the two CNN cells report at least; a later PR may add to them
CNN_SETS = {
    "fl_cnn64.topk": ({"setup_s", "fl_samples_per_s"},
                      {"fl_step_mfu", "mix_roofline.fl", "device_idle_share.fl",
                       "local_ms_per_round.fl", "compress_stage_ms_per_round.fl",
                       "round_gap_ms.fl", "dispatch_ms_per_round.fl"}),
    "fl_cnn64.dpsgd": ({"setup_s", "fl_samples_per_s"},
                       {"fl_step_mfu", "mix_roofline.fl", "device_idle_share.fl",
                        "local_ms_per_round.fl", "round_gap_ms.fl",
                        "dispatch_ms_per_round.fl"}),
}


def test_a_configuration_of_another_kind_joins_by_new_files_and_list_entries(tmp_path):
    before = {w["name"]: _metric_sets(run.load_cell(w["name"], SPEC)) for w in SPEC["workloads"]}
    for name, (e2e, layer) in CNN_SETS.items():
        assert e2e <= before[name][0] and layer <= before[name][1], name
    assert not any("compress_device_ms_per_round.fl" in sets[1] for sets in before.values())

    spec = copy.deepcopy(SPEC)
    config = tmp_path / "lm_probe.json"
    config.write_text(json.dumps({"kind": "gossip_lm_probe", "users": 16}))
    spec["configs"].append({"name": "lm_probe", "source": "https://arxiv.org/abs/2305.05644",
                            "file": str(config), "reduced": [], "why": "a second kind"})
    spec["workloads"].append({"name": "lm_probe.dpsgd", "config": "lm_probe",
                              "traffic": "fl_dpsgd", "chips": 1, "why": "a second kind"})
    shared = next(m for m in spec["end_to_end"] if m["name"] == "fl_samples_per_s")
    shared["workloads"].append("lm_probe.dpsgd")
    spec["per_layer"].append({"name": "lm_probe_roofline", "unit": "%", "better": "higher",
                              "source": "device_trace", "layer": "LM probe",
                              "moves": "fl_samples_per_s", "workloads": ["lm_probe.dpsgd"]})

    new = run.load_cell("lm_probe.dpsgd", spec)
    assert new["config_data"]["kind"] == "gossip_lm_probe"
    assert new["traffic_data"] == run.load_cell("fl_cnn64.dpsgd", SPEC)["traffic_data"]
    assert _metric_sets(new) == ({"setup_s", "fl_samples_per_s"}, {"lm_probe_roofline"})
    assert {w["name"]: _metric_sets(run.load_cell(w["name"], spec))
            for w in SPEC["workloads"]} == before


def test_result_line_keys_and_types(capsys):
    out = {"correct": True, "attempted": 12, "failed": 0,
           "metrics": {"setup_s": {"value": 12.5, "unit": "s"}},
           "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
                      "memory_peak_bytes": 123},
           "checks": {"bound_gap": {"value": 1e-4, "limit": 1e-2}}}
    line = json.loads(run.result_line(out))
    assert list(line)[-1] == "checks"
    assert isinstance(line["correct"], bool)
    assert isinstance(line["attempted"], int) and isinstance(line["failed"], int)
    for m in line["metrics"].values():
        assert isinstance(m["value"], float) and isinstance(m["unit"], str)
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(line["device"])
    err = capsys.readouterr().err.strip().splitlines()
    assert err[-1] == "check bound_gap: 0.0001 limit 0.01"


def test_without_a_tpu_the_run_exits_nonzero_and_prints_no_result(capsys):
    cell = SPEC["workloads"][0]["name"]
    rc = run.main(["--workload", cell, "--seed", "2147483659", "--seconds", "1"])
    assert rc == 2
    assert capsys.readouterr().out == ""
