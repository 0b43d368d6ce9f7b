"""Each fault a training cell can have, planted under the gossip-FL cells'
timed path and driven through the harness on the CPU at a small size,
comes out not correct; and how a fault is found for a cell's kind."""

import importlib
import sys

import pytest

import faults
import faults_gossip_fl
from test_bench_fl_check import CELLS, checks

KIND = "gossip_fl"


def test_round_that_returns_its_state_unchanged_is_not_correct():
    with faults.planted("frozen_round", kind=KIND):
        ok, vals, _ = checks(CELLS[0])
    assert not ok and vals["change_gap_median"] > 0.5, vals


def test_half_the_batch_left_out_is_not_correct():
    with faults.planted("half_batch", kind=KIND):
        ok, vals, _ = checks(CELLS[0])
    assert not ok, vals


@pytest.mark.parametrize("name", CELLS)
def test_exchange_left_out_is_not_correct(name):
    with faults.planted("no_exchange", kind=KIND):
        ok, vals, _ = checks(name)
    assert not ok and vals["change_gap_median"] > 0.05, vals


@pytest.mark.parametrize("name, module", [
    ("frozen_round", faults), ("no_exchange", faults), ("half_batch", faults_gossip_fl)])
def test_each_cnn_fault_is_found_for_its_kind(name, module):
    assert faults.lookup(name, KIND) is module.FAULTS[name]
    assert name in faults.names(KIND)


def test_an_unknown_fault_raises():
    with pytest.raises(KeyError, match="no_such_fault"):
        faults.lookup("no_such_fault", KIND)
    # the CNN's own fault is not another kind's
    with pytest.raises(KeyError, match="half_batch"):
        faults.lookup("half_batch", "no_such_kind")


def test_the_kinds_own_fault_module_is_consulted_first(tmp_path, monkeypatch):
    # a kind whose own module plants a "frozen_round" of its own
    (tmp_path / "faults_probe_kind.py").write_text(
        "import types\n"
        "TARGET = types.SimpleNamespace(step='sound')\n"
        "FAULTS = {'frozen_round': lambda: (TARGET, 'step', 'frozen')}\n")
    monkeypatch.syspath_prepend(str(tmp_path))
    monkeypatch.delitem(sys.modules, "faults_probe_kind", raising=False)
    own = importlib.import_module("faults_probe_kind")
    try:
        assert faults.lookup("frozen_round", "probe_kind") is own.FAULTS["frozen_round"]
        assert faults.lookup("no_exchange", "probe_kind") is faults.FAULTS["no_exchange"]
        with faults.planted("frozen_round", kind="probe_kind"):
            assert own.TARGET.step == "frozen"
        assert own.TARGET.step == "sound"
    finally:
        sys.modules.pop("faults_probe_kind", None)
