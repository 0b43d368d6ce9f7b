"""Each fault a training cell can have, planted under the gossip-FL cells'
timed path and driven through the harness on the CPU at a small size,
comes out not correct."""

import pytest

import faults
from test_bench_fl_check import CELLS, checks


def test_round_that_returns_its_state_unchanged_is_not_correct():
    with faults.planted("frozen_round"):
        ok, vals, _ = checks(CELLS[0])
    assert not ok and vals["change_gap_median"] > 0.5, vals


def test_half_the_batch_left_out_is_not_correct():
    with faults.planted("half_batch"):
        ok, vals, _ = checks(CELLS[0])
    assert not ok, vals


@pytest.mark.parametrize("name", CELLS)
def test_exchange_left_out_is_not_correct(name):
    with faults.planted("no_exchange"):
        ok, vals, _ = checks(name)
    assert not ok and vals["change_gap_median"] > 0.05, vals
