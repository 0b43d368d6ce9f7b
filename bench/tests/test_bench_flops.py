"""Operation and byte counts of the benchmark, from shapes."""

import json
import pathlib

import pytest

import flops
import run


def flops_dir():
    return pathlib.Path(flops.__file__).resolve().parent

CIFAR = (32, 32, 3)


def test_cnn_forward_at_cifar10_geometry():
    macs = flops.cnn_layer_macs(CIFAR)
    assert macs == [884_736, 4_718_592, 524_288, 8_192, 640]
    assert sum(macs) == 6_136_448
    assert flops.cnn_forward_flops(CIFAR) == 12_272_896          # 12.27 MFLOP


def test_cnn_train_is_forward_plus_two_backward_passes_but_conv1_input():
    fwd = flops.cnn_forward_flops(CIFAR)
    assert flops.cnn_train_flops(CIFAR) == 3 * fwd - 2 * 884_736


def test_cnn_parameters():
    assert flops.cnn_param_count(CIFAR) == 552_714
    assert flops.cnn_param_count((28, 28, 1)) == 429_258


def test_counts_follow_the_configured_widths():
    cfg = json.loads((flops_dir() / "configs" / "fl_cnn_cifar10_u64.json").read_text())
    image, classes = tuple(cfg["image"]), cfg["classes"]
    widths = (tuple(cfg["channels"]), tuple(cfg["hidden"]))
    assert flops.cnn_param_count(image, classes, *widths) == 552_714
    assert flops.cnn_forward_flops(image, classes, *widths) == 12_272_896
    # doubling conv2's channels doubles its multiply-adds and fc1's
    macs = flops.cnn_layer_macs(image, classes, (32, 128), (128, 64))
    assert macs[1:3] == [2 * 4_718_592, 2 * 524_288]


def test_mix_bytes():
    ops, nbytes = flops.mix_cost(64, 552_714)
    assert ops == 2 * 64 * 64 * 552_714
    assert nbytes == 4 * (2 * 64 * 552_714 + 64 * 64)


@pytest.mark.parametrize("traffic", ["fl_topk", "fl_dpsgd"])
def test_the_cnn_runner_counts_training_operations_at_the_configured_widths(traffic):
    cell = run.make_cell({"name": traffic, "chips": 1},
                         flops_dir() / "configs" / "fl_cnn_cifar10_u64.json", traffic)
    ctx = run.Context(cell, 2**31 + 7, False, None, None)
    run.load_runner(cell["config_data"]["kind"]).Runner(ctx)
    cfg = cell["config_data"]
    want = flops.cnn_train_flops(tuple(cfg["image"]), cfg["classes"], cfg["channels"],
                                 cfg["hidden"])
    assert ctx.counters["train_flops_per_sample"] == want == 35_049_216
    assert ctx.counters["params_per_user"] == 552_714
    assert ctx.counters["samples_per_round"] == (
        cfg["users"] * cell["traffic_data"]["local_steps"] * cfg["batch"])
