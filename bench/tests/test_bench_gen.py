"""The generator: every input of a run is determined by its seed."""

import numpy as np

import gen

BIG = 2**31 + 12345


def test_gossip_graph_data_and_weights_follow_the_seed():
    assert gen.gossip_edges(BIG, 16, 6, 7) == gen.gossip_edges(BIG, 16, 6, 7)
    assert gen.gossip_edges(BIG, 16, 6, 7) != gen.gossip_edges(BIG + 1, 16, 6, 7)
    x1, y1 = gen.image_data(BIG, 2, 8, (16, 16, 3), 10, 0.35)
    x2, y2 = gen.image_data(BIG, 2, 8, (16, 16, 3), 10, 0.35)
    x3, _ = gen.image_data(BIG + 1, 2, 8, (16, 16, 3), 10, 0.35)
    assert x1.shape == (2, 8, 16, 16, 3) and y1.shape == (2, 8)
    np.testing.assert_array_equal(np.asarray(x1), np.asarray(x2))
    np.testing.assert_array_equal(np.asarray(y1), np.asarray(y2))
    assert not np.array_equal(np.asarray(x1), np.asarray(x3))
    assert float(x1.min()) >= 0.0 and float(x1.max()) <= 1.0
    p1 = gen.cnn_init(BIG, (16, 16, 3), 10)
    p2 = gen.cnn_init(BIG, (16, 16, 3), 10)
    assert p1["fc1"]["w"].shape == (4 * 4 * 64, 128)
    np.testing.assert_array_equal(np.asarray(p1["conv1"]["w"]), np.asarray(p2["conv1"]["w"]))


def test_cnn_widths_follow_the_configuration():
    p = gen.cnn_init(BIG, (16, 16, 3), 10, channels=(8, 16), hidden=(24, 12))
    shapes = {k: v["w"].shape for k, v in p.items()}
    assert shapes == {"conv1": (3, 3, 3, 8), "conv2": (3, 3, 8, 16),
                      "fc1": (4 * 4 * 16, 24), "fc2": (24, 12), "fc3": (12, 10)}
