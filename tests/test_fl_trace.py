"""The gossip-FL round's names in a profile: the stage scopes in the
compiled round's HLO metadata, and the round spans on the host."""

import glob
import os

import jax
import numpy as np
import pytest

from repro.core.graphs import gossip_task_graph
from repro.data.synthetic import image_dataset
from repro.fl import gossip
from repro.fl.cnn import cnn_loss, init_cnn_params
from repro.train.compression import TopK

STAGES = ("fl.local", "fl.compress", "fl.mix", "fl.aggregate")


def _trainer(compressor=None):
    rng = np.random.default_rng(0)
    tg = gossip_task_graph(rng, 4, degree_low=2, degree_high=3)
    train, _ = image_dataset("mnist", 256, seed=0)
    cfg = gossip.GossipConfig(local_steps=2, batch_size=32, compressor=compressor)
    return gossip.GossipTrainer(
        tg, lambda k: init_cnn_params(k, (28, 28, 1), 10), cnn_loss,
        train.split(4, rng), cfg, seed=0,
    )


def _op_names(trainer) -> str:
    hlo = trainer._round_jit.lower(trainer._state, *trainer._data).compile().as_text()
    return "\n".join(line for line in hlo.splitlines() if "op_name=" in line)


@pytest.mark.parametrize("compressor, stages", [
    (TopK(fraction=0.1), STAGES),
    (None, ("fl.local", "fl.mix", "fl.aggregate")),
])
def test_compiled_round_carries_one_scope_per_stage(compressor, stages):
    meta = _op_names(_trainer(compressor))
    for stage in STAGES:
        assert (f"/{stage}/" in meta) == (stage in stages), stage
    # the backward pass keeps the local stage's scope
    assert "transpose(jvp" in meta and "fl.local/" in meta


def test_profile_of_two_rounds_holds_two_of_each_round_span(tmp_path):
    from jax.profiler import ProfileData

    trainer = _trainer()
    trainer.step_round()                     # compile outside the trace
    jax.profiler.start_trace(str(tmp_path))
    try:
        for _ in range(2):
            trainer.step_round()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(tmp_path, "plugins", "profile", "*", "*.xplane.pb"))
    names = [ev.name for plane in ProfileData.from_file(path).planes
             if plane.name.startswith("/host:")
             for line in plane.lines for ev in line.events
             if ev.name.startswith("fl.")]
    assert sorted(names) == sorted(
        [gossip.SPAN_ROUND, gossip.SPAN_DISPATCH, gossip.SPAN_READBACK] * 2)
