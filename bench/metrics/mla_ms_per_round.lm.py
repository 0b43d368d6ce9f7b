"""Device time of latent attention per round (device trace): the ops the
model's ``fl.mla`` scope holds (the four projections with their adapters,
the YaRN rope, the attention core, forward and backward, recomputes
included), over the ``fl.round`` spans of the traced window."""

import program_trace


def read(ctx):
    pt = program_trace.get(ctx)
    return None if pt is None else pt.stage_ms_per_round("fl.mla")
