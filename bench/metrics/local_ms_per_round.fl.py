"""Device time of local training per round (device trace): the ops the
trainer's ``fl.local`` scope holds (forward, backward and the SGDM update
of every local step), over the ``fl.round`` spans of the traced window."""

import program_trace


def read(ctx):
    pt = program_trace.get(ctx)
    return None if pt is None else pt.stage_ms_per_round("fl.local")
