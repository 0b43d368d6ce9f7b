"""Device time of the compression stage per round (device trace): every op
the trainer's ``fl.compress`` scope holds (the delta add, the top-k
threshold select, the mask kernels, the reshapes between them), over the
``fl.round`` spans of the traced window.  It follows the stage whatever
implements it."""

import program_trace


def read(ctx):
    pt = program_trace.get(ctx)
    return None if pt is None else pt.stage_ms_per_round("fl.compress")
