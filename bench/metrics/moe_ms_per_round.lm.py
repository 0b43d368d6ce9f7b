"""Device time of the expert layers per round (device trace): the ops of
the model's ``fl.moe`` scope (router, top-k, sort, gather, combine, shared
experts) and of ``fl.moe.experts`` within it (the grouped matmuls), forward
and backward, over the ``fl.round`` spans of the traced window."""

import program_trace


def read(ctx):
    pt = program_trace.get(ctx)
    if pt is None:
        return None
    parts = [pt.stage_ms_per_round(s) for s in ("fl.moe", "fl.moe.experts")]
    return None if all(p is None for p in parts) else sum(p or 0.0 for p in parts)
