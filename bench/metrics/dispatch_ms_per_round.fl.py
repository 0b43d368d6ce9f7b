"""Host time a round spends issuing its jitted calls (program span): the
trainer's ``fl.round.dispatch`` spans in the traced window, over its
``fl.round`` spans.  With one call a round (``last_round_dispatches``) it
is the mean duration of a dispatch."""

import program_trace


def read(ctx):
    pt = program_trace.get(ctx)
    return None if pt is None else pt.dispatch_ms_per_round()
