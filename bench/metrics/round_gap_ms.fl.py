"""Mean device idle between consecutive round programs (device trace): the
device's programs (``XLA Modules``) that run inside the trainer's
``fl.round`` spans, and the time from the end of one to the start of the
next.  What the host does in those gaps is logged with the trace: under
``fl.round.dispatch``, under ``fl.round.readback``, or neither."""

import program_trace


def read(ctx):
    pt = program_trace.get(ctx)
    return None if pt is None else pt.gap_ms()
