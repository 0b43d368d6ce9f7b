"""Share of the traced window in which no operation ran on the device:
1 - (union of the device's busy intervals) / window (device trace)."""


def read(ctx):
    share = ctx.tr.idle_share() if ctx.tr else None
    return None if share is None else 100.0 * share
