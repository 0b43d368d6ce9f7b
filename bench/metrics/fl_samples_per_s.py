"""Training samples completed over the whole window: users x local steps x
batch x rounds, over the window's seconds (host clock)."""


def read(ctx):
    if not ctx.records.get("window_s"):
        return None
    return ctx.records["samples"] / ctx.records["window_s"]
