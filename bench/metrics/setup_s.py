"""Set-up: process start to the first timed request or round, compiles
included (host clock)."""


def read(ctx):
    return ctx.setup_s
