"""Set-up: process start to the first measured step, compilation and
warm-up included (host clock)."""


def read(ctx):
    return ctx.setup_s
