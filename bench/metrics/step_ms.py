"""Filter step time: the window's wall time over the filter steps it
completed (host clock, a rate over the whole window)."""


def read(ctx):
    w = ctx.window
    return w.seconds * 1e3 / w.steps if w.steps else None
