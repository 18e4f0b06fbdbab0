"""Device time per filter step of the configuration's resampling kernel:
the ops whose name matches its ``kernel_pattern`` (device trace)."""

import tracing


def read(ctx):
    if ctx.trace is None or not ctx.window.steps:
        return None
    ns = tracing.op_time_ns(ctx.trace, ctx.config["kernel_pattern"])
    return ns / 1e6 / ctx.window.steps if ns else None
