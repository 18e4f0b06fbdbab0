"""Device time per filter step of every op other than the resampling
kernel: the model's transition and likelihood, the estimate, the key
schedule and the glue around them (device trace)."""

import tracing


def read(ctx):
    if ctx.trace is None or not ctx.window.steps:
        return None
    ns = tracing.op_time_ns(ctx.trace, ctx.config["kernel_pattern"], match=False)
    return ns / 1e6 / ctx.window.steps if ns else None
