"""Share of the traced window in which no op ran on the device, in %:
1 - (union of the ops' intervals) / window (device trace)."""

import tracing


def read(ctx):
    if ctx.trace is None or not ctx.trace["device_ops"]:
        return None
    return 100.0 * (1.0 - tracing.busy_ns(ctx.trace) / tracing.window_ns(ctx.trace))
