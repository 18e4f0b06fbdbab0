"""The whole filter step's share of the chip's peak, in %: the least time
for the step's compulsory work (``counts/``, the larger of the bytes and
the operations bound) over the traced time per step (window / steps)."""

import registry
import tracing


def read(ctx):
    if ctx.trace is None or not ctx.window.steps or ctx.peaks is None:
        return None
    least = registry.least_seconds(registry.work(ctx.config, "step"), ctx.peaks)
    return 100.0 * least / (tracing.window_ns(ctx.trace) / 1e9 / ctx.window.steps)
