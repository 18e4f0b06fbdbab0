"""The resampling kernel's share of its roofline, in %: the least time the
chip could take for the kernel entry's compulsory work (the larger of bytes
over peak bandwidth and operations over peak rate, ``counts/``), over the
kernel's measured device time per step."""

import registry
import tracing


def read(ctx):
    if ctx.trace is None or not ctx.window.steps or ctx.peaks is None:
        return None
    ns = tracing.op_time_ns(ctx.trace, ctx.config["kernel_pattern"])
    if not ns:
        return None
    least = registry.least_seconds(registry.work(ctx.config, "kernel"), ctx.peaks)
    return 100.0 * least / (ns / 1e9 / ctx.window.steps)
