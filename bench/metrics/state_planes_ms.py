"""Device self time per filter step of the ops under the program's
``resample/planes`` scope, kernel ops aside: the pack of the particle state
into the kernel's plane stack and its unpack, with whatever XLA fused into
them (device trace, read by the ops' name stacks: ``trace_names.py``).
None where no op carries the scope: a program without it."""

import re

import trace_names
import tracing

PLANES_RX = re.compile(r"(?:^|/)resample/planes(?:/|$)")


def read(ctx):
    names = trace_names.load(ctx)
    if names is None or not ctx.window.steps:
        return None
    scopes = names["device_op_scopes"]
    if not any(PLANES_RX.search(s) for s in scopes):
        return None
    kernel = re.compile(ctx.config["kernel_pattern"])
    ops = [[i, s, e] for i, (_, s, e) in enumerate(ctx.trace["device_ops"])]
    ns = sum(t for i, t in tracing.self_times(tracing.clip(ops, ctx.trace["window"]))
             if PLANES_RX.search(scopes[i]) and not kernel.search(ctx.trace["device_ops"][i][0]))
    return ns / 1e6 / ctx.window.steps
