"""Device self time per filter step of the ops under the program's
``pf/estimate`` scope, kernel ops aside (device trace, read by the ops'
name stacks: ``trace_names.py``)."""

import trace_names


def read(ctx):
    return trace_names.stage_ms(ctx, "estimate")
