"""Device self time per filter step of the ops under no ``pf/`` scope,
kernel ops aside: the key schedule, the scan's control, copies and a
track's start (device trace, read by the ops' name stacks:
``trace_names.py``)."""

import trace_names


def read(ctx):
    return trace_names.stage_ms(ctx, "unscoped")
