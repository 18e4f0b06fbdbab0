"""Host time per observation of ``PJRT_LoadedExecutable_Execute``, the
launch's enqueue and output allocation: the union of its intervals inside
each ``bench/dispatch`` span (device trace's host lines,
``trace_names.py``)."""

import trace_names


def read(ctx):
    return trace_names.host_us(ctx, "PJRT_LoadedExecutable_Execute")
