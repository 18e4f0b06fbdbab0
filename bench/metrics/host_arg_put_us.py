"""Host time per observation of the runtime's ``DevicePut`` of the jitted
call's host arguments: the union of its intervals inside each
``bench/dispatch`` span (device trace's host lines, ``trace_names.py``)."""

import trace_names


def read(ctx):
    return trace_names.host_us(ctx, "DevicePut")
