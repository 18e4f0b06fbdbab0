"""Device self time per filter step of the ops under the program's
``pf/resample`` scope that are not the configuration's kernel
(``kernel_pattern``): the ``Resampler`` entry's work around its launch
(device trace, read by the ops' name stacks: ``trace_names.py``)."""

import trace_names


def read(ctx):
    return trace_names.stage_ms(ctx, "resample_glue")
