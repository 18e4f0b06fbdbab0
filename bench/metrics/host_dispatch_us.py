"""Mean host time per observation from calling the jitted step until it
returns (the benchmark's host-clock span around the call)."""

import numpy as np


def read(ctx):
    if ctx.traffic["mode"] != "per_observation" or not ctx.window.dispatch:
        return None
    return float(np.mean(ctx.window.dispatch)) * 1e6
