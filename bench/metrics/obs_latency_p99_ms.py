"""99th percentile of the time from sending an observation to holding its
estimate on the host, over every observation of the window (host clock)."""

import numpy as np


def read(ctx):
    lat = ctx.window.latencies
    if ctx.traffic["mode"] != "per_observation" or not lat:
        return None
    return float(np.quantile(np.asarray(lat, np.float64), 0.99, method="inverted_cdf")) * 1e3
