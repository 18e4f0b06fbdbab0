"""The one traffic generator: a closed loop with one client.

A traffic file (``traffic/<mix>.json``) gives its parameters:

* ``mode``: ``whole_track``, one request per track through
  ``System.run_track`` (the estimates are read back before the next
  request), or ``per_observation``, one request per observation through
  ``System.step``, the observation passed from the host as the call's
  argument (the estimate is read back before the next observation is
  sent; a track starts with ``System.start``);
* ``steps_per_track``: T, the observations in a track;
* ``pool``: how many simulated trajectories the tracks cycle through;
* ``warmup_steps``: observations sent before the window in
  ``per_observation`` mode (``whole_track`` warms up with one track);
* ``check_tracks``: how many finished tracks the correctness check replays.

Track r filters trajectory ``r mod pool`` with filter key
``fold_in(base_key, r)``; the same seed gives the same tracks in the same
order. Host spans (``bench/...``) mark what the host is doing, for the
trace's idle-gap breakdown.
"""

from __future__ import annotations

import dataclasses
import time

import numpy as np

WARMUP_TRACK = 2**31 - 1  # a track number the window never reaches


@dataclasses.dataclass
class Window:
    """What one measured window did, on the host clock (seconds)."""

    start: float = 0.0
    end: float = 0.0
    steps: int = 0  # filter steps completed
    requests: int = 0  # requests completed
    latencies: list = dataclasses.field(default_factory=list)  # per request
    dispatch: list = dataclasses.field(default_factory=list)  # jitted call, per request
    tracks: dict = dataclasses.field(default_factory=dict)  # r -> estimates so far

    @property
    def seconds(self) -> float:
        return self.end - self.start


def warm_up(traffic, system, base_key, pool_dev, pool_np):
    """Run every program the window runs, on a track the window never
    reaches. Returns the seconds each entry's first call took."""
    import jax

    first = {}
    if traffic["mode"] == "whole_track":
        for i in range(2):
            t0 = time.perf_counter()
            np.asarray(system.run_track(base_key, np.int32(WARMUP_TRACK - i), pool_dev))
            first.setdefault("run_track", time.perf_counter() - t0)
        return first
    t0 = time.perf_counter()
    state = system.start(base_key, np.int32(WARMUP_TRACK))
    jax.block_until_ready(state)
    first["start"] = time.perf_counter() - t0
    zs = pool_np[WARMUP_TRACK % len(pool_np)]
    for t in range(1, traffic["warmup_steps"] + 1):
        t0 = time.perf_counter()
        state, est = system.step(state, zs[(t - 1) % len(zs)], np.float32(t))
        float(est)
        first.setdefault("step", time.perf_counter() - t0)
    return first


def run_window(traffic, system, base_key, pool_dev, pool_np, seconds) -> Window:
    import jax

    span = jax.profiler.TraceAnnotation
    w = Window()
    n_pool = len(pool_np)
    steps_per_track = traffic["steps_per_track"]
    with span("bench/window"):
        w.start = time.perf_counter()
        r = 0
        if traffic["mode"] == "whole_track":
            while True:
                t0 = time.perf_counter()
                with span("bench/dispatch"):
                    out = system.run_track(base_key, np.int32(r), pool_dev)
                t1 = time.perf_counter()
                with span("bench/read"):
                    est = np.asarray(out)
                t2 = time.perf_counter()
                w.tracks[r] = est
                w.latencies.append(t2 - t0)
                w.dispatch.append(t1 - t0)
                w.steps += steps_per_track
                w.requests += 1
                r += 1
                if t2 - w.start >= seconds:
                    break
        else:
            done = False
            while not done:
                with span("bench/start"):
                    state = system.start(base_key, np.int32(r))
                zs = pool_np[r % n_pool]
                ests = []
                w.tracks[r] = ests
                for t in range(1, steps_per_track + 1):
                    t0 = time.perf_counter()
                    with span("bench/dispatch"):
                        state, est = system.step(state, zs[t - 1], np.float32(t))
                    t1 = time.perf_counter()
                    with span("bench/read"):
                        ests.append(float(est))
                    t2 = time.perf_counter()
                    w.latencies.append(t2 - t0)
                    w.dispatch.append(t1 - t0)
                    w.steps += 1
                    w.requests += 1
                    if t2 - w.start >= seconds:
                        done = True
                        break
                r += 1
        w.end = time.perf_counter()
    w.tracks = {k: np.asarray(v, np.float32) for k, v in w.tracks.items()}
    return w
