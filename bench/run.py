#!/usr/bin/env python3
"""Run one benchmark cell once, on the TPU this process finds.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Builds the cell's configuration through the program's public API, warms up
the programs the cell's traffic runs, drives the traffic for ``--seconds``
and checks the window's answers against the plain reference. Set-up is
reported phase by phase on earlier lines; the compared numbers, each beside
its limit, are the last lines on standard error; the last line on standard
output is one JSON object. ``--trace 0`` reports the cell's end-to-end
metrics; ``--trace 1`` traces the window with the JAX profiler and reports
its per-layer metrics and a breakdown.

Exits non-zero, printing no result, where JAX finds no TPU or fewer chips
than the cell asks for.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path[:0] = [str(BENCH), str(ROOT / "src")]

import registry  # noqa: E402

CACHE_DIR = ROOT / ".jax_cache" / "bench"
TRACE_DIR = ROOT / ".bench_trace"


class NoAccelerator(RuntimeError):
    pass


def log(msg):
    print(msg, file=sys.stderr, flush=True)


class CompileLog:
    """Compile time and persistent-cache outcomes, from JAX's own events."""

    def __init__(self):
        from jax import monitoring

        self.seconds, self.events = {}, {}
        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)

    def _duration(self, event, duration_secs, **_):
        self.seconds[event] = self.seconds.get(event, 0.0) + duration_secs

    def _event(self, event, **_):
        self.events[event] = self.events.get(event, 0) + 1

    def take(self):
        out = {"trace_s": self.seconds.get("/jax/core/compile/jaxpr_trace_duration", 0.0),
               "lower_s": self.seconds.get(
                   "/jax/core/compile/jaxpr_to_mlir_module_duration", 0.0),
               "backend_compile_s": self.seconds.get(
                   "/jax/core/compile/backend_compile_duration", 0.0),
               "cache_hits": self.events.get("/jax/compilation_cache/cache_hits", 0),
               "cache_misses": self.events.get("/jax/compilation_cache/cache_misses", 0)}
        self.seconds, self.events = {}, {}
        return out


def base_keys(seed):
    """(pool key, filter key) from a seed of up to 64 bits."""
    import jax

    key = jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF), (seed >> 32) & 0xFFFFFFFF)
    return jax.random.fold_in(key, 1), jax.random.fold_in(key, 2)


def run(workload, seed, seconds, trace, *, require_tpu=True, overrides=None):
    """One run of one cell. Returns the result dict (the last line)."""
    cell = registry.find_cell(workload)
    config = {**cell.config, **(overrides or {}).get("config", {})}
    traffic = {**cell.traffic, **(overrides or {}).get("traffic", {})}

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    import numpy as np

    devices = jax.devices()
    dev = devices[0]
    if require_tpu and (dev.platform != "tpu" or len(devices) < cell.chips):
        raise NoAccelerator(f"{workload} needs {cell.chips} TPU chip(s); JAX found "
                            f"{len(devices)} {dev.platform} device(s)")
    peaks = registry.peaks(dev.device_kind) if require_tpu else None
    import repro  # noqa: F401  (the system under test: a lone copy of bench/ stops here)

    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    compiles = CompileLog()
    log(f"setup jax_init_s {time.perf_counter() - T_PROCESS} device {dev.platform} "
        f"{dev.device_kind} x{len(devices)} jax {jax.__version__}")

    import check
    import loadgen
    import system as system_mod

    reference = registry.load_module("reference", config["reference"])
    t0 = time.perf_counter()
    pool_key, filter_key = base_keys(seed)
    _, pool_zs = reference.simulate(config, jax.random.split(pool_key, traffic["pool"]),
                                    traffic["steps_per_track"])
    pool_zs = np.asarray(pool_zs)
    pool_dev = jax.device_put(pool_zs)
    log(f"setup simulate_s {time.perf_counter() - t0} {compiles.take()}")

    t0 = time.perf_counter()
    sut = system_mod.build(config)
    first = loadgen.warm_up(traffic, sut, filter_key, pool_dev, pool_zs)
    log(f"setup build_and_warmup_s {time.perf_counter() - t0} first_calls_s {first} "
        f"{compiles.take()}")
    setup_s = time.perf_counter() - T_PROCESS
    log(f"setup total_s {setup_s}")

    trace_dir = TRACE_DIR / workload
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0  # host spans are the benchmark's own
        options.host_tracer_level = 1
        jax.profiler.start_trace(str(trace_dir), profiler_options=options)
    window = loadgen.run_window(traffic, sut, filter_key, pool_dev, pool_zs, seconds)
    traced = None
    if trace:
        jax.profiler.stop_trace()
        import tracing

        traced = tracing.read_xplane(trace_dir)
    in_window = compiles.take()
    lat = np.asarray(window.latencies) * 1e3
    log(f"window s {window.seconds} steps {window.steps} requests {window.requests} "
        f"latency_ms p50 {np.median(lat)} max {lat.max()} dispatch_us_mean "
        f"{np.mean(window.dispatch) * 1e6} compiles_in_window {in_window}")

    stats = dev.memory_stats() or {}
    device = {"platform": dev.platform, "kind": dev.device_kind, "count": len(devices),
              "memory_peak_bytes": stats.get("peak_bytes_in_use")}
    ctx = Context(config=config, traffic=traffic, window=window, setup_s=setup_s,
                  trace=traced, peaks=peaks)
    metric_defs = cell.per_layer if trace else cell.end_to_end
    metrics = {}
    for m in metric_defs:
        value = registry.load_module("metrics", m["name"]).read(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    result = {"correct": False, "attempted": window.requests, "failed": 0,
              "metrics": metrics, "device": device}
    if traced is not None:
        import tracing

        result["device"]["busy_s"] = tracing.busy_ns(traced) / 1e9
        result["device"]["window_s"] = tracing.window_ns(traced) / 1e9
        result["breakdown"] = {"device_ops": tracing.top_ops(traced),
                               "idle_gaps": tracing.idle_gaps(traced)}

    del sut  # the program's state is freed before the reference runs
    t0 = time.perf_counter()
    correct, checks = check.compare(config, traffic, window, reference, filter_key,
                                    pool_zs, seed)
    log(f"check reference_s {time.perf_counter() - t0}")
    result["correct"] = bool(correct)
    # a request fails where it answered with a non-finite estimate
    bad = [int(np.sum(~np.isfinite(e))) for e in window.tracks.values()]
    result["failed"] = sum(bad) if traffic["mode"] == "per_observation" else sum(map(bool, bad))
    result["checks"] = checks
    for name, c in checks.items():
        log(f"check {name} {c['value']} limit {c['limit']}")
    return result


class Context:
    """What a metric reader reads: the window's host-clock record, the
    trace (None without ``--trace 1``), the configuration, the traffic,
    set-up seconds and the chip's peaks."""

    def __init__(self, **kw):
        self.__dict__.update(kw)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        result = run(args.workload, args.seed, args.seconds, bool(args.trace))
    except NoAccelerator as e:
        log(f"bench: {e}")
        return 2
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
