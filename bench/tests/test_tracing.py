"""The reduction from trace events to metrics, on a hand-made trace whose
answers are worked out by hand, and on a trace recorded on the chip."""

import json
from pathlib import Path

import pytest

import registry
import tracing

# window 0..100 ns; two kernel launches, one model op, one op past the end
HAND = {
    "window": [0, 100],
    "device_ops": [["megopolis_fused", 10, 40], ["fusion.1", 40, 50],
                   ["megopolis_fused", 60, 90], ["copy", 95, 120]],
    "host_spans": [["bench/dispatch", 0, 12], ["bench/read", 12, 100],
                   ["bench/send", 52, 58]],
}
DATA = Path(__file__).with_name("data")


def test_busy_union_and_window():
    # union: [10, 50] + [60, 90] + [95, 100] clipped = 40 + 30 + 5
    assert tracing.busy_ns(HAND) == 75
    assert tracing.window_ns(HAND) == 100


def test_op_time_by_pattern():
    assert tracing.op_time_ns(HAND, "megopolis") == 60
    assert tracing.op_time_ns(HAND, "megopolis", match=False) == 10 + 5


def test_idle_gaps_are_labelled_by_the_innermost_host_span():
    gaps = tracing.idle_gaps(HAND)
    # gaps: [0, 10] (dispatch), [50, 60] (send inside read), [90, 95] (read)
    assert gaps == [["bench/dispatch", 10e-9], ["bench/send", 10e-9], ["bench/read", 5e-9]]


def test_top_ops():
    assert tracing.top_ops(HAND, 2) == [["megopolis_fused", 60e-9], ["fusion.1", 10e-9]]


class _Ctx:
    def __init__(self, trace, config, steps, peaks, mode="whole_track"):
        self.trace, self.config, self.peaks = trace, config, peaks
        self.window = type("W", (), {"steps": steps, "dispatch": [], "latencies": []})()
        self.traffic = {"mode": mode}


def _read(name, ctx):
    return registry.load_module("metrics", name).read(ctx)


def test_metric_readers_on_the_hand_trace():
    cfg = {"kernel_pattern": "megopolis", "num_particles": 1024, "num_iters": 2,
           "state_dim": 1, "ess_threshold": None,
           "counts": {"kernel": "megopolis_apply", "step": "ungm_filter_step"}}
    peaks = {"hbm_bytes_per_s": 1e12, "ops_per_s": 1e18}
    ctx = _Ctx(HAND, cfg, steps=2, peaks=peaks)
    assert _read("resample_kernel_ms", ctx) == pytest.approx(30e-6)
    assert _read("model_ms", ctx) == pytest.approx(7.5e-6)
    assert _read("device_idle_share", ctx) == pytest.approx(25.0)
    # kernel bytes 12 KiB at 1e12 B/s = 12.288 ns, over 30 ns per step
    assert _read("resample_roofline", ctx) == pytest.approx(100 * 12.288 / 30)
    # step bytes 8 KiB = 8.192 ns, over 100 ns / 2 steps
    assert _read("step_mfu", ctx) == pytest.approx(100 * 8.192 / 50)


def test_readers_find_nothing_without_a_trace():
    ctx = _Ctx(None, {}, steps=5, peaks=None)
    for name in ("resample_kernel_ms", "model_ms", "device_idle_share",
                 "resample_roofline", "step_mfu"):
        assert _read(name, ctx) is None


def test_self_time_of_nested_ops():
    # a loop op spanning two body ops, as the chip's op line records it
    nested = {"window": [0, 100], "host_spans": [],
              "device_ops": [["%while.4", 0, 100], ["%megopolis_pallas_fused.7", 10, 60],
                             ["%fusion.5", 60, 90]]}
    assert tracing.op_time_ns(nested, "megopolis_pallas") == 50
    assert tracing.op_time_ns(nested, "megopolis_pallas", match=False) == 20 + 30
    assert tracing.top_ops(nested) == [["%megopolis_pallas_fused.7", 50e-9],
                                       ["%fusion.5", 30e-9], ["%while.4", 20e-9]]
    assert tracing.busy_ns(nested) == 100


def test_recorded_chip_trace():
    """0.3 s of ``ungm-alg6-n2e20-b32.online`` traced on a TPU v5 lite:
    55 observations, one fused Megopolis launch each."""
    trace = tracing.read_xplane(DATA / "online_trace")
    ops = tracing.clip(trace["device_ops"], trace["window"])
    kernel = [o for o in ops if "megopolis_pallas_fused" in o[0]]
    assert len(kernel) == 55
    spans = [s[0] for s in trace["host_spans"]]
    assert spans.count("bench/dispatch") == spans.count("bench/read") == 55
    assert tracing.window_ns(trace) == 304400948
    assert tracing.op_time_ns(trace, "megopolis_pallas") == 205934178
    assert tracing.op_time_ns(trace, "megopolis_pallas", match=False) == 3154491
    # ops nest or are disjoint: self times add up to the busy union
    assert tracing.busy_ns(trace) == 205934178 + 3154491
    assert tracing.top_ops(trace, 1) == [["%megopolis_pallas_fused.1", 0.205934178]]
    assert [g[0] for g in tracing.idle_gaps(trace, 3)] == ["bench/read"] * 3

    cfg = registry.find_cell("ungm-alg6-n2e20-b32.online").config
    ctx = _Ctx(trace, cfg, steps=55, peaks=registry.peaks("TPU v5 lite"),
               mode="per_observation")
    assert _read("resample_kernel_ms", ctx) == pytest.approx(205934178 / 55 / 1e6)
    assert _read("device_idle_share", ctx) == pytest.approx(
        100 * (1 - 209088669 / 304400948))
    # 12 MiB at 819 GB/s over 3.744 ms per step
    assert _read("resample_roofline", ctx) == pytest.approx(
        100 * (12 * 2**20 / 819e9) / (205934178 / 55 / 1e9))
