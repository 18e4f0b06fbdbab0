"""The trace read by the names the program puts on its work: the stage
split of the device time and the runtime's host events, on a hand-made
trace whose answers are worked out by hand and on traces recorded on the
chip."""

import subprocess
import sys
from pathlib import Path

import pytest

import registry
import run
import trace_names
import tracing

DATA = Path(__file__).with_name("data")
BODY = "jit(run_track)/while/body"
KERNEL = (f"{BODY}/pf/resample/megopolis/pallas/apply/float32/jit(megopolis_pallas_fused)/"
          "megopolis_pallas_apply/pallas_call")
# window 0..100 ns; a loop op holding one step's ops, and a copy past the end
HAND = {
    "window": [0, 100],
    "device_ops": [["%while.1", 0, 90], ["%fusion.1", 5, 15], ["%fusion.2", 15, 20],
                   ["%fusion.3", 20, 22], ["%megopolis_pallas_apply.1", 22, 60],
                   ["%fusion.4", 60, 63], ["%fusion.5", 63, 65], ["%copy.1", 70, 75],
                   ["%copy.2", 95, 120]],
    "device_op_scopes": [
        "jit(run_track)/while",
        f"{BODY}/pf/predict/add",
        f"{BODY}/pf/update/exp",
        f"{BODY}/pf/resample/megopolis/pallas/apply/float32/jit(_randint)/add",
        KERNEL,
        f"{BODY}/pf/estimate/reduce_sum",
        # the first pf/ segment is the caller's: a cached helper's stale stack follows it
        f"{BODY}/pf/update/jit(helper)/pf/predict/mul",
        f"{BODY}/jit(_threefry_split)/slice",
        "",
    ],
    "host_spans": [["bench/dispatch", 0, 10], ["bench/read", 10, 50],
                   ["bench/dispatch", 50, 60], ["bench/read", 60, 100]],
    "host_events": [["DevicePut", 2, 4], ["DevicePut", 3, 6], ["DevicePut", 40, 45],
                    ["DevicePut", 55, 58], ["PJRT_LoadedExecutable_Execute", 6, 9],
                    ["PJRT_LoadedExecutable_Execute", 58, 62],
                    ["TransferFromDevice", 88, 97]],
}
STAGE_METRICS = ("predict_ms", "update_ms", "estimate_ms", "resample_glue_ms", "unscoped_ms")
HOST_METRICS = ("host_arg_put_us", "host_execute_us")


class _Ctx:
    def __init__(self, trace, config, steps, mode="whole_track"):
        self.trace, self.config, self.peaks = trace, config, None
        self.window = type("W", (), {"steps": steps, "dispatch": [], "latencies": []})()
        self.traffic = {"mode": mode}


def _read(name, ctx):
    return registry.load_module("metrics", name).read(ctx)


def _cfg(cell="ungm-alg6-n2e20-b32.online"):
    return registry.find_cell(cell).config


def test_stage_split_by_the_first_pf_scope():
    split = trace_names.stage_ns(HAND, HAND, "megopolis")
    # the loop's self time is 90 less the 65 ns of the ops inside it
    assert split == {"predict": 10, "update": 5 + 2, "estimate": 3, "resample_glue": 2,
                     "unscoped": 25 + 5 + 5}


def test_stage_readers_partition_the_non_kernel_time():
    ctx = _Ctx(HAND, {**_cfg(), "kernel_pattern": "megopolis"}, steps=2)
    got = {m: _read(m, ctx) for m in STAGE_METRICS}
    assert got == pytest.approx({"predict_ms": 5e-6, "update_ms": 3.5e-6, "estimate_ms": 1.5e-6,
                                 "resample_glue_ms": 1e-6, "unscoped_ms": 17.5e-6})
    assert sum(got.values()) == pytest.approx(_read("model_ms", ctx))
    assert _read("resample_kernel_ms", ctx) == pytest.approx(19e-6)


def test_host_readers_take_the_union_inside_dispatch_per_observation():
    ctx = _Ctx(HAND, _cfg(), steps=2, mode="per_observation")
    # DevicePut: [2, 6] and [55, 58] inside the two dispatches; Execute: [6, 9], [58, 60]
    assert _read("host_arg_put_us", ctx) == pytest.approx((4 + 3) / 2 / 1e3)
    assert _read("host_execute_us", ctx) == pytest.approx((3 + 2) / 2 / 1e3)
    assert _read("host_arg_put_us", _Ctx(HAND, _cfg(), steps=2)) is None  # whole tracks


def test_idle_gaps_by_host_event():
    # the one gap, [90, 95], lies in bench/read and in the transfer's event
    assert trace_names.idle_by_host_event(HAND) == [["bench/read", "TransferFromDevice", 5e-9]]


def test_readers_find_nothing_without_a_trace_or_its_scopes():
    for name in STAGE_METRICS + HOST_METRICS:
        assert _read(name, _Ctx(None, _cfg(), steps=5, mode="per_observation")) is None
    unscoped = {**HAND, "device_op_scopes": [""] * len(HAND["device_ops"])}
    for name in STAGE_METRICS:
        assert _read(name, _Ctx(unscoped, _cfg(), steps=2)) is None


def _chip_ctx(name, monkeypatch):
    monkeypatch.setattr(run, "TRACE_DIR", DATA / name)
    trace = tracing.read_xplane(DATA / name)
    steps = sum(1 for s in tracing.clip(trace["host_spans"], trace["window"])
                if s[0] == "bench/dispatch")
    return _Ctx(trace, _cfg(), steps=steps, mode="per_observation")


def test_recorded_chip_trace_names_and_host_events(monkeypatch, capsys):
    """The trace of ``test_tracing.py`` (55 observations of the online
    cell, recorded before the program named its stages)."""
    ctx = _chip_ctx("online_trace", monkeypatch)
    names = trace_names.load(ctx)
    assert names["device_ops"] == ctx.trace["device_ops"]
    kernel = [s for (n, _, _), s in zip(names["device_ops"], names["device_op_scopes"])
              if "megopolis_pallas" in n]
    assert len(kernel) == 55
    assert set(kernel) == {"jit(step)/jit(megopolis_pallas_fused)/pallas_call"}
    assert ctx.window.steps == 55
    assert _read("host_arg_put_us", ctx) == pytest.approx(383, rel=0.01)
    assert _read("host_execute_us", ctx) == pytest.approx(214, rel=0.01)
    # no op carries a pf/ stage, so the stage readers say nothing
    for name in STAGE_METRICS:
        assert _read(name, ctx) is None
    assert "trace idle_by_host_event [['bench/read'," in capsys.readouterr().err


def test_reading_the_trace_imports_no_tensorflow():
    code = ("import sys; sys.path[:0] = [%r]\n"
            "import trace_names\n"
            "trace_names.read_names(next(__import__('pathlib').Path(%r).rglob('*.xplane.pb')))\n"
            "assert not [m for m in sys.modules if m.startswith('tensorflow')]\n"
            % (str(registry.ROOT / "bench"), str(DATA / "online_trace")))
    p = subprocess.run([sys.executable, "-c", code], capture_output=True, text=True, timeout=120)
    assert p.returncode == 0, p.stderr


def test_recorded_scoped_chip_trace(monkeypatch):
    """0.3 s of ``ungm-alg6-n2e20-b32.online`` traced on a TPU v5 lite with
    the program's stage scopes: 54 observations, one fused Megopolis launch
    each."""
    ctx = _chip_ctx("online_trace_scoped", monkeypatch)
    assert ctx.window.steps == 54
    names = trace_names.load(ctx)
    kernel = {(n.split(".")[0], s) for (n, _, _), s in
              zip(names["device_ops"], names["device_op_scopes"]) if "megopolis_pallas" in n}
    # the pallas_call's name is the HLO instruction's and a segment of tf_op
    assert kernel == {("%megopolis_pallas_apply", "jit(step)/pf/resample/megopolis/pallas/apply/"
                       "float32/jit(megopolis_pallas_fused)/megopolis_pallas_apply/pallas_call")}
    got = {m: _read(m, ctx) for m in STAGE_METRICS}
    assert got == pytest.approx({"predict_ms": 29629 / 54e6, "update_ms": 0.0,
                                 "estimate_ms": 92397 / 54e6, "resample_glue_ms": 2455509 / 54e6,
                                 "unscoped_ms": 519362 / 54e6})
    assert sum(got.values()) == pytest.approx(_read("model_ms", ctx), rel=1e-12)
    assert _read("resample_kernel_ms", ctx) == pytest.approx(202192189 / 54e6)
    put, execute = _read("host_arg_put_us", ctx), _read("host_execute_us", ctx)
    assert put == pytest.approx(387.36, rel=1e-4) and execute == pytest.approx(216.87, rel=1e-4)
    dispatch = [e - s for n, s, e in tracing.clip(ctx.trace["host_spans"], ctx.trace["window"])
                if n == "bench/dispatch"]
    assert put + execute < sum(dispatch) / len(dispatch) / 1e3
