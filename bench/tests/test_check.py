"""A whole run of each cell on the CPU at a small size, through the real
harness with only the look for a chip skipped: sound runs come out
correct; the control (the reference in bfloat16 in the program's place)
and every planted fault come out not correct."""

import jax.numpy as jnp
import numpy as np
import pytest

import check
import faults
import registry
import run
import system

SMALL = {
    "config": {"num_particles": 2048,
               "resampler": {"family": "megopolis", "backend": "pallas_interpret",
                             "plane_dtype": "float32"}},
    "traffic": {"steps_per_track": 60, "pool": 2, "check_tracks": 2, "warmup_steps": 2},
}
CELLS = [w["name"] for w in registry.load_benchmark()["workloads"]]
STREAM_CELLS = [c for c in CELLS if registry.find_cell(c).traffic["mode"] == "whole_track"]
SEED = 2**33 + 12345


@pytest.fixture(autouse=True)
def _cache_in_tmp(tmp_path, monkeypatch):
    monkeypatch.setattr(run, "CACHE_DIR", tmp_path / "jax_cache")
    monkeypatch.setattr(run, "TRACE_DIR", tmp_path / "trace")


def _run(cell, trace=False):
    return run.run(cell, SEED, 1.0, trace, require_tpu=False, overrides=SMALL)


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(cell):
    result = _run(cell)
    assert result["correct"], result["checks"]
    assert list(result)[-1] == "checks"
    assert set(result["metrics"]) == {m["name"] for m in registry.find_cell(cell).end_to_end}
    assert result["checks"]["est_gap"]["value"] < 1e-4


def test_traced_run_reports_per_layer_metrics_and_a_breakdown():
    cell = "ungm-alg6-n2e20-b32.online"
    result = _run(cell, trace=True)
    assert result["correct"]
    # the CPU has no TPU op line: device readers find nothing and say nothing
    assert set(result["metrics"]) == {"host_dispatch_us"}
    assert result["breakdown"]["device_ops"] == []
    assert result["device"]["window_s"] > 0


@pytest.mark.parametrize("cell", STREAM_CELLS)
def test_control_is_not_correct(cell, monkeypatch):
    cfg = {**registry.find_cell(cell).config, **SMALL["config"]}
    reference = registry.load_module("reference", cfg["reference"])
    monkeypatch.setattr(system, "build", lambda c: faults.control_system(c, reference))
    result = _run(cell)
    assert not result["correct"]
    assert result["checks"]["est_gap"]["value"] > result["checks"]["est_gap"]["limit"]


@pytest.mark.parametrize("fault", faults.FAULTS)
@pytest.mark.parametrize("cell", CELLS)
def test_planted_fault_is_not_correct(cell, fault):
    with faults.planted(fault):
        result = _run(cell)
    assert not result["correct"], (fault, result["checks"])


def test_the_sample_is_drawn_from_the_seed():
    w = type("W", (), {"tracks": {r: np.zeros(5, np.float32) for r in range(20)}})()
    traffic = {"steps_per_track": 5, "check_tracks": 4}
    a = check.sample_tracks(w, traffic, SEED)
    assert a == check.sample_tracks(w, traffic, SEED) and len(a) == 4
    assert a != check.sample_tracks(w, traffic, SEED + 1)


def test_reference_control_reads_far_above_the_program():
    cfg = {**registry.find_cell(CELLS[0]).config, **SMALL["config"]}
    reference = registry.load_module("reference", cfg["reference"])
    pool_key, filter_key = run.base_keys(SEED)
    import jax

    _, zs = reference.simulate(cfg, jax.random.split(pool_key, 2), 60)
    keys = jnp.stack([jax.random.fold_in(filter_key, r) for r in range(2)])
    hi = np.asarray(reference.filter_tracks(cfg, keys, zs))
    lo = np.asarray(reference.filter_tracks(cfg, keys, zs, jnp.bfloat16))
    assert np.max(np.abs(hi - lo)) > 100 * cfg["limits"]["est_gap"]
