"""The bearings-only cell's pieces: its registry entry, its work counts,
its plain reference and the reader of ``state_planes_ms``."""

import json

import jax
import jax.numpy as jnp
import numpy as np

import registry

CELL = "bearings4d-alg6-n2e20-b32.stream"


def _config():
    return registry.find_cell(CELL).config


def _small(**kw):
    return {**_config(), "num_particles": 4096, **kw}


def test_the_cell_resolves_and_alone_reports_state_planes_ms():
    cell = registry.find_cell(CELL)
    assert cell.chips == 1 and cell.traffic["mode"] == "whole_track"
    assert cell.config["state_dim"] == 4 and cell.config["model"] == "bearings_only"
    assert "state_planes_ms" in [m["name"] for m in cell.per_layer]
    for w in registry.load_benchmark()["workloads"]:
        if w["name"] != CELL:
            names = [m["name"] for m in registry.find_cell(w["name"]).per_layer]
            assert "state_planes_ms" not in names, w["name"]


def test_config_holds_the_programs_model_defaults():
    """``system.build`` runs ``bearings_only()``, whose values are module
    constants; the reference reads the configuration's values: the two
    must agree."""
    from repro.pf import models

    cfg = _config()
    program = {"sigma_q": models._SIGMA_Q, "sigma_r": models._SIGMA_R,
               "prior_mean": list(models._PRIOR_MEAN), "prior_std": list(models._PRIOR_STD)}
    for key, value in program.items():
        assert value == cfg[key], key


def test_filter_step_counts_by_hand():
    # the [N, 4] f32 state read and written once: 2 * N * 4 * 4 bytes;
    # 2 x 20 noise + 10 move + 9 likelihood + 4 estimate = 63 ops per
    # particle, plus the apply kernel's N x B x (30 + state_dim) evaluations
    cfg = {"num_particles": 2048, "num_iters": 4, "state_dim": 4, "ess_threshold": None,
           "counts": {"kernel": "megopolis_apply", "step": "bearings_filter_step"}}
    work = registry.load_module("counts", "bearings_filter_step").count(cfg)
    assert work == {"bytes": 2 * 2048 * 4 * 4, "ops": 2048 * 63 + 2048 * 4 * 34}
    cfg = _config()
    assert registry.work(cfg, "step")["bytes"] == 32 * 2**20  # 32 MiB at N = 2^20
    assert registry.work(cfg, "kernel")["bytes"] == 36 * 2**20  # w, x in, x out


def test_reference_simulates_from_the_true_initial_state():
    ref = registry.load_module("reference", "bearings_filter")
    cfg = _small()
    xs, zs = ref.simulate(cfg, jax.random.split(jax.random.PRNGKey(0), 3), 10)
    assert xs.shape == (3, 10, 4) and zs.shape == (3, 10)
    x0 = np.asarray(cfg["true_initial_state"])
    # one step on: position moved by the velocity, noise at sigma_q
    step = x0 + np.array([x0[1], 0.0, x0[3], 0.0])
    assert np.abs(np.asarray(xs[:, 0]) - step).max() < 5 * cfg["sigma_q"]


def test_bf16_control_fails_the_limit():
    """The reference computed in bfloat16 differs from float32 by more than
    the cell's ``est_gap`` limit on a small seeded case."""
    ref = registry.load_module("reference", "bearings_filter")
    cfg = _small()
    _, zs = ref.simulate(cfg, jax.random.split(jax.random.PRNGKey(1), 2), 12)
    keys = jax.random.split(jax.random.PRNGKey(2), 2)
    f32 = np.asarray(ref.filter_tracks(cfg, keys, zs))
    bf16 = np.asarray(ref.filter_tracks(cfg, keys, zs, jnp.bfloat16))
    assert f32.shape == (2, 12, 4) and np.isfinite(f32).all()
    assert np.abs(bf16 - f32).max() > cfg["limits"]["est_gap"]


class _Ctx:
    def __init__(self, trace, steps):
        self.trace = trace
        self.window = type("W", (), {"steps": steps})()
        self.config = {"kernel_pattern": "megopolis_pallas"}


def test_state_planes_ms_reads_the_scoped_ops():
    base = "jit(run_track)/while/body/pf/resample/megopolis/pallas/apply/float32"
    trace = {
        "window": [0, 10_000_000],
        "device_ops": [["%concatenate.17", 0, 1_000_000],
                       ["%megopolis_pallas_apply.7", 1_000_000, 9_000_000],
                       ["%bitcast_bitcast_fusion.2", 9_000_000, 9_500_000],
                       ["%fusion.117", 9_500_000, 10_000_000]],
        "device_op_scopes": [base + "/resample/planes/concatenate",
                             base + "/jit(megopolis_pallas_fused)/megopolis_pallas_apply/pallas_call",
                             base + "/resample/planes/transpose",
                             "jit(run_track)/while/body/pf/predict/mul"],
        "host_spans": [],
    }
    read = registry.load_module("metrics", "state_planes_ms").read
    assert read(_Ctx(trace, 3)) == 1.5 / 3  # 1.0 + 0.5 ms over 3 steps
    # a program without the scope (the parent's) has nothing to read
    trace["device_op_scopes"] = [s.replace("resample/planes/", "") for s in
                                 trace["device_op_scopes"]]
    assert read(_Ctx(trace, 3)) is None
    assert read(_Ctx(None, 3)) is None


def test_the_benchmark_file_names_the_new_entries_once():
    bench = json.loads((registry.ROOT / "BENCHMARK.json").read_text())
    assert [c["name"] for c in bench["configs"]].count("bearings4d-alg6-n2e20-b32") == 1
    assert [w["name"] for w in bench["workloads"]][-1] == CELL
    assert bench["per_layer"][-1]["name"] == "state_planes_ms"
    assert bench["per_layer"][-1]["workloads"] == [CELL]
