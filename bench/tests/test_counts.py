"""Each ``counts/`` function against a hand calculation at a small N."""

import registry

SMALL = {"num_particles": 2048, "num_iters": 4, "state_dim": 1,
         "counts": {"kernel": "megopolis_apply", "step": "ungm_filter_step"}}


def test_apply_counts_by_hand():
    # weights read 2048 x 4 B, state read and written 2 x 2048 x 4 B;
    # 2048 x 4 evaluations x (22 hash + 4 index + 2 accept + 2 + 1 selects)
    work = registry.load_module("counts", "megopolis_apply").count(SMALL)
    assert work == {"bytes": 8192 + 16384, "ops": 2048 * 4 * 31}


def test_apply_counts_at_the_cells_size():
    cfg = {**SMALL, "num_particles": 1 << 20, "num_iters": 32}
    work = registry.load_module("counts", "megopolis_apply").count(cfg)
    assert work["bytes"] == 12 * 2**20  # 12 MiB: w in, x in, x out


def test_step_counts_by_hand():
    # log-weights read, state read and written, 4 stats words written;
    # 7 prelude ops per particle, then the sweeps as in apply
    work = registry.load_module("counts", "megopolis_step").count(SMALL)
    assert work == {"bytes": 8192 + 16384 + 16, "ops": 2048 * 7 + 2048 * 4 * 31}


def test_filter_step_counts_by_hand():
    alg6 = registry.load_module("counts", "ungm_filter_step").count(
        {**SMALL, "ess_threshold": None})
    # the state in and out; 9 + 20 + 6 + 1 model ops per particle + the kernel's
    assert alg6 == {"bytes": 2 * 8192, "ops": 2048 * 36 + 2048 * 4 * 31}
    sir = registry.load_module("counts", "ungm_filter_step").count(
        {**SMALL, "ess_threshold": 0.5, "counts": {"kernel": "megopolis_step"}})
    # the state and the log-weights in and out; 9 + 20 + 6 + 6 per particle
    assert sir == {"bytes": 4 * 8192, "ops": 2048 * 41 + 2048 * 7 + 2048 * 4 * 31}


def test_least_seconds_takes_the_larger_bound():
    peak = {"hbm_bytes_per_s": 1e9, "ops_per_s": 1e12}
    assert registry.least_seconds({"bytes": 2e9, "ops": 1e12}, peak) == 2.0
    assert registry.least_seconds({"bytes": 1e6, "ops": 3e12}, peak) == 3.0


def test_peaks_refuse_an_unknown_device_kind():
    import pytest

    assert registry.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="no peaks"):
        registry.peaks("TPU v99")
