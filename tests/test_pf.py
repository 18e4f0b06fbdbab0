"""End-to-end SIR particle filter tests on the paper's UNGM system (§7)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.pf import ParticleFilter, run_filter, ungm
from repro.pf.filter import simulate
from repro.pf.metrics import rmse

T = 50
N_PARTICLES = 4096


@pytest.fixture(scope="module")
def trajectory():
    xs, zs = simulate(jax.random.PRNGKey(1), ungm(), T)
    return np.asarray(xs), np.asarray(zs)


@pytest.mark.parametrize("resampler", ["megopolis", "metropolis", "systematic", "multinomial"])
def test_filter_tracks_ungm(resampler, trajectory):
    xs, zs = trajectory
    pf = ParticleFilter(ungm(), N_PARTICLES, resampler=resampler, num_iters=30)
    ests = run_filter(jax.random.PRNGKey(2), pf, jnp.asarray(zs))
    assert ests.shape == (T,)
    assert np.isfinite(np.asarray(ests)).all()
    err = rmse(np.asarray(ests), xs)
    # The paper's Table 2 RMSE ~ 2.9-3.2 at 2^20 particles over 100 steps;
    # small-scale CPU runs land in the same band.
    assert err < 6.0, f"{resampler}: RMSE {err}"


def test_megopolis_rmse_close_to_unbiased(trajectory):
    """Paper Table 2: Megopolis B=32 RMSE within ~2% of systematic's."""
    xs, zs = trajectory
    runs_m, runs_s = [], []
    for k in range(4):
        key = jax.random.PRNGKey(10 + k)
        pf_m = ParticleFilter(ungm(), N_PARTICLES, resampler="megopolis", num_iters=32)
        pf_s = ParticleFilter(ungm(), N_PARTICLES, resampler="systematic")
        runs_m.append(np.asarray(run_filter(key, pf_m, jnp.asarray(zs))))
        runs_s.append(np.asarray(run_filter(key, pf_s, jnp.asarray(zs))))
    r_m = rmse(np.stack(runs_m), xs)
    r_s = rmse(np.stack(runs_s), xs)
    assert r_m < 1.25 * r_s, (r_m, r_s)


def test_filter_resampler_is_pluggable(trajectory):
    """Every registered resampler must run inside the jitted filter."""
    from repro.core import list_resamplers

    xs, zs = trajectory
    for name in list_resamplers():
        pf = ParticleFilter(ungm(), 1024, resampler=name, num_iters=8)
        ests = run_filter(jax.random.PRNGKey(4), pf, jnp.asarray(zs)[:5])
        assert np.isfinite(np.asarray(ests)).all(), name
