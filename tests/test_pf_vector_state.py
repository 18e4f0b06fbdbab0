"""The particle filter on a vector state ``[N, d]``: Gordon, Salmond and
Smith's 4-D bearings-only tracker through ``run_filter`` and its siblings,
against the benchmark's plain reference, and the scalar UNGM filter's
estimates unchanged by the vector-state estimate."""

import importlib.util
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.spec import MegopolisSpec
from repro.pf import ParticleFilter, run_filter, run_filter_bank, ungm
from repro.pf.filter import simulate
from repro.pf.models import bearings_only

N = 4096
T = 12
ITERS = 32
SEG = 1024
BENCH = Path(__file__).resolve().parents[1] / "bench"
CFG = {"num_particles": N, "num_iters": ITERS, "segment": SEG, "sigma_q": 0.001,
       "sigma_r": 0.005, "prior_mean": [0.0, 0.0, 0.4, -0.05],
       "prior_std": [0.5, 0.005, 0.3, 0.01],
       "true_initial_state": [-0.05, 0.001, 0.7, -0.055]}


@pytest.fixture(scope="module")
def reference():
    """``bench/reference/bearings_filter.py``; it loads its helpers through
    the benchmark's ``registry``, so ``bench/`` is on the path while it
    imports."""
    sys.path.insert(0, str(BENCH))
    try:
        spec = importlib.util.spec_from_file_location(
            "bearings_filter_reference", BENCH / "reference" / "bearings_filter.py")
        module = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(module)
    finally:
        sys.path.remove(str(BENCH))
    return module


@pytest.fixture(scope="module")
def tracks(reference):
    """Two tracks: filter keys and the reference's simulated bearings."""
    _, zs = reference.simulate(CFG, jax.random.split(jax.random.PRNGKey(5), 2), T)
    return jax.random.split(jax.random.PRNGKey(9), 2), zs


def _pf(backend, **kw):
    return ParticleFilter(bearings_only(), N, resampler=MegopolisSpec(
        num_iters=ITERS, segment=SEG, backend=backend), **kw)


def _program(pf, keys, zs):
    run = jax.jit(lambda k, z: run_filter(k, pf, z))
    return np.stack([np.asarray(run(k, z)) for k, z in zip(keys, zs)])


def test_bearings_defaults_are_the_sources_values():
    """Gordon, Salmond and Smith 1993, sec. 4; the benchmark's reference
    reads the same values from its configuration file."""
    from repro.pf import models

    assert models._SIGMA_Q == CFG["sigma_q"]
    assert models._SIGMA_R == CFG["sigma_r"]
    assert list(models._PRIOR_MEAN) == CFG["prior_mean"]
    assert list(models._PRIOR_STD) == CFG["prior_std"]


def test_pallas_filter_matches_reference(reference, tracks):
    """The kernel backend runs the reference's operations on the same
    random streams; only the estimate's sum differs in order (``[N, 4]``
    against ``[4, N]``): 4096 terms below 2 in magnitude, so a few f32 ulps
    of the mean. Observed under 1e-6; the tolerance is 1e-5."""
    keys, zs = tracks
    got = _program(_pf("pallas_interpret"), keys, zs)
    want = np.asarray(reference.filter_tracks(CFG, keys, zs))
    assert got.shape == want.shape == (2, T, 4)
    np.testing.assert_allclose(got, want, rtol=0, atol=1e-5)


# Monte Carlo error of the resampler at N = 4096: the reference backend
# draws its accept/reject uniforms from ``jax.random.uniform`` and not from
# the kernels' counter hash, so it and the reference share the prior and
# the process noise and differ in which particles survive. Over 4 seeds of
# 8 tracks the RMS gap per component (x, vx, y, vy) was at most
# (0.005, 0.0007, 0.016, 0.003); the tolerance is four times that.
MC_RMS_TOL = np.array([0.02, 0.003, 0.064, 0.012])


def test_reference_backend_filter_agrees_with_reference(reference, tracks):
    keys, zs = tracks
    got = _program(_pf("reference"), keys, zs)
    want = np.asarray(reference.filter_tracks(CFG, keys, zs))
    rms = np.sqrt(np.mean((got - want) ** 2, axis=(0, 1)))
    assert (rms < MC_RMS_TOL).all(), rms
    assert not np.array_equal(got, want)  # another stream, not the same one


def test_vector_estimates_are_per_component(tracks):
    keys, zs = tracks
    pf = _pf("reference")
    ests, tel = jax.jit(lambda k, z: run_filter(k, pf, z, telemetry=True))(keys[0], zs[0])
    assert ests.shape == (T, 4) and ests.dtype == jnp.float32
    assert np.isfinite(np.asarray(ests)).all()
    assert tel.steps.ess_norm.shape == (T,)


def test_simulate_returns_vector_states():
    xs, zs = simulate(jax.random.PRNGKey(0), bearings_only(), T)
    assert xs.shape == (T, 4) and zs.shape == (T,)
    # the observations are the bearings of the states, to a few sigma_r
    resid = np.angle(np.exp(1j * (np.asarray(zs) - np.arctan2(xs[:, 2], xs[:, 0]))))
    assert np.abs(resid).max() < 5 * 0.005


def test_bearing_residual_wraps_across_pi():
    """A bearing just below pi and a particle just above -pi are 0.002 rad
    apart, not 2 pi - 0.002."""
    model = bearings_only()
    x = jnp.array([[-1.0, 0.0, -0.001, 0.0]], jnp.float32)  # bearing -pi + 0.001
    w = model.likelihood(jnp.float32(np.pi - 0.001), x, 1.0)
    assert float(w[0]) == pytest.approx(np.exp(-0.5 * 0.002**2 / 0.005**2), rel=1e-3)


def test_step_conditional_weighted_mean_per_component():
    pf = _pf("reference", ess_threshold=0.5)
    model = pf.model
    key, kx, kw = jax.random.split(jax.random.PRNGKey(3), 3)
    particles = model.init(kx, N)
    log_w = 0.1 * jax.random.normal(kw, (N,))
    z, t = jnp.float32(0.5), jnp.float32(1.0)
    x_bar, lw, est, stats = pf.step_conditional(key, particles, log_w, z, t)
    assert x_bar.shape == (N, 4) and lw.shape == (N,) and est.shape == (4,)
    # the estimate is the weighted mean, per component, of the moved
    # particles under the pre-resample weights
    k_pred, _ = jax.random.split(key)
    x = np.asarray(model.transition(k_pred, particles, t), np.float64)
    lw_pre = np.asarray(log_w, np.float64) + np.log(
        np.maximum(np.asarray(model.likelihood(z, jnp.asarray(x, jnp.float32), t)), 1e-30))
    wn = np.exp(lw_pre - lw_pre.max())
    want = (wn[:, None] * x).sum(axis=0) / wn.sum()
    np.testing.assert_allclose(np.asarray(est), want, rtol=1e-4, atol=1e-6)


@pytest.mark.parametrize("ess_threshold", [None, 0.5], ids=["alg6", "conditional"])
def test_bank_rows_are_single_vector_filters(ess_threshold, tracks):
    """Row s of a bank on a vector state is ``run_filter`` on row s's key,
    with the ``[S, T, d]`` layout. The estimate sums the same 4096 terms
    per component, over axis 1 of
    ``[S, N, 4]`` in the bank and axis 0 of ``[N, 4]`` alone, whose orders
    may differ by a few f32 ulps (observed 3e-7)."""
    key = jax.random.PRNGKey(21)
    _, zs = tracks
    pf = _pf("pallas_interpret", ess_threshold=ess_threshold)
    bank = np.asarray(jax.jit(lambda k, z: run_filter_bank(k, pf, z))(key, zs))
    assert bank.shape == (2, T, 4)
    keys = jax.random.split(key, 2)
    np.testing.assert_allclose(bank, _program(pf, keys, zs), rtol=0, atol=2e-6)


def _parent_run_filter(key, pf, zs):
    """The scalar filter's scan as it was written before vector states: the
    estimate is ``mean(x_bar)`` (Alg. 6) or ``sum(wn * x) / sum(wn)``."""
    from repro.core.metrics import log_weights_from_linear, normalise_log_weights

    model, r = pf.model, pf._built

    def body(carry, inp):
        x, log_w, k = carry
        t, z = inp
        k, ks = jax.random.split(k)
        k_pred, k_res = jax.random.split(ks)
        x = model.transition(k_pred, x, t)
        w = model.likelihood(z, x, t)
        if pf.ess_threshold is None:
            x_bar, _ = r.apply(k_res, w, x)
            return (x_bar, log_w, k), jnp.mean(x_bar)
        log_w = log_w + log_weights_from_linear(w)
        wn = normalise_log_weights(log_w)
        est = jnp.sum(wn * x) / jnp.sum(wn)
        x_bar, _, stats = r.step(k_res, log_w, x, pf.ess_threshold)
        log_w = jnp.where(stats.ess_norm < pf.ess_threshold, jnp.zeros_like(log_w), log_w)
        return (x_bar, log_w, k), est

    k0, key = jax.random.split(key)
    x0 = model.init(k0, pf.num_particles)
    ts = jnp.arange(1, zs.shape[0] + 1, dtype=jnp.float32)
    return jax.lax.scan(body, (x0, jnp.zeros_like(x0), key), (ts, zs))[1]


@pytest.mark.parametrize("ess_threshold", [None, 0.5], ids=["alg6", "conditional"])
def test_scalar_ungm_estimates_unchanged(ess_threshold):
    """The UNGM filter's ``[T]`` estimates are bit for bit those of the
    scalar-only estimate, on the kernel backend."""
    _, zs = simulate(jax.random.PRNGKey(1), ungm(), T)
    pf = ParticleFilter(ungm(), N, ess_threshold=ess_threshold, resampler=MegopolisSpec(
        num_iters=ITERS, segment=SEG, backend="pallas_interpret"))
    key = jax.random.PRNGKey(2)
    got = jax.jit(lambda k, z: run_filter(k, pf, z))(key, zs)
    want = jax.jit(lambda k, z: _parent_run_filter(k, pf, z))(key, zs)
    assert got.shape == (T,)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_state_planes_are_scoped():
    """The plane pack and unpack around the kernel carry the
    ``resample/planes`` scope in the lowered program."""
    r = MegopolisSpec(num_iters=4, segment=SEG, backend="pallas_interpret").build()
    w = jnp.ones((N,), jnp.float32)
    x = jnp.zeros((N, 4), jnp.float32)
    text = jax.jit(r.apply).lower(jax.random.PRNGKey(0), w, x).as_text(debug_info=True)
    assert "resample/planes" in text
