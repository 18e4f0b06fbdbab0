"""Benchmark state-space models.

``ungm`` is the univariate nonlinear growth model of the paper's §7
(eqs. 22-23; Gordon/Kitagawa/Arulampalam standard):

    x_t = x_{t-1}/2 + 25 x_{t-1} / (1 + x_{t-1}^2) + 8 cos(1.2 t) + v,
    z_t = x_t^2 / 20 + n,            v ~ N(0, 10),  n ~ N(0, 1).

``bearings_only`` is the 4-D bearings-only tracker of the bootstrap
filter's own paper (Gordon, Salmond & Smith 1993, IEE Proc. F 140(2)):
a vector state ``[N, 4]``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.pf.filter import StateSpaceModel

_SIGMA_V2 = 10.0  # process-noise variance (paper: sigma_v^2 = 10)
_SIGMA_N2 = 1.0  # measurement-noise variance (paper: sigma_n^2 = 1)


def _transition(key, x, t):
    v = jax.random.normal(key, x.shape, x.dtype) * jnp.sqrt(_SIGMA_V2)
    return x / 2.0 + 25.0 * x / (1.0 + x**2) + 8.0 * jnp.cos(1.2 * t) + v


def _observe(key, x, t):
    n = jax.random.normal(key, x.shape, x.dtype) * jnp.sqrt(_SIGMA_N2)
    return x**2 / 20.0 + n


def _likelihood(z, x, t):
    # p(z | x) up to a constant; normalisation is irrelevant to resampling
    # (the Metropolis family explicitly tolerates unnormalised weights).
    resid = z - x**2 / 20.0
    return jnp.exp(-0.5 * resid**2 / _SIGMA_N2)


def _init(key, n):
    return jax.random.normal(key, (n,)) * jnp.sqrt(_SIGMA_V2)


def ungm() -> StateSpaceModel:
    return StateSpaceModel(
        transition=_transition,
        observe=_observe,
        likelihood=_likelihood,
        init=_init,
        name="ungm",
    )


# ---------------------------------------------------------------- scenarios
def ungm_theta(amp: float = 8.0, obs_var: float = _SIGMA_N2) -> dict:
    """One scenario's parameters for ``ungm_family``: forcing amplitude
    (the paper's fixed 8 cos(1.2 t) term) and measurement-noise variance."""
    return {"amp": jnp.float32(amp), "obs_var": jnp.float32(obs_var)}


def _transition_theta(key, x, t, theta):
    v = jax.random.normal(key, x.shape, x.dtype) * jnp.sqrt(_SIGMA_V2)
    return x / 2.0 + 25.0 * x / (1.0 + x**2) + theta["amp"] * jnp.cos(1.2 * t) + v


def _observe_theta(key, x, t, theta):
    n = jax.random.normal(key, x.shape, x.dtype) * jnp.sqrt(theta["obs_var"])
    return x**2 / 20.0 + n


def _likelihood_theta(z, x, t, theta):
    resid = z - x**2 / 20.0
    return jnp.exp(-0.5 * resid**2 / theta["obs_var"])


def ungm_family() -> StateSpaceModel:
    """UNGM with per-scenario parameters (trailing ``theta`` pytree arg) —
    the scenario-axis model for ``run_filter_bank``: one bank runs S
    differently-forced / differently-noised UNGM instances at once.
    ``theta == ungm_theta()`` reproduces ``ungm`` exactly."""
    return StateSpaceModel(
        transition=_transition_theta,
        observe=_observe_theta,
        likelihood=_likelihood_theta,
        init=_init,
        name="ungm-family",
    )


# ------------------------------------------------------------- bearings-only
_SIGMA_Q = 0.001  # process-noise std of each acceleration (paper: sqrt(q))
_SIGMA_R = 0.005  # bearing-noise std (paper: sqrt(r))
_PRIOR_MEAN = (0.0, 0.0, 0.4, -0.05)  # (x, vx, y, vy)
_PRIOR_STD = (0.5, 0.005, 0.3, 0.01)


def _wrap(a):
    """An angle wrapped to (-pi, pi]."""
    return jnp.pi - jnp.mod(jnp.pi - a, 2.0 * jnp.pi)


def bearings_only() -> StateSpaceModel:
    """Bearings-only tracking (Gordon, Salmond & Smith 1993, sec. 4), with
    the paper's values.  State ``x = (x, vx, y, vy)``:

        x_t = Phi x_{t-1} + Gamma w_t,   w ~ N(0, sigma_q^2 I_2),
        z_t = atan2(y_t, x_t) + v_t,     v ~ N(0, sigma_r^2),

    with Phi the constant-velocity map (position += velocity) and Gamma =
    [[0.5, 0], [1, 0], [0, 0.5], [0, 1]].  The map is written out per
    component (a TPU matmul at default precision would round through
    bfloat16).  The likelihood wraps the bearing residual to (-pi, pi]; far
    from the bearing it underflows to 0, which the Megopolis ratio test
    takes as it is.  Particles are ``[N, 4]``; ``transition`` and
    ``observe`` also take one state ``[4]``."""
    r = _SIGMA_R**2
    mean = jnp.asarray(_PRIOR_MEAN, jnp.float32)
    std = jnp.asarray(_PRIOR_STD, jnp.float32)

    def transition(key, x, t):
        w = jax.random.normal(key, x.shape[:-1] + (2,), x.dtype) * _SIGMA_Q
        px, vx, py, vy = (x[..., i] for i in range(4))
        wx, wy = w[..., 0], w[..., 1]
        return jnp.stack(
            [px + vx + 0.5 * wx, vx + wx, py + vy + 0.5 * wy, vy + wy], axis=-1
        )

    def bearing(x):
        return jnp.arctan2(x[..., 2], x[..., 0])

    def observe(key, x, t):
        return bearing(x) + jax.random.normal(key, x.shape[:-1], x.dtype) * _SIGMA_R

    def likelihood(z, x, t):
        resid = _wrap(z - bearing(x))
        return jnp.exp(-0.5 * resid**2 / r)

    def init(key, n):
        return jax.random.normal(key, (n, 4)) * std + mean

    return StateSpaceModel(
        transition=transition,
        observe=observe,
        likelihood=likelihood,
        init=init,
        name="bearings-only",
    )
