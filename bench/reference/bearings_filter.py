"""Plain reference of the bearings-only tracking filter, in jax.numpy alone.

The second example of Gordon, Salmond and Smith, "Novel approach to
nonlinear/non-Gaussian Bayesian state estimation", IEE Proc. F 140(2),
1993: a target with state (x, vx, y, vy) moves at near-constant velocity,

    x_t = Phi x_{t-1} + Gamma w_t,   w ~ N(0, sigma_q^2 I_2),
    Phi = [[1,1,0,0],[0,1,0,0],[0,0,1,1],[0,0,0,1]],
    Gamma = [[0.5,0],[1,0],[0,0.5],[0,1]],

and an observer at the origin measures its bearing,
``z_t = atan2(y_t, x_t) + v_t``, ``v ~ N(0, sigma_r^2)``. The filter is
the bootstrap filter with Megopolis resampling every step (Alg. 6): the
weight of a particle is ``exp(-0.5 wrap(z - atan2(y, x))^2 / sigma_r^2)``,
the residual wrapped to (-pi, pi] and not shifted by its maximum (weights
that underflow to 0 stay 0), and the estimate is the mean of each component
of the resampled particles. ``Phi x + Gamma w`` is written out per
component, as the program writes it.

Nothing here imports the code under test. The random streams are the
filter's documented ones, the same schedule as ``ungm_filter.py`` (whose
hash and rotation helpers this module loads):

* per track ``k0, key = split(key)``; the prior is ``normal(k0, [N, 4]) *
  prior_std + prior_mean``; per step ``key, ks = split(key)`` and
  ``k_pred, k_res = split(ks)``; the process noise is
  ``normal(k_pred, [N, 2]) * sigma_q``;
* Megopolis as in ``ungm_filter.py``, the four state components carried by
  value as a ``[4, N]`` stack, taken with the same two whole-array
  rotations per iteration as the comparison weight.

A trajectory starts at ``true_initial_state`` and evolves with the process
noise (per step ``k, k1, k2 = split(k, 3)``: ``normal(k1, [2])`` the
process noise, ``normal(k2, [])`` the bearing noise). Every array of the
filter is held in ``dtype``: float32 for the reference, a lower precision
for the control.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

import registry

_ungm = registry.load_module("reference", "ungm_filter")


def _rotate(v, o, segment):
    """``out[:, i] = v[:, j(i, o)]`` for a ``[d, N]`` stack: the Megopolis
    index map of ``ungm_filter._rotate`` applied to every row."""
    d = v.shape[0]
    v3 = v.reshape(d, -1, segment)
    v3 = jnp.roll(v3, -(o // segment), axis=1)
    return jnp.roll(v3, -(o % segment), axis=2).reshape(d, -1)


def megopolis(key, w, x, num_iters, segment):
    """Resampled particles ``x'[:, i] = x[:, k_i]`` (Alg. 5) for weights
    ``w[N]`` and a state stack ``x[d, N]``."""
    n = w.shape[0]
    k_off, k_seed = jax.random.split(key)
    offsets = jax.random.randint(k_off, (num_iters,), 0, n, dtype=jnp.int32)
    seed = _ungm._seed(k_seed)
    i = jnp.arange(n, dtype=jnp.int32)

    def body(b, carry):
        wk, xk = carry
        o = offsets[b]
        w_j = _ungm._rotate(w, o, segment)
        accept = _ungm._uniform(seed, i, b, w.dtype) * wk <= w_j
        return (jnp.where(accept, w_j, wk),
                jnp.where(accept[None], _rotate(x, o, segment), xk))

    _, x_new = jax.lax.fori_loop(0, num_iters, body, (w, x))
    return x_new


def _move(x, w):
    """``Phi x + Gamma w`` for a state stack ``x[4, ...]`` and noise
    ``w[2, ...]`` (already scaled by ``sigma_q``)."""
    px, vx, py, vy = x[0], x[1], x[2], x[3]
    return jnp.stack([px + vx + 0.5 * w[0], vx + w[0], py + vy + 0.5 * w[1], vy + w[1]])


def _bearing(x):
    return jnp.arctan2(x[2], x[0])


def _likelihood(cfg, z, x):
    resid = z - _bearing(x)
    resid = jnp.pi - jnp.mod(jnp.pi - resid, 2.0 * jnp.pi)
    return jnp.exp(-0.5 * resid**2 / cfg["sigma_r"] ** 2)


def _track(cfg, key, zs, dtype):
    n = cfg["num_particles"]
    mean = jnp.asarray(cfg["prior_mean"], jnp.float32).astype(dtype)
    std = jnp.asarray(cfg["prior_std"], jnp.float32).astype(dtype)
    k0, key = jax.random.split(key)
    x = (jax.random.normal(k0, (n, 4), dtype) * std + mean).T  # [4, N]

    def body(carry, z):
        x, key = carry
        key, ks = jax.random.split(key)
        k_pred, k_res = jax.random.split(ks)
        noise = jax.random.normal(k_pred, (n, 2), dtype) * cfg["sigma_q"]
        x = _move(x, noise.T)
        w = _likelihood(cfg, z, x)
        x = megopolis(k_res, w, x, cfg["num_iters"], cfg["segment"])
        return (x, key), jnp.mean(x, axis=1)

    _, est = jax.lax.scan(body, (x, key), zs.astype(dtype))
    return est


@functools.partial(jax.jit, static_argnames=("cfg_items", "dtype"))
def _tracks(keys, zs, cfg_items, dtype):
    cfg = dict(cfg_items)
    # one track after another: under vmap the rotations' per-track shifts
    # would turn into gathers
    return jax.lax.map(lambda kz: _track(cfg, kz[0], kz[1], dtype), (keys, zs)).astype(jnp.float32)


def filter_tracks(cfg, keys, zs, dtype=jnp.float32):
    """Estimates ``f32[K, T, 4]`` of K tracks: ``keys[k]`` is track k's
    filter key, ``zs[k]`` its observations."""
    with jax.default_matmul_precision("highest"):
        return _tracks(keys, zs, _items(cfg), jnp.dtype(dtype))


def _items(cfg):
    keys = ("num_particles", "num_iters", "segment", "sigma_q", "sigma_r", "prior_mean",
            "prior_std", "true_initial_state")
    return tuple((k, tuple(cfg[k]) if isinstance(cfg[k], list) else cfg[k]) for k in keys)


@functools.partial(jax.jit, static_argnames=("cfg_items", "steps"))
def _simulate(keys, cfg_items, steps):
    cfg = dict(cfg_items)

    def one(key):
        def body(carry, _):
            x, k = carry
            k, k1, k2 = jax.random.split(k, 3)
            x = _move(x, jax.random.normal(k1, (2,)) * cfg["sigma_q"])
            z = _bearing(x) + jax.random.normal(k2, ()) * cfg["sigma_r"]
            return (x, k), (x, z)

        x0 = jnp.asarray(cfg["true_initial_state"], jnp.float32)
        _, (xs, zs) = jax.lax.scan(body, (x0, key), None, length=steps)
        return xs, zs

    return jax.vmap(one)(keys)


def simulate(cfg, keys, steps):
    """Ground truth ``(xs f32[K, steps, 4], zs f32[K, steps])``, one
    trajectory per key: the observations the filter is fed."""
    return _simulate(keys, _items(cfg), steps)
