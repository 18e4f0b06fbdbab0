"""Plain reference of the UNGM particle filter, in jax.numpy alone.

The univariate nonlinear growth model of arXiv:2109.13504 §7 (eqs. 22-23):

    x_t = x_{t-1}/2 + 25 x_{t-1} / (1 + x_{t-1}^2) + amp cos(freq t) + v,
    z_t = x_t^2 / 20 + n,        v ~ N(0, sigma_v2),  n ~ N(0, sigma_n2),

filtered by the bootstrap SIR filter with Megopolis resampling (Alg. 5),
either unconditionally every step (Alg. 6: estimate = mean of the resampled
particles) or when the normalised ESS falls below a threshold (classic
conditional SIR: log-weights carried across steps, estimate = the weighted
mean before resampling, log-weights reset after a resample).

Nothing here imports the code under test. The random streams are the
filter's documented ones, written out again so that a replay with the same
key draws the same numbers:

* per track ``k0, key = split(key)``; the prior is ``normal(k0, [N]) *
  sqrt(init_var)``; per step ``key, ks = split(key)`` and
  ``k_pred, k_res = split(ks)``; the process noise is ``normal(k_pred)``;
* Megopolis: ``k_off, k_seed = split(k_res)``; the B offsets are
  ``randint(k_off, [B], 0, N)``; the uniform of particle i at iteration b is
  the 24-bit murmur3 counter hash of ``(seed(k_seed), i, b)``; particle i
  compares with ``j = align(i) + align(o_b) + (i + o_b) mod S`` (mod N),
  S = the coalescing segment, and takes j when ``u * w[k] <= w[j]``.

The comparison weight and the ancestor's state are carried by value and
taken with two whole-array rotations per iteration; that is the same
``particles'[i] = particles[k_i]`` without a gather. Every array is held in
``dtype``: float32 for the reference, a lower precision for the control.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

_GOLDEN = np.uint32(0x9E3779B9)


def _fmix(x):
    """murmur3's 32-bit finaliser."""
    x = x ^ (x >> np.uint32(16))
    x = x * np.uint32(0x85EBCA6B)
    x = x ^ (x >> np.uint32(13))
    x = x * np.uint32(0xC2B2AE35)
    return x ^ (x >> np.uint32(16))


def _seed(key):
    data = jax.random.key_data(key).astype(jnp.uint32)
    return _fmix(data[0] ^ (data[1] * _GOLDEN))


def _uniform(seed, i, b, dtype):
    s = seed + b.astype(jnp.uint32) * _GOLDEN
    bits = _fmix(_fmix(s) ^ (i.astype(jnp.uint32) * _GOLDEN))
    return ((bits >> np.uint32(8)).astype(jnp.int32).astype(jnp.float32)
            * np.float32(1.0 / (1 << 24))).astype(dtype)


def _rotate(v, o, segment):
    """``out[i] = v[j(i, o)]`` for the Megopolis index map: whole segments
    rotate by ``o // segment``, lanes inside a segment by ``o mod segment``."""
    v2 = v.reshape(-1, segment)
    v2 = jnp.roll(v2, -(o // segment), axis=0)
    return jnp.roll(v2, -(o % segment), axis=1).reshape(-1)


def megopolis(key, w, x, num_iters, segment):
    """Resampled particles ``x'[i] = x[k_i]`` (Alg. 5) for weights ``w``."""
    n = w.shape[0]
    k_off, k_seed = jax.random.split(key)
    offsets = jax.random.randint(k_off, (num_iters,), 0, n, dtype=jnp.int32)
    seed = _seed(k_seed)
    i = jnp.arange(n, dtype=jnp.int32)

    def body(b, carry):
        wk, xk = carry
        o = offsets[b]
        w_j = _rotate(w, o, segment)
        accept = _uniform(seed, i, b, w.dtype) * wk <= w_j
        return jnp.where(accept, w_j, wk), jnp.where(accept, _rotate(x, o, segment), xk)

    _, x_new = jax.lax.fori_loop(0, num_iters, body, (w, x))
    return x_new


def _transition(cfg, key, x, t):
    v = jax.random.normal(key, x.shape, x.dtype) * jnp.sqrt(jnp.asarray(cfg["sigma_v2"], x.dtype))
    return (x / 2.0 + 25.0 * x / (1.0 + x**2)
            + cfg["forcing_amp"] * jnp.cos(cfg["forcing_freq"] * t) + v)


def _likelihood(cfg, z, x):
    resid = z - x**2 / 20.0
    return jnp.exp(-0.5 * resid**2 / cfg["sigma_n2"])


def _track(cfg, key, zs, dtype):
    n = cfg["num_particles"]
    thr = cfg["ess_threshold"]
    k0, key = jax.random.split(key)
    x = jax.random.normal(k0, (n,), dtype) * jnp.sqrt(jnp.asarray(cfg["init_var"], dtype))
    ts = jnp.arange(1, zs.shape[0] + 1, dtype=jnp.float32).astype(dtype)

    def body(carry, inp):
        x, lw, key = carry
        t, z = inp
        key, ks = jax.random.split(key)
        k_pred, k_res = jax.random.split(ks)
        x = _transition(cfg, k_pred, x, t)
        w = _likelihood(cfg, z, x)
        resample = functools.partial(megopolis, k_res, num_iters=cfg["num_iters"],
                                     segment=cfg["segment"])
        if thr is None:
            x = resample(w, x)
            return (x, lw, key), jnp.mean(x)
        lw = lw + jnp.log(jnp.maximum(w, jnp.asarray(1e-30, dtype)))
        wn = jnp.exp(lw - jnp.max(lw))
        s1 = jnp.sum(wn)
        est = jnp.sum(wn * x) / s1
        ess_norm = s1 * s1 / jnp.maximum(jnp.sum(wn * wn), 1e-30) / n
        do = ess_norm < thr
        x = jnp.where(do, resample(wn, x), x)
        lw = jnp.where(do, jnp.zeros_like(lw), lw)
        return (x, lw, key), est

    _, est = jax.lax.scan(body, (x, jnp.zeros((n,), dtype), key), (ts, zs.astype(dtype)))
    return est


@functools.partial(jax.jit, static_argnames=("cfg_items", "dtype"))
def _tracks(keys, zs, cfg_items, dtype):
    cfg = dict(cfg_items)
    # one track after another: under vmap the rotations' per-track shifts
    # would turn into gathers
    return jax.lax.map(lambda kz: _track(cfg, kz[0], kz[1], dtype), (keys, zs)).astype(jnp.float32)


def filter_tracks(cfg, keys, zs, dtype=jnp.float32):
    """Estimates ``f32[K, T]`` of K tracks: ``keys[k]`` is track k's filter
    key, ``zs[k]`` its observations."""
    with jax.default_matmul_precision("highest"):
        return _tracks(keys, zs, _items(cfg), jnp.dtype(dtype))


def _items(cfg):
    keys = ("num_particles", "num_iters", "segment", "ess_threshold", "sigma_v2",
            "sigma_n2", "init_var", "forcing_amp", "forcing_freq")
    return tuple((k, cfg[k]) for k in keys)


@functools.partial(jax.jit, static_argnames=("cfg_items", "steps"))
def _simulate(keys, cfg_items, steps):
    cfg = dict(cfg_items)

    def one(key):
        k0, key = jax.random.split(key)
        x0 = jax.random.normal(k0, ()) * jnp.sqrt(jnp.float32(cfg["init_var"]))

        def body(carry, t):
            x, k = carry
            k, k1, k2 = jax.random.split(k, 3)
            x = _transition(cfg, k1, x, t)
            z = x**2 / 20.0 + jax.random.normal(k2, ()) * jnp.sqrt(jnp.float32(cfg["sigma_n2"]))
            return (x, k), (x, z)

        _, (xs, zs) = jax.lax.scan(body, (x0, key), jnp.arange(1, steps + 1, dtype=jnp.float32))
        return xs, zs

    return jax.vmap(one)(keys)


def simulate(cfg, keys, steps):
    """Ground truth ``(xs, zs)``, each ``f32[K, steps]``, one trajectory of
    the model per key: the observations the filter is fed."""
    return _simulate(keys, _items(cfg), steps)
