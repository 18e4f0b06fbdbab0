"""The system under test, built from a configuration file.

This is the one module of the benchmark that imports the program
(``repro``, under ``src/``). It builds the configuration's particle filter
through the program's public API and wraps its two entries in the jitted
calls the traffic drives:

* ``run_track(base_key, r, pool) -> f32[T]``: ``run_filter`` over the whole
  of track ``r`` (filter key ``fold_in(base_key, r)``, observations
  ``pool[r mod len(pool)]``);
* ``start(base_key, r) -> state`` and ``step(state, z, t) -> (state, est)``:
  the same track one observation at a time through ``ParticleFilter.step``
  (Alg. 6) or ``ParticleFilter.step_conditional`` (conditional SIR), with
  ``run_filter``'s own key schedule, so both entries filter a track alike.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Callable


@dataclasses.dataclass(frozen=True)
class System:
    run_track: Callable
    start: Callable
    step: Callable


def build(cfg) -> System:
    import jax
    import jax.numpy as jnp

    from repro.core.spec import spec_from_name
    from repro.pf import models
    from repro.pf.filter import ParticleFilter, run_filter

    res = dict(cfg["resampler"])
    spec = spec_from_name(res.pop("family"), num_iters=cfg["num_iters"],
                          segment=cfg["segment"], **res)
    model = getattr(models, cfg["model"])()
    n = cfg["num_particles"]
    pf = ParticleFilter(model, n, resampler=spec, ess_threshold=cfg["ess_threshold"])
    conditional = cfg["ess_threshold"] is not None

    @jax.jit
    def run_track(base_key, r, pool):
        return run_filter(jax.random.fold_in(base_key, r), pf, pool[r % pool.shape[0]])

    @jax.jit
    def start(base_key, r):
        k0, k = jax.random.split(jax.random.fold_in(base_key, r))
        x = model.init(k0, n)
        return (k, x, jnp.zeros((n,), jnp.float32)) if conditional else (k, x)

    @functools.partial(jax.jit, donate_argnums=0)
    def step(state, z, t):
        k, ks = jax.random.split(state[0])
        if conditional:
            x, lw, est, _ = pf.step_conditional(ks, state[1], state[2], z, t)
            return (k, x, lw), est
        x, est, _, _ = pf.step(ks, state[1], z, t)
        return (k, x), est

    return System(run_track=run_track, start=start, step=step)
