"""Faults planted in the program's timed path, for showing that the check
catches them (``tests/test_check.py`` and ``calibrate.py``; the benchmark's
own runs never plant one).

* ``state_unchanged``: the resampler runs, and the step returns the state it
  was given in place of the resampled one;
* ``half_batch``: the estimate is the (weighted) mean over the first half of
  the particles alone;
* ``answer_altered``: the estimate of step 50 of every track is off by
  ``ALTERED_BY`` state units, where it is produced.

The exchange between chips is not a fault these one-chip cells can have.
"""

from __future__ import annotations

import contextlib

FAULTS = ("state_unchanged", "half_batch", "answer_altered")
ALTERED_BY = 0.01


@contextlib.contextmanager
def planted(name):
    """Patch the program so that every filter built inside has ``name``."""
    import jax
    import jax.numpy as jnp

    from repro.core import spec
    from repro.pf import filter as pf_filter

    pf_cls = pf_filter.ParticleFilter
    saved = {(spec.Resampler, "apply"): spec.Resampler.apply,
             (spec.Resampler, "step"): spec.Resampler.step,
             (pf_cls, "step"): pf_cls.step,
             (pf_cls, "step_conditional"): pf_cls.step_conditional}
    apply, step = spec.Resampler.apply, spec.Resampler.step
    pf_step, pf_cond = pf_cls.step, pf_cls.step_conditional

    if name == "state_unchanged":
        def apply_(self, key, w, p):
            return p, apply(self, key, w, p)[1]

        def step_(self, key, lw, p, thr):
            _, anc, stats = step(self, key, lw, p, thr)
            return p, anc, stats

        spec.Resampler.apply, spec.Resampler.step = apply_, step_
    elif name == "half_batch":
        def pf_step_(self, key, particles, z, t, theta=None):
            x_bar, _, w, anc = pf_step(self, key, particles, z, t, theta)
            return x_bar, jnp.mean(x_bar[: x_bar.shape[0] // 2]), w, anc

        def pf_cond_(self, key, particles, log_w, z, t, theta=None):
            x_bar, lw_out, _, stats = pf_cond(self, key, particles, log_w, z, t, theta)
            k_pred, _ = jax.random.split(key)
            x = self.model.transition(k_pred, particles, t)
            lw = log_w + jnp.log(jnp.maximum(self.model.likelihood(z, x, t), 1e-30))
            h = x.shape[0] // 2
            wn = jnp.exp(lw[:h] - jnp.max(lw[:h]))
            return x_bar, lw_out, jnp.sum(wn * x[:h]) / jnp.sum(wn), stats

        pf_cls.step, pf_cls.step_conditional = pf_step_, pf_cond_
    elif name == "answer_altered":
        def pf_step_(self, key, particles, z, t, theta=None):
            x_bar, est, w, anc = pf_step(self, key, particles, z, t, theta)
            return x_bar, jnp.where(t == 50, est + ALTERED_BY, est), w, anc

        def pf_cond_(self, key, particles, log_w, z, t, theta=None):
            x_bar, lw, est, stats = pf_cond(self, key, particles, log_w, z, t, theta)
            return x_bar, lw, jnp.where(t == 50, est + ALTERED_BY, est), stats

        pf_cls.step, pf_cls.step_conditional = pf_step_, pf_cond_
    else:
        raise ValueError(f"unknown fault {name!r}; have {FAULTS}")
    try:
        yield
    finally:
        for (owner, attr), fn in saved.items():
            setattr(owner, attr, fn)


def control_system(config, reference, dtype="bfloat16"):
    """The control: the plain reference computed in ``dtype``, put in the
    program's place behind ``System.run_track`` (whole-track traffic)."""
    import jax
    import jax.numpy as jnp

    import system

    def run_track(base_key, r, pool):
        key = jax.random.fold_in(base_key, r)
        zs = pool[r % pool.shape[0]]
        return reference.filter_tracks(config, key[None], zs[None], jnp.dtype(dtype))[0]

    def unsupported(*_):
        raise NotImplementedError("the control drives whole-track traffic only")

    return system.System(run_track=run_track, start=unsupported, step=unsupported)
