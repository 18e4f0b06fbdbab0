"""SIR / bootstrap particle filter (paper Algorithms 1 and 6).

The modified SIR filter (Alg. 6) drops weight normalisation — the
Metropolis-family resamplers only use weight *ratios* — and estimates the
state as the post-resampling particle mean (uniform weights).

Two execution modes:
  * ``run_filter``: fully jitted ``lax.scan`` over time steps (production).
  * ``run_filter_bank``: S independent filters — a SCENARIO axis of
    observation streams, model parameters and keys — under ONE jitted scan
    whose resampling step is a single batched launch (DESIGN.md §4).

Every step names its stages with always-on scopes (``obs/trace.py``,
DESIGN.md §15): ``pf/predict``, ``pf/update``, ``pf/resample`` and
``pf/estimate``; the key splits and the scan stay outside them.  A profile
attributes each device op to its stage by the first ``pf/`` name in its
name stack.

Model callables take ``(key, x, t)``; scenario-parameterised models take a
trailing ``theta`` pytree (``(key, x, t, theta)``), enabling per-scenario
dynamics in the bank (see ``repro.pf.models.ungm_family``).

Particles are ``[N]`` (a scalar state) or ``[N, d]`` (a vector state, e.g.
``repro.pf.models.bearings_only``); every estimate is per component, so a
run's estimates are ``[T]`` or ``[T, d]`` (DESIGN.md §11).
"""

from __future__ import annotations

import dataclasses
import warnings
from typing import Callable, Optional, Union

import jax
import jax.numpy as jnp

from repro.core.metrics import (
    degenerate_weights,
    effective_sample_size,
    log_mean_weight,
    log_weights_from_linear,
    max_normalised_weight,
    normalise_log_weights,
    unique_ancestor_count,
)
from repro.core.resamplers.batched import split_batch_keys
from repro.core.spec import ResamplerSpec, coerce_spec
from repro.obs.stats import StepStats
from repro.obs.telemetry import Telemetry
from repro.obs.trace import span


@dataclasses.dataclass(frozen=True)
class StateSpaceModel:
    transition: Callable  # (key, x[N(, d)], t) -> x[N(, d)]
    observe: Callable  # (key, x[(d)], t) -> z[]    (for ground-truth sim)
    likelihood: Callable  # (z, x[N(, d)], t) -> w[N]  (unnormalised)
    init: Callable  # (key, n) -> x[n(, d)]
    name: str = "model"


@dataclasses.dataclass(frozen=True)
class ParticleFilter:
    """SIR filter config.  ``resampler`` is a registry name or a typed
    ``ResamplerSpec`` (DESIGN.md §9); a spec carries its own hyperparameters
    and backend, so combining one with ``num_iters`` / ``resampler_kwargs``
    raises.  The spec resolves (and validates) eagerly at construction."""

    model: StateSpaceModel
    num_particles: int
    resampler: Union[str, ResamplerSpec] = "megopolis"
    # B for string-named resamplers; None defaults to 30, the fixed
    # application prior of paper §7.  Must stay unset when ``resampler`` is
    # already a spec (the spec carries its own count).
    num_iters: Union[int, str, None] = None
    # None (default) keeps Alg. 6's unconditional per-step resample.  A
    # float in [0, 1] switches the filter to classic conditional SIR: carry
    # log-weights across steps and resample only when the normalised ESS
    # drops below the threshold — one fused ``Resampler.step`` launch per
    # time step on kernel backends (DESIGN.md §12).
    ess_threshold: Optional[float] = None
    resampler_kwargs: tuple = ()  # deprecated: pre-spec hyperparameter channel

    def __post_init__(self):
        if self.ess_threshold is not None and not 0.0 <= self.ess_threshold <= 1.0:
            raise ValueError(
                "ParticleFilter.ess_threshold must be in [0, 1] (a normalised "
                f"ESS fraction) or None for Alg. 6; got {self.ess_threshold}"
            )
        if isinstance(self.resampler, ResamplerSpec):
            if self.resampler_kwargs:
                raise ValueError(
                    "ParticleFilter: pass hyperparameters inside the ResamplerSpec, "
                    "not via the deprecated resampler_kwargs tuple"
                )
            if self.num_iters is not None:
                raise ValueError(
                    "ParticleFilter: num_iters is ignored when resampler is a "
                    "ResamplerSpec — set it inside the spec "
                    "(e.g. MegopolisSpec(num_iters=...))"
                )
            spec = self.resampler
        else:
            if self.resampler_kwargs:
                warnings.warn(
                    "ParticleFilter.resampler_kwargs is deprecated; pass a "
                    "ResamplerSpec as `resampler` instead (e.g. "
                    "MetropolisC1Spec(num_iters=30, partition_size_bytes=128))",
                    DeprecationWarning,
                    stacklevel=3,
                )
            iters = 30 if self.num_iters is None else self.num_iters
            spec = coerce_spec(self.resampler, num_iters=iters)
            spec = spec.replace(**dict(self.resampler_kwargs))
        object.__setattr__(self, "_built", spec.build())

    @property
    def spec(self) -> ResamplerSpec:
        """The resolved resampler spec this filter runs."""
        return self._built.spec

    def step(self, key, particles, z, t, theta=None):
        """One SIR step (Alg. 6): returns
        ``(particles', estimate, weights, ancestors)``.

        Stage 2 runs the FUSED resample+gather path (``Resampler.apply``,
        DESIGN.md §11): on kernel backends the ancestor indices never
        round-trip through HBM — the kernel selects the ancestor and copies
        its state in VMEM; on reference/xla the same call is the classic
        index-then-gather composition, bit-identically.  The ancestors are
        the launch's own int32 output (telemetry composes survivor counts
        from them, DESIGN.md §15); callers that drop them compile the
        pre-telemetry program unchanged."""
        k_pred, k_res = jax.random.split(key)
        # Stage 1: predict + update
        with span("pf/predict"):
            x = _call(self.model.transition, k_pred, particles, t, theta=theta)
        with span("pf/update"):
            w = _call(self.model.likelihood, z, x, t, theta=theta)
        # Stage 2: fused resample + ancestor gather
        with span("pf/resample"):
            x_bar, ancestors = self._built.apply(k_res, w, x)
        # Stage 3: estimate (uniform post-resampling weights)
        with span("pf/estimate"):
            est = jnp.mean(x_bar, axis=0)
        return x_bar, est, w, ancestors

    def step_conditional(self, key, particles, log_w, z, t, theta=None):
        """One conditional-SIR step (classic ESS-triggered SIR, DESIGN.md
        §12): returns ``(particles', log_w', estimate, stats)`` with
        ``stats`` the step's ``StepStats`` record (DESIGN.md §15).

        Log-weights accumulate across steps; stage 2 is the FUSED
        ``Resampler.step`` — normalise, ESS, the resample-or-not branch and
        the state copy in ONE launch on kernel backends.  The estimate is
        the weighted posterior mean over the PRE-resample weights (the
        conditional filter's weights are not uniform after a skipped
        resample, so the Alg. 6 plain mean would be biased)."""
        k_pred, k_res = jax.random.split(key)
        # Stage 1: predict + update (log-weight accumulation)
        with span("pf/predict"):
            x = _call(self.model.transition, k_pred, particles, t, theta=theta)
        with span("pf/update"):
            w = _call(self.model.likelihood, z, x, t, theta=theta)
            log_w = log_w + log_weights_from_linear(w)
        # Stage 3 first: the estimate consumes the pre-resample weights
        with span("pf/estimate"):
            wn = _per_particle(normalise_log_weights(log_w), x)
            est = jnp.sum(wn * x, axis=0) / jnp.sum(wn, axis=0)
        # Stage 2: fused normalise → ESS → conditional resample → gather
        with span("pf/resample"):
            x_bar, _, stats = self._built.step(
                k_res, log_w, x, self.ess_threshold
            )
            log_w = jnp.where(
                stats.ess_norm < self.ess_threshold, jnp.zeros_like(log_w), log_w
            )
        return x_bar, log_w, est, stats


def _per_particle(w, x):
    """Weights ``[..., N]`` shaped to broadcast against particles ``x``
    ``[..., N(, d)]``: unchanged for a scalar state."""
    return w if x.ndim == w.ndim else w.reshape(w.shape + (1,) * (x.ndim - w.ndim))


def _call(fn, *args, theta=None):
    """Invoke a model callable, appending ``theta`` only when given — keeps
    the plain ``(key, x, t)`` model API untouched."""
    return fn(*args) if theta is None else fn(*args, theta)


def simulate(key, model: StateSpaceModel, num_steps: int, theta=None):
    """Ground-truth trajectory ``xs[T(, d)]`` and observations ``zs[T]``,
    starting from one draw of the model's prior."""

    def body(carry, t):
        x, k = carry
        k, k1, k2 = jax.random.split(k, 3)
        x = _call(model.transition, k1, x, t, theta=theta)
        z = _call(model.observe, k2, x, t, theta=theta)
        return (x, k), (x, z)

    k0, key = jax.random.split(key)
    x0 = model.init(k0, 1)[0]
    _, (xs, zs) = jax.lax.scan(body, (x0, key), jnp.arange(1, num_steps + 1, dtype=jnp.float32))
    return xs, zs


def _alg6_step_stats(w: jnp.ndarray, ancestors: jnp.ndarray,
                     axis: int = -1) -> StepStats:
    """Compose the ``StepStats`` record of an UNCONDITIONAL (Alg. 6) step
    from the values the step already produced: the resample always fires
    (``resampled ≡ 1``), so the evidence increment is unconditionally
    ``log_mean_weight``.  Uses the same ``core.metrics`` helpers the fused
    step kernels mirror, so the record means the same thing in both filter
    modes.  Batched inputs (``[S, N]`` weights + ``[S, N]`` ancestors)
    yield batched ``[S]`` records."""
    lw = log_weights_from_linear(w)
    n = w.shape[axis]
    return StepStats(
        ess_norm=effective_sample_size(lw, axis=axis) / jnp.float32(n),
        log_evidence_incr=log_mean_weight(lw, axis=axis),
        resampled=jnp.ones(w.shape[:-1], jnp.float32),
        max_weight=max_normalised_weight(lw, axis=axis),
        survivors=unique_ancestor_count(ancestors, axis=axis),
        degenerate=degenerate_weights(w, axis=axis),
    )


def run_filter(key, pf: ParticleFilter, observations: jnp.ndarray, theta=None,
               telemetry: bool = False, with_ess: bool = False,
               checkpoint=None):
    """Jitted scan over time; returns estimates f32[T], or f32[T, d] for a
    vector state ``[N, d]`` (the mean of each component).

    ``checkpoint`` (a ``repro.resilience.CheckpointPolicy``) makes the run
    crash-consistent: the time scan executes in snapshot-period chunks of
    the SAME jitted body, durably persisting the scan carry + outputs after
    each chunk and resuming from the latest snapshot — estimates and
    telemetry stay bit-identical to the monolithic scan (DESIGN.md §16).

    ``telemetry=True`` additionally returns a ``Telemetry`` record whose
    ``steps`` field holds one ``StepStats`` per time step (every field
    f32/int32[T] — DESIGN.md §15): the resample trigger diagnostics
    (ess_norm, max_weight), the evidence ledger (log_evidence_incr), and
    the degeneracy counters (resampled, survivors).  With the default
    ``pf.ess_threshold=None`` (Alg. 6, unconditional resample) the stats
    are composed from the values the step already computes; with a
    threshold set the filter runs classic conditional SIR
    (``step_conditional``) and the record IS the fused step's own output —
    still one ``Resampler.step`` launch per time step on kernel backends
    (DESIGN.md §12).  Telemetry never changes the computation: same launch
    counts, bit-identical estimates (analyzer pass 6); disabled, it is
    structurally absent from the jaxpr.

    ``with_ess=True`` is the DEPRECATED pre-telemetry diagnostic: it still
    returns the old ``(estimates, ess_norm[T])`` pair (bit-identical to
    ``Telemetry.steps.ess_norm``) with a ``DeprecationWarning``.

    Peak-memory note (DESIGN.md §11): the resample stage is the fused
    ``Resampler.apply`` (or ``Resampler.step``), so the scan body's live
    set at the resample boundary is the in/out particle buffers only — no
    int32 ancestor vector, and (unless telemetry asks for it) no weight
    buffer escapes the step into the scan's stacked outputs.  The
    accounting lives in ``launch/memmodel.py::resample_step_bytes``.
    """
    if with_ess:
        if telemetry:
            raise ValueError(
                "run_filter: pass telemetry=True OR the deprecated "
                "with_ess=True, not both"
            )
        warnings.warn(
            "run_filter(with_ess=True) is deprecated; use telemetry=True and "
            "read Telemetry.steps.ess_norm (DESIGN.md §15)",
            DeprecationWarning,
            stacklevel=2,
        )
    conditional = pf.ess_threshold is not None
    record = telemetry or with_ess

    def body(carry, inp):
        particles, log_w, k = carry
        t, z = inp
        k, ks = jax.random.split(k)
        if conditional:
            particles, log_w, est, stats = pf.step_conditional(
                ks, particles, log_w, z, t, theta=theta
            )
            out = (est, stats) if record else est
            return (particles, log_w, k), out
        particles, est, w, ancestors = pf.step(ks, particles, z, t, theta=theta)
        if not record:
            # Don't thread the pre-resample weight buffer into the scan
            # outputs when nobody consumes it — the diagnostic is opt-in.
            return (particles, log_w, k), est
        return (particles, log_w, k), (est, _alg6_step_stats(w, ancestors))

    k0, key = jax.random.split(key)
    particles = pf.model.init(k0, pf.num_particles)
    log_w0 = jnp.zeros((pf.num_particles,), jnp.float32)
    ts = jnp.arange(1, observations.shape[0] + 1, dtype=jnp.float32)
    if checkpoint is None:
        _, out = jax.lax.scan(body, (particles, log_w0, key), (ts, observations))
    else:
        from repro.resilience.checkpointing import checkpointed_scan

        _, out = checkpointed_scan(
            body, (particles, log_w0, key), (ts, observations), checkpoint
        )
    if not record:
        return out
    ests, steps = out
    if with_ess:
        return ests, steps.ess_norm
    return ests, Telemetry(steps=steps)


def run_filter_bank(key, pf: ParticleFilter, observations: jnp.ndarray, thetas=None,
                    telemetry: bool = False):
    """Run S independent filters in ONE jitted scan; returns estimates
    f32[S, T], or f32[S, T, d] for a vector state.

    ``telemetry=True`` additionally returns a ``Telemetry`` record with one
    ``StepStats`` per scenario per step (every field ``[S, T]``, matching
    the estimate layout); row ``s`` is bit-identical to the single filter's
    record.  Off (the default), the record is structurally absent from the
    jaxpr (DESIGN.md §15).

    The scenario axis (DESIGN.md §4): ``observations`` is ``[S, T]`` — one
    observation stream per scenario; ``thetas`` (optional) is a pytree whose
    leaves carry a leading ``[S]`` axis of per-scenario model parameters.
    ``key`` is split once along the scenario axis (the batched-API key
    contract), so row ``s`` of the result is bit-identical to
    ``run_filter(split(key, S)[s], pf, observations[s], thetas[s])`` — a
    bank is a drop-in replacement for the naive Python loop of S filters,
    at one device launch per pipeline stage instead of S.

    Every stage is batched: predict/update via vmap over the scenario axis,
    resampling via the registry's batched path (one launch over the whole
    ``[S, N]`` weight bank).  With ``pf.ess_threshold`` set the bank runs
    conditional SIR: the resample stage is ONE ``Resampler.step_rows``
    launch and each scenario takes its OWN resample-or-not branch on-chip
    (DESIGN.md §12) — row ``s`` still bit-identical to the single filter.
    """
    num_s = observations.shape[0]
    resampler = pf._built
    conditional = pf.ess_threshold is not None
    keys = split_batch_keys(key, num_s)

    def init_one(k):
        k0, kc = jax.random.split(k)
        return pf.model.init(k0, pf.num_particles), kc

    particles, carry_keys = jax.vmap(init_one)(keys)

    theta_axes = None if thetas is None else jax.tree.map(lambda _: 0, thetas)

    def body(carry, inp):
        xs, log_w, ks = carry  # [S, N] particles/log-weights, [S] key chain
        t, zs = inp  # scalar step, [S] observations
        step = jax.vmap(jax.random.split)(ks)
        ks_next, step_keys = step[:, 0], step[:, 1]
        pr = jax.vmap(jax.random.split)(step_keys)
        k_pred, k_res = pr[:, 0], pr[:, 1]
        # Stage 1 (batched): predict + update
        with span("pf/predict"):
            x = jax.vmap(
                lambda k, xr, th: _call(pf.model.transition, k, xr, t, theta=th),
                in_axes=(0, 0, theta_axes),
            )(k_pred, xs, thetas)
        with span("pf/update"):
            w = jax.vmap(
                lambda z, xr, th: _call(pf.model.likelihood, z, xr, t, theta=th),
                in_axes=(0, 0, theta_axes),
            )(zs, x, thetas)
            if conditional:
                log_w = log_w + log_weights_from_linear(w)
        if conditional:
            # Conditional SIR: accumulate log-weights, estimate from the
            # pre-resample posterior, then ONE fused step_rows launch —
            # stage arithmetic mirrors step_conditional row for row.
            with span("pf/estimate"):
                wn = _per_particle(normalise_log_weights(log_w, axis=-1), x)
                est = jnp.sum(wn * x, axis=1) / jnp.sum(wn, axis=1)
            with span("pf/resample"):
                x_bar, _, stats = resampler.step_rows(
                    k_res, log_w, x, pf.ess_threshold
                )
                log_w = jnp.where(
                    (stats.ess_norm < pf.ess_threshold)[:, None], 0.0, log_w
                )
            out = (est, stats) if telemetry else est
            return (x_bar, log_w, ks_next), out
        # Stage 2: ONE batched FUSED resample+gather launch for the whole
        # bank (Resampler.apply_rows, DESIGN.md §11) — on the batch-grid
        # kernel families this is a single fused launch per step
        with span("pf/resample"):
            x_bar, ancestors = resampler.apply_rows(k_res, w, x)
        # Stage 3 (batched): estimate
        with span("pf/estimate"):
            est = jnp.mean(x_bar, axis=1)
        out = (est, _alg6_step_stats(w, ancestors)) if telemetry else est
        return (x_bar, log_w, ks_next), out

    log_w0 = jnp.zeros((num_s, pf.num_particles), jnp.float32)
    ts = jnp.arange(1, observations.shape[1] + 1, dtype=jnp.float32)
    _, out = jax.lax.scan(body, (particles, log_w0, carry_keys), (ts, observations.T))
    # Scan stacks time first ([T, S(, d)]); swap to the [S, T(, d)] estimate
    # layout so row s is the single filter's trajectory.
    if not telemetry:
        return jnp.swapaxes(out, 0, 1)
    ests, steps = out
    return jnp.swapaxes(ests, 0, 1), Telemetry(steps=jax.tree.map(jnp.transpose, steps))
