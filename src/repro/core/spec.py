"""Typed resampler specs — one object per family, one build surface (DESIGN.md §9).

The paper's headline claim is that Megopolis needs *no tuning parameter*
beyond the eq. (3) iteration count, yet the pre-spec API forced every call
site to hand-thread ``num_iters`` and per-algorithm kwargs.  A
``ResamplerSpec`` is the typed replacement: a frozen, hashable dataclass —
one per algorithm family — that carries every hyperparameter the family
has, validates it EAGERLY (bad segment / backend / kind errors at
construction, not at trace time), and builds a uniform callable::

    spec = MegopolisSpec(num_iters=24, segment=32)
    r = spec.build()            # -> Resampler
    anc  = r(key, weights)      # int32[N]      (single population)
    bank = r.batch(key, w_bank) # int32[B, N]   (weights[B, N], split-key rows)

Properties:

  * **Static-safe.**  Specs are registered as static pytree nodes
    (``jax.tree_util.register_static``): hashable, usable as ``jit`` static
    arguments, storable inside other frozen configs (``ParticleFilter``,
    ``SMCDecodeConfig``), and ``jax.tree`` round-trips return the same
    object.
  * **Sweepable.**  ``spec.replace(partition_size_bytes=2048)`` returns a
    validated variant — benchmark sweeps are spec transformations.
  * **No tuning parameter.**  ``num_iters='auto'`` (the Metropolis-family
    default) routes through ``select_iterations`` (paper eq. 3) at call
    time, so the no-tuning story is first-class: ``MegopolisSpec().build()``
    resamples any weight vector without the caller ever choosing ``B``.
  * **Backend dispatch.**  ``backend='reference' | 'xla' | 'pallas_interpret'
    | 'pallas'`` selects the execution surface in the spec: ``reference``
    is the pure-jnp algorithm, ``xla`` the same jit-wrapped, and the
    ``pallas*`` pair the TPU kernel (interpret mode validates on CPU).
    EVERY family builds on every backend — the kernel matrix is complete
    (Megopolis, Metropolis, C1/C2, rejection, and all five prefix-sum
    kinds); kernels whose geometry is tile-fixed require the matching
    spec fields (``segment=1024`` for Megopolis, ``partition_size_bytes=
    4096`` for C1/C2) so the coalescing contract stays explicit.

``spec_from_name(name, **kw)`` maps the 10 registry names onto spec
instances (with a difflib nearest-match hint on unknown names);
``get_resampler`` / ``get_resampler_batch`` remain as thin legacy shims
over the same family table.
"""

from __future__ import annotations

import dataclasses
import difflib
from typing import Any, Callable, ClassVar, Tuple, Union

import jax
import jax.numpy as jnp

from repro.core.iterations import select_iterations
from repro.core.metrics import (
    degenerate_log_weights,
    degenerate_weights,
    effective_sample_size,
    log_mean_weight,
    max_normalised_weight,
    normalise_log_weights,
    unique_ancestor_count,
)
from repro.obs.stats import stats_from_vector
from repro.obs.trace import dispatch_span
from repro.resilience.guards import check_guard_policy, maybe_emit_guard_event
from repro.core.resamplers.batched import split_batch_keys
from repro.core.resamplers.megopolis import DEFAULT_SEGMENT, megopolis, megopolis_batch
from repro.core.resamplers.metropolis import (
    WARP,
    metropolis,
    metropolis_batch,
    metropolis_c1,
    metropolis_c1_batch,
    metropolis_c2,
    metropolis_c2_batch,
)
from repro.core.resamplers.prefix_sum import (
    improved_systematic,
    improved_systematic_batch,
    multinomial,
    multinomial_batch,
    residual,
    residual_batch,
    stratified,
    stratified_batch,
    systematic,
    systematic_batch,
)
from repro.core.resamplers.rejection import rejection, rejection_batch
from repro.kernels.common import PLANE_DTYPES, quantise_plane

AUTO = "auto"
BACKENDS = ("reference", "xla", "pallas_interpret", "pallas")
# Kernel coalescing segment: one (8, 128) f32 VMEM tile (DESIGN.md §2).
KERNEL_SEGMENT = 1024
# The C1/C2 kernels' partition is that same tile, in the papers' byte units.
KERNEL_PARTITION_BYTES = KERNEL_SEGMENT * 4
PALLAS_BACKENDS = ("pallas_interpret", "pallas")
# Loop-bound cap when num_iters='auto' resolves under trace: eq. (3) yields a
# traced B, so offset tables are drawn at this static size and the
# accept/reject loop runs the traced bound (clamped).  4096 covers every
# weight family in the paper's sweeps (y <= 4 needs B <= ~210; the
# one-heavy-particle torture case at N=512 needs ~2.4k).
AUTO_MAX_ITERS = 4096


def _is_traced(x) -> bool:
    return isinstance(x, jax.core.Tracer)


def _check_positive_int(value, field: str, cls: str):
    if isinstance(value, bool) or not isinstance(value, int) or value < 1:
        raise ValueError(f"{cls}.{field} must be a positive int; got {value!r}")


def _check_num_iters(value, cls: str):
    if value == AUTO:
        return
    if isinstance(value, bool) or not isinstance(value, int) or value < 1:
        raise ValueError(
            f"{cls}.num_iters must be a positive int or {AUTO!r} (eq. 3 selection); "
            f"got {value!r}"
        )


def _check_backend(value, cls: str):
    if value not in BACKENDS:
        raise ValueError(f"{cls}.backend must be one of {BACKENDS}; got {value!r}")


def _check_plane_dtype(value, cls: str):
    if value not in PLANE_DTYPES:
        raise ValueError(
            f"{cls}.plane_dtype must be one of {PLANE_DTYPES}; got {value!r}"
        )


def _take_rows(particles: jnp.ndarray, ancestors: jnp.ndarray) -> jnp.ndarray:
    """Row-wise ancestor gather: ``out[b] = particles[b][ancestors[b]]``."""
    return jax.vmap(lambda p, a: jnp.take(p, a, axis=0))(particles, ancestors)


class Resampler:
    """A built resampler: the ONE callable surface every family shares.

    Constructed by ``ResamplerSpec.build()``; hyperparameters and backend
    are baked in, so call sites never thread kwargs::

        r(key, weights)            # int32[N]     over f32[N]
        r.batch(key, weights)      # int32[B, N]  over f32[B, N]
        r.batch_rows(keys, weights)  # explicit per-row keys (filter banks)
        r.apply(key, weights, particles)        # -> (particles', ancestors)
        r.apply_batch(key, weights, particles)  # bank form of apply
        r.apply_rows(keys, weights, particles)  # explicit per-row keys
        r.step(key, log_w, particles, ess_threshold)   # fused SMC step
        r.step_rows(keys, log_w, particles, ess_threshold)  # bank form
        r.name, r.spec             # registry name / originating spec

    ``batch`` follows the DESIGN.md §4 contract: the key is split once
    along the batch axis and row ``b`` is bit-identical to the single call
    with ``split(key, B)[b]`` (the pallas batched Megopolis kernel instead
    shares the offset table bank-wide — its own documented contract).

    ``apply`` is the fused resample+gather data path (DESIGN.md §11):
    select ancestors AND copy each ancestor's particle state in one step,
    ``particles`` being ``[N]``/``[N, ...]`` (``[B, N, ...]`` for the bank
    forms).  On the reference/xla backends it IS the index + ``jnp.take``
    composition (the bit-identical oracle); on the pallas backends the
    state copy happens inside the kernel — the ancestor vector never
    round-trips through HBM between selection and gather.  Every form
    returns ``(particles', ancestors)`` with ancestors bit-identical to the
    corresponding index-only call.

    ``step`` is the fused SMC step (DESIGN.md §12): normalise log-weights,
    compute ESS, take the resample-or-not branch, and copy state, returning
    ``(particles', ancestors, stats)`` with ``stats`` a ``StepStats``
    record (ess_norm, log_evidence_incr, resampled, max_weight, survivors
    — DESIGN.md §15).  The resample branch (``ess_norm < ess_threshold``,
    strict) is bit-identical to ``apply(key, normalise_log_weights(log_w),
    particles)``; the no-op branch returns the particles bit-identical with
    identity ancestors and ``incr = 0``.  Randomness is consumed
    unconditionally in BOTH branches (where-select, not cond), so key
    chains advance identically whether or not a resample fires.  On the
    pallas backends the whole step is ONE kernel launch with the first four
    stats fields reduced in-kernel; on reference/xla it IS the normalise →
    ESS → branch → ``apply`` composition (the bit-identical oracle).
    ``survivors`` (the distinct-ancestor count) is composed from the
    returned ancestors on every backend.
    """

    def __init__(
        self,
        spec: "ResamplerSpec",
        single: Callable,
        batch: Callable,
        *,
        apply: Callable = None,
        apply_batch: Callable = None,
        apply_rows: Callable = None,
        step: Callable = None,
        step_rows: Callable = None,
    ):
        self.spec = spec
        self.name = spec.name
        # The plane-compression axis (DESIGN.md §14).  Quantisation happens
        # HERE — once, at the public entry — for EVERY backend, so the
        # reference lane is the bit-exact oracle of the compressed kernels.
        self.plane_dtype = getattr(spec, "plane_dtype", "float32")
        # The §16 degeneracy-guard axis: 'off' | 'flag' | 'recover'.
        self.guard = getattr(spec, "guard", "off")
        self._single = single
        self._batch = batch

        # Derived (reference/xla) apply forms compose the SAME single/batch
        # callables the index path runs — deliberately NOT re-jitted as one
        # program: a separately compiled composition may constant-fold the
        # prefix-sum family's f32 cumsum differently and shift a searchsorted
        # boundary, breaking the bit-identical-oracle contract.  Callers
        # wanting one fused XLA program jit the call site (consumers do:
        # the filter/sampler scans are jitted wholesale).
        if apply is None:
            def apply(key, w, p):
                ancestors = single(key, w)
                return jnp.take(p, ancestors, axis=0), ancestors

        if apply_batch is None:
            def apply_batch(key, w, p):
                ancestors = batch(key, w)
                return _take_rows(p, ancestors), ancestors

        if apply_rows is None:
            inner = apply

            def apply_rows(keys, w, p):
                return jax.vmap(inner)(keys, w, p)

        self._apply = apply
        self._apply_batch = apply_batch
        self._apply_rows = apply_rows

        # Composed step default: the SAME (possibly fused) apply callable,
        # wrapped in the normalise → ESS → branch glue.  Not re-jitted, for
        # the same reason as the apply defaults above — this composition is
        # the oracle the fused step kernels are gated against.
        if step is None:
            apply_fn = apply
            plane_dtype = self.plane_dtype

            def step(key, log_w, particles, ess_threshold):
                n = log_w.shape[-1]
                ess_n = effective_sample_size(log_w) / jnp.float32(n)
                do = ess_n < ess_threshold
                # Normalised weights re-land on the plane-dtype grid — the
                # value the fused step kernels' in-body requantise matches.
                # A no-op at f32.
                w = quantise_plane(normalise_log_weights(log_w), plane_dtype)
                p_res, a_res = apply_fn(key, w, particles)
                ancestors = jnp.where(do, a_res, jnp.arange(n, dtype=jnp.int32))
                p_out = jnp.where(do, p_res, particles)
                incr = jnp.where(do, log_mean_weight(log_w), jnp.float32(0.0))
                stats4 = jnp.stack([
                    ess_n,
                    incr,
                    jnp.where(do, jnp.float32(1.0), jnp.float32(0.0)),
                    max_normalised_weight(log_w),
                ])
                return p_out, ancestors, stats4

        if step_rows is None:
            step_fn = step

            def step_rows(keys, log_w, particles, ess_threshold):
                return jax.vmap(step_fn, in_axes=(0, 0, 0, None))(
                    keys, log_w, particles, ess_threshold
                )

        self._step = step
        self._step_rows = step_rows
        self.__name__ = f"{self.name}_resampler"
        self.__qualname__ = self.__name__

    def quantise(self, x: jnp.ndarray) -> jnp.ndarray:
        """Round a float array onto the spec's plane-dtype grid — the value
        the compressed tiles represent on the wire (DESIGN.md §14).
        Identity at ``plane_dtype='float32'`` and for non-float arrays.
        Applied by every public entry, so ``r_bf16(key, w)`` equals
        ``r_f32(key, r_bf16.quantise(w))`` ancestor-for-ancestor."""
        return quantise_plane(x, self.plane_dtype)

    def _span(self, entry: str):
        """The dispatch trace span (DESIGN.md §15):
        ``family/backend/entry/plane_dtype``.  A named scope: it leaves
        the jaxpr unchanged, so the structural jaxpr gates never see it."""
        return dispatch_span(
            self.name, getattr(self.spec, "backend", "reference"), entry,
            self.plane_dtype,
        )

    def _guard_weights(self, w: jnp.ndarray, entry: str) -> jnp.ndarray:
        """§16 guard for the linear-weight entries: at ``guard='recover'``,
        degenerate rows (``metrics.degenerate_weights``: zero/nan/±inf
        mass) are substituted with the uniform bank before dispatch — an
        exact bitwise passthrough on clean rows; at ``'flag'`` the weights
        run untouched and a ``ResilienceEvent`` is staged (only while a
        recorder is active at trace time).  ``'off'`` returns ``w``
        unchanged with zero extra equations."""
        if self.guard == "off":
            return w
        deg = degenerate_weights(w, axis=-1)
        if self.guard == "recover":
            n = w.shape[-1]
            w = jnp.where(
                jnp.expand_dims(deg, -1), jnp.full_like(w, 1.0 / n), w
            )
        maybe_emit_guard_event(
            self.name, getattr(self.spec, "backend", "reference"), entry,
            self.guard, deg,
        )
        return w

    def _guard_log_weights(self, lw: jnp.ndarray, entry: str):
        """§16 guard for the fused step: returns ``(lw_run, degenerate)``.

        ``degenerate`` (``metrics.degenerate_log_weights``) is composed
        into ``StepStats`` under EVERY policy — the flag itself is free
        telemetry, so 'off' and 'flag' trace to the identical jaxpr.  At
        ``'recover'`` degenerate rows are replaced by the all-zeros
        log-weight bank (uniform weights) before dispatch, so the kernel
        runs a clean-input program with the same key: RNG is consumed
        branch-independently and every output is finite."""
        deg = degenerate_log_weights(lw, axis=-1)
        if self.guard == "recover":
            lw = jnp.where(jnp.expand_dims(deg, -1), jnp.zeros_like(lw), lw)
        if self.guard != "off":
            maybe_emit_guard_event(
                self.name, getattr(self.spec, "backend", "reference"), entry,
                self.guard, deg,
            )
        return lw, deg

    def __call__(self, key: jax.Array, weights: jnp.ndarray) -> jnp.ndarray:
        if weights.ndim != 1:
            raise ValueError(
                f"{self.name}: expected weights[N]; got shape {weights.shape} "
                "(use .batch for weights[B, N])"
            )
        with self._span("single"):
            return self._single(
                key, self._guard_weights(self.quantise(weights), "single")
            )

    def batch(self, key: jax.Array, weights: jnp.ndarray) -> jnp.ndarray:
        if weights.ndim != 2:
            raise ValueError(
                f"{self.name}.batch: expected weights[B, N]; got shape {weights.shape}"
            )
        with self._span("batch"):
            return self._batch(
                key, self._guard_weights(self.quantise(weights), "batch")
            )

    def batch_rows(self, keys: jax.Array, weights: jnp.ndarray) -> jnp.ndarray:
        """vmap the single-population call over explicit per-row keys.

        The filter-bank path: callers that already carry per-row key chains
        (``run_filter_bank``) join the batched launch without re-deriving
        keys.  Row ``b`` is bit-identical to ``self(keys[b], weights[b])``.
        """
        if weights.ndim != 2:
            raise ValueError(
                f"{self.name}.batch_rows: expected weights[B, N]; got shape {weights.shape}"
            )
        with self._span("batch_rows"):
            return jax.vmap(self._single)(
                keys, self._guard_weights(self.quantise(weights), "batch_rows")
            )

    def _check_state(self, weights, particles, who: str, lead: int = 1):
        if particles.ndim < lead or particles.shape[:lead] != weights.shape[:lead]:
            raise ValueError(
                f"{self.name}.{who}: particles must lead with the "
                f"{'[B, N]' if lead == 2 else '[N]'} axes of weights; got "
                f"particles {particles.shape} for weights {weights.shape}"
            )

    def apply(self, key: jax.Array, weights: jnp.ndarray, particles: jnp.ndarray):
        """Fused resample+gather: ``(particles', ancestors)`` over one
        population (DESIGN.md §11).  ``particles'[i] = particles[a[i]]``
        with ``a`` bit-identical to ``self(key, weights)``."""
        if weights.ndim != 1:
            raise ValueError(
                f"{self.name}.apply: expected weights[N]; got shape {weights.shape} "
                "(use .apply_batch for weights[B, N])"
            )
        self._check_state(weights, particles, "apply")
        with self._span("apply"):
            return self._apply(
                key, self._guard_weights(self.quantise(weights), "apply"),
                self.quantise(particles),
            )

    def apply_batch(self, key: jax.Array, weights: jnp.ndarray, particles: jnp.ndarray):
        """Bank form of ``apply`` under the §4 split-key contract."""
        if weights.ndim != 2:
            raise ValueError(
                f"{self.name}.apply_batch: expected weights[B, N]; got shape "
                f"{weights.shape}"
            )
        self._check_state(weights, particles, "apply_batch", lead=2)
        with self._span("apply_batch"):
            return self._apply_batch(
                key, self._guard_weights(self.quantise(weights), "apply_batch"),
                self.quantise(particles),
            )

    def apply_rows(self, keys: jax.Array, weights: jnp.ndarray, particles: jnp.ndarray):
        """``apply`` over explicit per-row keys (the filter-bank path): row
        ``b`` is bit-identical to ``self.apply(keys[b], weights[b],
        particles[b])``; on kernel backends with a leading-batch-grid fused
        kernel (Megopolis, Metropolis, rejection) this is ONE launch."""
        if weights.ndim != 2:
            raise ValueError(
                f"{self.name}.apply_rows: expected weights[B, N]; got shape "
                f"{weights.shape}"
            )
        if keys.shape[0] != weights.shape[0]:
            # The fused bank kernels size their grid from weights; a short
            # key array would read out-of-bounds seeds instead of failing
            # like the vmap-derived batch_rows does — check here, once,
            # for every backend.
            raise ValueError(
                f"{self.name}.apply_rows: expected one key per row; got "
                f"{keys.shape[0]} keys for weights[{weights.shape[0]}, ...]"
            )
        self._check_state(weights, particles, "apply_rows", lead=2)
        with self._span("apply_rows"):
            return self._apply_rows(
                keys, self._guard_weights(self.quantise(weights), "apply_rows"),
                self.quantise(particles),
            )

    def step(
        self,
        key: jax.Array,
        log_weights: jnp.ndarray,
        particles: jnp.ndarray,
        ess_threshold,
    ):
        """Fused SMC step over one population (DESIGN.md §12): returns
        ``(particles', ancestors, stats)`` with ``stats`` a ``StepStats``
        record (DESIGN.md §15).  Resamples iff ``ess_norm < ess_threshold``
        (strict: a threshold of 0 never fires, a population exactly at
        threshold does not fire); the resample branch is bit-identical to
        ``self.apply(key, normalise_log_weights(log_weights), particles)``,
        the no-op branch returns ``particles`` unchanged with identity
        ancestors and ``incr = 0``.  The key is consumed either way.  The
        stats vector comes straight out of the (single) kernel launch on
        the pallas backends; ``survivors`` is composed here from the
        returned ancestors — consumers that drop the record compile the
        exact pre-telemetry program (analyzer pass 6)."""
        if log_weights.ndim != 1:
            raise ValueError(
                f"{self.name}.step: expected log_weights[N]; got shape "
                f"{log_weights.shape} (use .step_rows for log_weights[B, N])"
            )
        self._check_state(log_weights, particles, "step")
        with self._span("step"):
            lw_run, deg = self._guard_log_weights(
                self.quantise(log_weights), "step"
            )
            p_out, ancestors, stats4 = self._step(
                key, lw_run, self.quantise(particles), ess_threshold,
            )
            stats = stats_from_vector(
                stats4, unique_ancestor_count(ancestors), deg
            )
        return p_out, ancestors, stats

    def step_rows(
        self,
        keys: jax.Array,
        log_weights: jnp.ndarray,
        particles: jnp.ndarray,
        ess_threshold,
    ):
        """``step`` over explicit per-row keys (the filter-bank path): row
        ``b`` is bit-identical to ``self.step(keys[b], log_weights[b],
        particles[b], ess_threshold)`` — each row takes its OWN branch and
        the returned ``StepStats`` record is batched ``[B]`` per field.  On
        kernel backends with a leading-batch-grid step kernel (Megopolis,
        Metropolis, rejection) this is ONE launch."""
        if log_weights.ndim != 2:
            raise ValueError(
                f"{self.name}.step_rows: expected log_weights[B, N]; got shape "
                f"{log_weights.shape}"
            )
        if keys.shape[0] != log_weights.shape[0]:
            raise ValueError(
                f"{self.name}.step_rows: expected one key per row; got "
                f"{keys.shape[0]} keys for log_weights[{log_weights.shape[0]}, ...]"
            )
        self._check_state(log_weights, particles, "step_rows", lead=2)
        with self._span("step_rows"):
            lw_run, deg = self._guard_log_weights(
                self.quantise(log_weights), "step_rows"
            )
            p_out, ancestors, stats4 = self._step_rows(
                keys, lw_run, self.quantise(particles), ess_threshold,
            )
            stats = stats_from_vector(
                stats4, unique_ancestor_count(ancestors), deg
            )
        return p_out, ancestors, stats

    def __repr__(self):
        return f"Resampler({self.spec!r})"


@dataclasses.dataclass(frozen=True)
class ResamplerSpec:
    """Base class: frozen, hashable, static-safe spec of one resampler family."""

    _NAME: ClassVar[str] = ""

    @property
    def name(self) -> str:
        return self._NAME

    def replace(self, **changes) -> "ResamplerSpec":
        """Return a validated copy with ``changes`` applied (sweep-friendly)."""
        return dataclasses.replace(self, **changes)

    def build(self) -> Resampler:
        raise NotImplementedError

    def build_resilient(self, *, ladder=None, recorder=None, probe=True) -> Resampler:
        """Build with the §16 backend fallback ladder: try this spec's
        backend, demoting rung by rung (default pallas → pallas_interpret →
        xla → reference) on typed build/probe failures, emitting one
        ``backend_demotion`` ``ResilienceEvent`` per rung into ``recorder``.
        Raises ``BackendUnavailable`` (with per-rung causes) only when every
        rung fails."""
        from repro.resilience.fallback import build_with_fallback

        return build_with_fallback(
            self, ladder=ladder, recorder=recorder, probe=probe
        )


def _resolve_iters_dynamic(num_iters, weights):
    """Trace-safe iteration count: eq. (3) when 'auto', else the static int."""
    if num_iters == AUTO:
        return jnp.minimum(select_iterations(weights), AUTO_MAX_ITERS)
    return num_iters


def _resolve_iters_static(num_iters, weights, name: str) -> int:
    """Concrete iteration count for kernel grids (pallas backends)."""
    if num_iters != AUTO:
        return num_iters
    if _is_traced(weights):
        raise TypeError(
            f"{name}: num_iters='auto' under a pallas backend needs concrete "
            "weights (B sets the kernel grid); pass an int num_iters to use "
            "this spec inside jit."
        )
    return int(select_iterations(weights))


def _per_row_auto_batch(spec, single):
    """Pallas ``.batch`` under ``num_iters='auto'``: eq. (3) must see EACH
    row's weights — resolving one bank-level B would silently under-iterate
    concentrated rows — and the §4 contract (row b bit-identical to the
    single call with split key b) must survive, so the rows are launched
    individually with their own static B.  Needs concrete weights (host
    loop); inside jit pass an int ``num_iters``."""

    def batch(key, w):
        if _is_traced(w):
            raise TypeError(
                f"{spec.name}: num_iters='auto' under a pallas backend needs "
                "concrete weights (eq. 3 resolves per row); pass an int "
                "num_iters to use .batch inside jit."
            )
        keys = split_batch_keys(key, w.shape[0])
        return jnp.stack([single(keys[b], w[b]) for b in range(w.shape[0])])

    return batch


def _per_row_auto_apply(spec, apply_single, *, explicit_keys: bool):
    """The ``apply`` analogue of ``_per_row_auto_batch``: eq. (3) resolves
    per row, so 'auto' bank applies launch row-by-row over concrete
    weights; inside jit pass an int ``num_iters``."""

    def fn(key_or_keys, w, p):
        if _is_traced(w):
            raise TypeError(
                f"{spec.name}: num_iters='auto' under a pallas backend needs "
                "concrete weights (eq. 3 resolves per row); pass an int "
                "num_iters to use the bank apply forms inside jit."
            )
        keys = key_or_keys if explicit_keys else split_batch_keys(key_or_keys, w.shape[0])
        outs = [apply_single(keys[b], w[b], p[b]) for b in range(w.shape[0])]
        return jnp.stack([o[0] for o in outs]), jnp.stack([o[1] for o in outs])

    return fn


def _per_row_auto_step(spec, step_single):
    """The ``step`` analogue of ``_per_row_auto_apply``: eq. (3) resolves
    per row from each row's normalised weights, so 'auto' bank steps launch
    row-by-row over concrete log-weights; inside jit pass an int
    ``num_iters``."""

    def fn(keys, log_w, p, thr):
        if _is_traced(log_w):
            raise TypeError(
                f"{spec.name}: num_iters='auto' under a pallas backend needs "
                "concrete log-weights (eq. 3 resolves per row); pass an int "
                "num_iters to use step_rows inside jit."
            )
        outs = [step_single(keys[b], log_w[b], p[b], thr) for b in range(log_w.shape[0])]
        return tuple(jnp.stack([o[i] for o in outs]) for i in range(3))

    return fn


def _maybe_jit(single, batch, backend: str):
    """backend='xla' is the reference algorithm jit-wrapped (bit-identical)."""
    if backend == "xla":
        return jax.jit(single), jax.jit(batch)
    return single, batch


def _vmap_batch(single):
    """Derive the standard DESIGN.md §4 batched form: split keys + vmap."""

    def batch(key, weights):
        keys = split_batch_keys(key, weights.shape[0])
        return jax.vmap(single)(keys, weights)

    return batch


@dataclasses.dataclass(frozen=True)
class MegopolisSpec(ResamplerSpec):
    """The paper's contribution (Alg. 5): segment-coalesced Metropolis.

    ``segment`` is the coalescing segment size S of the reference path; the
    pallas backends run the TPU kernel, whose S is fixed at one VMEM tile
    (``KERNEL_SEGMENT`` = 1024) — constructing a pallas spec therefore
    requires ``segment=1024`` so the coalescing contract stays explicit.
    """

    num_iters: Union[int, str] = AUTO
    segment: int = DEFAULT_SEGMENT
    backend: str = "reference"
    plane_dtype: str = "float32"
    guard: str = "off"

    _NAME: ClassVar[str] = "megopolis"

    def __post_init__(self):
        _check_num_iters(self.num_iters, "MegopolisSpec")
        _check_positive_int(self.segment, "segment", "MegopolisSpec")
        _check_backend(self.backend, "MegopolisSpec")
        _check_plane_dtype(self.plane_dtype, "MegopolisSpec")
        check_guard_policy(self.guard, "MegopolisSpec")
        if self.backend in ("pallas", "pallas_interpret") and self.segment != KERNEL_SEGMENT:
            raise ValueError(
                f"MegopolisSpec: the pallas kernel coalesces at segment="
                f"{KERNEL_SEGMENT} (one f32 VMEM tile); got segment={self.segment}. "
                "Set segment=1024 or use backend='reference'/'xla'."
            )

    def build(self) -> Resampler:
        if self.backend in ("pallas", "pallas_interpret"):
            # Lazy import: kernels are only a dependency of pallas specs.
            from repro.kernels.megopolis.ops import (
                megopolis_tpu,
                megopolis_tpu_apply,
                megopolis_tpu_apply_batch,
                megopolis_tpu_apply_rows,
                megopolis_tpu_batch,
                megopolis_tpu_step,
                megopolis_tpu_step_rows,
            )

            interpret = self.backend == "pallas_interpret"
            pd = self.plane_dtype

            def single(key, w):
                b = _resolve_iters_static(self.num_iters, w, self.name)
                return megopolis_tpu(key, w, b, interpret=interpret, plane_dtype=pd)

            def batch(key, w):
                b = _resolve_iters_static(self.num_iters, w, self.name)
                return megopolis_tpu_batch(key, w, b, interpret=interpret,
                                           plane_dtype=pd)

            def apply(key, w, p):
                b = _resolve_iters_static(self.num_iters, w, self.name)
                return megopolis_tpu_apply(key, w, p, b, interpret=interpret,
                                           plane_dtype=pd)

            def apply_batch(key, w, p):
                # Same bank-level resolve + shared-offset contract as .batch,
                # so apply_batch ancestors == .batch ancestors under 'auto'.
                b = _resolve_iters_static(self.num_iters, w, self.name)
                return megopolis_tpu_apply_batch(key, w, p, b, interpret=interpret,
                                                 plane_dtype=pd)

            def step(key, lw, p, thr):
                # eq. (3) sees the SAME normalised weights the composed
                # path hands to apply — fused/composed 'auto' agree on B.
                b = _resolve_iters_static(
                    self.num_iters, normalise_log_weights(lw), self.name
                )
                return megopolis_tpu_step(key, lw, p, b, thr, interpret=interpret,
                                          plane_dtype=pd)

            if self.num_iters == AUTO:
                # batch_rows' per-row contract needs eq. (3) PER ROW.
                apply_rows = _per_row_auto_apply(self, apply, explicit_keys=True)
                step_rows = _per_row_auto_step(self, step)
            else:

                def apply_rows(keys, w, p):
                    return megopolis_tpu_apply_rows(
                        keys, w, p, self.num_iters, interpret=interpret,
                        plane_dtype=pd,
                    )

                def step_rows(keys, lw, p, thr):
                    return megopolis_tpu_step_rows(
                        keys, lw, p, self.num_iters, thr, interpret=interpret,
                        plane_dtype=pd,
                    )

            return Resampler(self, single, batch, apply=apply,
                             apply_batch=apply_batch, apply_rows=apply_rows,
                             step=step, step_rows=step_rows)

        seg = self.segment

        if self.num_iters == AUTO:

            def single(key, w):
                # eq. (3) resolves at call time; the loop runs the (possibly
                # traced) selected bound over an offset table drawn at the
                # static cap.  NB: a (AUTO_MAX_ITERS,) draw shares no prefix
                # with a (B,) draw, so 'auto' is a distinct random stream
                # from the same spec with num_iters=B pinned (unlike the
                # Metropolis family, where the two are bit-identical).
                b = _resolve_iters_dynamic(AUTO, w)
                key_off, _ = jax.random.split(key)
                offsets = jax.random.randint(key_off, (AUTO_MAX_ITERS,), 0, w.shape[0])
                return megopolis(key, w, b, segment=seg, offsets=offsets)

        else:

            def single(key, w):
                return megopolis(key, w, self.num_iters, segment=seg)

        single_fn, batch_fn = _maybe_jit(single, _vmap_batch(single), self.backend)
        return Resampler(self, single_fn, batch_fn)


def _metropolis_family_build(spec, fn, extra_kwargs: dict) -> Resampler:
    """Shared build for the fixed-point accept/reject loops (Algs. 2-4):
    ``num_iters`` is only a loop bound + fold_in counter, so the 'auto'
    (traced) count is bit-identical to the same static count."""

    def single(key, w):
        b = _resolve_iters_dynamic(spec.num_iters, w)
        return fn(key, w, b, **extra_kwargs)

    single_fn, batch_fn = _maybe_jit(single, _vmap_batch(single), spec.backend)
    return Resampler(spec, single_fn, batch_fn)


@dataclasses.dataclass(frozen=True)
class MetropolisSpec(ResamplerSpec):
    """Paper Alg. 2: the random-access Metropolis baseline."""

    num_iters: Union[int, str] = AUTO
    backend: str = "reference"
    plane_dtype: str = "float32"
    guard: str = "off"

    _NAME: ClassVar[str] = "metropolis"

    def __post_init__(self):
        _check_num_iters(self.num_iters, "MetropolisSpec")
        _check_backend(self.backend, "MetropolisSpec")
        _check_plane_dtype(self.plane_dtype, "MetropolisSpec")
        check_guard_policy(self.guard, "MetropolisSpec")

    def build(self) -> Resampler:
        if self.backend in PALLAS_BACKENDS:
            from repro.kernels.metropolis.ops import (
                metropolis_tpu,
                metropolis_tpu_apply,
                metropolis_tpu_apply_batch,
                metropolis_tpu_apply_rows,
                metropolis_tpu_batch,
                metropolis_tpu_step,
                metropolis_tpu_step_rows,
            )

            interpret = self.backend == "pallas_interpret"
            pd = self.plane_dtype

            def single(key, w):
                b = _resolve_iters_static(self.num_iters, w, self.name)
                return metropolis_tpu(key, w, b, interpret=interpret, plane_dtype=pd)

            def apply(key, w, p):
                b = _resolve_iters_static(self.num_iters, w, self.name)
                return metropolis_tpu_apply(key, w, p, b, interpret=interpret,
                                            plane_dtype=pd)

            def step(key, lw, p, thr):
                b = _resolve_iters_static(
                    self.num_iters, normalise_log_weights(lw), self.name
                )
                return metropolis_tpu_step(key, lw, p, b, thr, interpret=interpret,
                                           plane_dtype=pd)

            if self.num_iters == AUTO:
                batch = _per_row_auto_batch(self, single)
                apply_batch = _per_row_auto_apply(self, apply, explicit_keys=False)
                apply_rows = _per_row_auto_apply(self, apply, explicit_keys=True)
                step_rows = _per_row_auto_step(self, step)
            else:

                def batch(key, w):
                    # One [B, R, 128] launch; row b bit-identical to the
                    # single kernel with split(key, B)[b] (held on-kernel,
                    # DESIGN.md §4).
                    return metropolis_tpu_batch(
                        key, w, self.num_iters, interpret=interpret, plane_dtype=pd
                    )

                def apply_batch(key, w, p):
                    return metropolis_tpu_apply_batch(
                        key, w, p, self.num_iters, interpret=interpret,
                        plane_dtype=pd,
                    )

                def apply_rows(keys, w, p):
                    return metropolis_tpu_apply_rows(
                        keys, w, p, self.num_iters, interpret=interpret,
                        plane_dtype=pd,
                    )

                def step_rows(keys, lw, p, thr):
                    return metropolis_tpu_step_rows(
                        keys, lw, p, self.num_iters, thr, interpret=interpret,
                        plane_dtype=pd,
                    )

            return Resampler(self, single, batch, apply=apply,
                             apply_batch=apply_batch, apply_rows=apply_rows,
                             step=step, step_rows=step_rows)
        return _metropolis_family_build(self, metropolis, {})


def _check_kernel_partition(spec, cls: str):
    """The C1/C2 kernels' partition is one (8,128) f32 VMEM tile: pallas
    specs must say so (same explicitness rule as MegopolisSpec.segment)."""
    if spec.backend in PALLAS_BACKENDS and spec.partition_size_bytes != KERNEL_PARTITION_BYTES:
        raise ValueError(
            f"{cls}: the pallas kernel's partition is one f32 VMEM tile = "
            f"{KERNEL_PARTITION_BYTES} bytes; got partition_size_bytes="
            f"{spec.partition_size_bytes}. Set partition_size_bytes=4096 or "
            "use backend='reference'/'xla'."
        )


def _c1c2_pallas_build(spec, tpu_fn, tpu_apply_fn, tpu_step_fn) -> Resampler:
    """Shared pallas build for the segment-local variants: single kernel
    call, batch via lax.map over split keys (row b == single with key b —
    the same §4 contract the reference lane derives by vmap).  'auto'
    batches resolve eq. (3) per row (see ``_per_row_auto_batch``: lax.map
    would hand ``single`` traced rows and a bank-level B would be wrong).
    The fused ``apply``/``step`` forms compose the same way: C1/C2 have no
    leading-batch-grid kernel, so the bank forms map the fused single."""

    interpret = spec.backend == "pallas_interpret"
    pd = spec.plane_dtype

    def single(key, w):
        b = _resolve_iters_static(spec.num_iters, w, spec.name)
        return tpu_fn(key, w, b, interpret=interpret, plane_dtype=pd)

    def apply(key, w, p):
        b = _resolve_iters_static(spec.num_iters, w, spec.name)
        return tpu_apply_fn(key, w, p, b, interpret=interpret, plane_dtype=pd)

    def step(key, lw, p, thr):
        b = _resolve_iters_static(
            spec.num_iters, normalise_log_weights(lw), spec.name
        )
        return tpu_step_fn(key, lw, p, b, thr, interpret=interpret, plane_dtype=pd)

    if spec.num_iters == AUTO:
        batch = _per_row_auto_batch(spec, single)
        apply_batch = _per_row_auto_apply(spec, apply, explicit_keys=False)
        apply_rows = _per_row_auto_apply(spec, apply, explicit_keys=True)
        step_rows = _per_row_auto_step(spec, step)
    else:

        def batch(key, w):
            keys = split_batch_keys(key, w.shape[0])
            return jax.lax.map(lambda kw: single(kw[0], kw[1]), (keys, w))

        def apply_batch(key, w, p):
            keys = split_batch_keys(key, w.shape[0])
            return jax.lax.map(lambda kwp: apply(*kwp), (keys, w, p))

        def apply_rows(keys, w, p):
            return jax.lax.map(lambda kwp: apply(*kwp), (keys, w, p))

        def step_rows(keys, lw, p, thr):
            return jax.lax.map(
                lambda klp: step(klp[0], klp[1], klp[2], thr), (keys, lw, p)
            )

    return Resampler(spec, single, batch, apply=apply,
                     apply_batch=apply_batch, apply_rows=apply_rows,
                     step=step, step_rows=step_rows)


@dataclasses.dataclass(frozen=True)
class MetropolisC1Spec(ResamplerSpec):
    """Paper Alg. 3 (Dülger C1): one warp-shared partition, all iterations.

    The pallas kernel shares the partition at tile granularity (its "warp"
    is the whole 1024-lane tile; ``warp`` is a reference-lane knob) and
    requires ``partition_size_bytes=4096`` — one f32 VMEM tile.
    """

    num_iters: Union[int, str] = AUTO
    partition_size_bytes: int = 128
    warp: int = WARP
    backend: str = "reference"
    plane_dtype: str = "float32"
    guard: str = "off"

    _NAME: ClassVar[str] = "metropolis_c1"

    def __post_init__(self):
        _check_num_iters(self.num_iters, "MetropolisC1Spec")
        _check_positive_int(self.partition_size_bytes, "partition_size_bytes", "MetropolisC1Spec")
        _check_positive_int(self.warp, "warp", "MetropolisC1Spec")
        _check_backend(self.backend, "MetropolisC1Spec")
        _check_kernel_partition(self, "MetropolisC1Spec")
        _check_plane_dtype(self.plane_dtype, "MetropolisC1Spec")
        check_guard_policy(self.guard, "MetropolisC1Spec")

    def build(self) -> Resampler:
        if self.backend in PALLAS_BACKENDS:
            from repro.kernels.metropolis.ops import (
                metropolis_c1_tpu,
                metropolis_c1_tpu_apply,
                metropolis_c1_tpu_step,
            )

            return _c1c2_pallas_build(
                self, metropolis_c1_tpu, metropolis_c1_tpu_apply,
                metropolis_c1_tpu_step,
            )
        return _metropolis_family_build(
            self,
            metropolis_c1,
            {"partition_size_bytes": self.partition_size_bytes, "warp": self.warp},
        )


@dataclasses.dataclass(frozen=True)
class MetropolisC2Spec(ResamplerSpec):
    """Paper Alg. 4 (Dülger C2): fresh warp-shared partition per iteration.

    Pallas geometry as for C1: tile-granular sharing,
    ``partition_size_bytes=4096`` required.
    """

    num_iters: Union[int, str] = AUTO
    partition_size_bytes: int = 128
    warp: int = WARP
    backend: str = "reference"
    plane_dtype: str = "float32"
    guard: str = "off"

    _NAME: ClassVar[str] = "metropolis_c2"

    def __post_init__(self):
        _check_num_iters(self.num_iters, "MetropolisC2Spec")
        _check_positive_int(self.partition_size_bytes, "partition_size_bytes", "MetropolisC2Spec")
        _check_positive_int(self.warp, "warp", "MetropolisC2Spec")
        _check_backend(self.backend, "MetropolisC2Spec")
        _check_kernel_partition(self, "MetropolisC2Spec")
        _check_plane_dtype(self.plane_dtype, "MetropolisC2Spec")
        check_guard_policy(self.guard, "MetropolisC2Spec")

    def build(self) -> Resampler:
        if self.backend in PALLAS_BACKENDS:
            from repro.kernels.metropolis.ops import (
                metropolis_c2_tpu,
                metropolis_c2_tpu_apply,
                metropolis_c2_tpu_step,
            )

            return _c1c2_pallas_build(
                self, metropolis_c2_tpu, metropolis_c2_tpu_apply,
                metropolis_c2_tpu_step,
            )
        return _metropolis_family_build(
            self,
            metropolis_c2,
            {"partition_size_bytes": self.partition_size_bytes, "warp": self.warp},
        )


@dataclasses.dataclass(frozen=True)
class RejectionSpec(ResamplerSpec):
    """Murray's rejection resampler (§1 context): unbiased, capped loop."""

    max_iters: int = 1024
    backend: str = "reference"
    plane_dtype: str = "float32"
    guard: str = "off"

    _NAME: ClassVar[str] = "rejection"

    def __post_init__(self):
        _check_positive_int(self.max_iters, "max_iters", "RejectionSpec")
        _check_backend(self.backend, "RejectionSpec")
        _check_plane_dtype(self.plane_dtype, "RejectionSpec")
        check_guard_policy(self.guard, "RejectionSpec")

    def build(self) -> Resampler:
        if self.backend in PALLAS_BACKENDS:
            from repro.kernels.rejection.ops import (
                rejection_tpu,
                rejection_tpu_apply,
                rejection_tpu_apply_batch,
                rejection_tpu_apply_rows,
                rejection_tpu_batch,
                rejection_tpu_step,
                rejection_tpu_step_rows,
            )

            interpret = self.backend == "pallas_interpret"
            pd = self.plane_dtype

            def single(key, w):
                return rejection_tpu(key, w, max_iters=self.max_iters,
                                     interpret=interpret, plane_dtype=pd)

            def batch(key, w):
                return rejection_tpu_batch(
                    key, w, max_iters=self.max_iters, interpret=interpret,
                    plane_dtype=pd,
                )

            def apply(key, w, p):
                return rejection_tpu_apply(
                    key, w, p, max_iters=self.max_iters, interpret=interpret,
                    plane_dtype=pd,
                )

            def apply_batch(key, w, p):
                return rejection_tpu_apply_batch(
                    key, w, p, max_iters=self.max_iters, interpret=interpret,
                    plane_dtype=pd,
                )

            def apply_rows(keys, w, p):
                return rejection_tpu_apply_rows(
                    keys, w, p, max_iters=self.max_iters, interpret=interpret,
                    plane_dtype=pd,
                )

            def step(key, lw, p, thr):
                return rejection_tpu_step(
                    key, lw, p, thr, max_iters=self.max_iters, interpret=interpret,
                    plane_dtype=pd,
                )

            def step_rows(keys, lw, p, thr):
                return rejection_tpu_step_rows(
                    keys, lw, p, thr, max_iters=self.max_iters, interpret=interpret,
                    plane_dtype=pd,
                )

            return Resampler(self, single, batch, apply=apply,
                             apply_batch=apply_batch, apply_rows=apply_rows,
                             step=step, step_rows=step_rows)

        def single(key, w):
            return rejection(key, w, max_iters=self.max_iters)

        single_fn, batch_fn = _maybe_jit(single, _vmap_batch(single), self.backend)
        return Resampler(self, single_fn, batch_fn)


_PREFIX_SUM_KINDS = {
    "multinomial": multinomial,
    "systematic": systematic,
    "improved_systematic": improved_systematic,
    "stratified": stratified,
    "residual": residual,
}


@dataclasses.dataclass(frozen=True)
class PrefixSumSpec(ResamplerSpec):
    """The prefix-sum family (§6.5): Algs. 7/8 + classical extras.

    ``kind`` selects the algorithm; none takes an iteration count (the
    family's whole point — one cumsum, one search)."""

    kind: str = "systematic"
    backend: str = "reference"
    plane_dtype: str = "float32"
    guard: str = "off"

    def __post_init__(self):
        if self.kind not in _PREFIX_SUM_KINDS:
            hint = difflib.get_close_matches(str(self.kind), _PREFIX_SUM_KINDS, n=1)
            did_you_mean = f" — did you mean {hint[0]!r}?" if hint else ""
            raise ValueError(
                f"PrefixSumSpec.kind must be one of {sorted(_PREFIX_SUM_KINDS)}; "
                f"got {self.kind!r}{did_you_mean}"
            )
        _check_backend(self.backend, "PrefixSumSpec")
        _check_plane_dtype(self.plane_dtype, "PrefixSumSpec")
        check_guard_policy(self.guard, "PrefixSumSpec")

    @property
    def name(self) -> str:
        return self.kind

    def build(self) -> Resampler:
        if self.backend in PALLAS_BACKENDS:
            from repro.kernels.prefix_sum.ops import (
                prefix_resample_tpu,
                prefix_resample_tpu_apply,
                prefix_resample_tpu_step,
            )

            interpret = self.backend == "pallas_interpret"
            kind = self.kind
            pd = self.plane_dtype

            def single(key, w):
                return prefix_resample_tpu(key, w, kind, interpret=interpret,
                                           plane_dtype=pd)

            def batch(key, w):
                # Scan + search per row under lax.map (row b == single with
                # split(key, B)[b], the §4 contract).
                keys = split_batch_keys(key, w.shape[0])
                return jax.lax.map(lambda kw: single(kw[0], kw[1]), (keys, w))

            def apply(key, w, p):
                return prefix_resample_tpu_apply(key, w, p, kind, interpret=interpret,
                                                 plane_dtype=pd)

            def apply_batch(key, w, p):
                keys = split_batch_keys(key, w.shape[0])
                return jax.lax.map(lambda kwp: apply(*kwp), (keys, w, p))

            def apply_rows(keys, w, p):
                return jax.lax.map(lambda kwp: apply(*kwp), (keys, w, p))

            def step(key, lw, p, thr):
                return prefix_resample_tpu_step(
                    key, lw, p, thr, kind, interpret=interpret, plane_dtype=pd
                )

            def step_rows(keys, lw, p, thr):
                # No leading-batch-grid step kernel for this family yet:
                # the bank form maps the single-launch step (same shape as
                # apply_rows above).
                return jax.lax.map(
                    lambda klp: step(klp[0], klp[1], klp[2], thr), (keys, lw, p)
                )

            return Resampler(self, single, batch, apply=apply,
                             apply_batch=apply_batch, apply_rows=apply_rows,
                             step=step, step_rows=step_rows)

        fn = _PREFIX_SUM_KINDS[self.kind]

        def single(key, w):
            return fn(key, w)

        single_fn, batch_fn = _maybe_jit(single, _vmap_batch(single), self.backend)
        return Resampler(self, single_fn, batch_fn)


for _cls in (
    MegopolisSpec,
    MetropolisSpec,
    MetropolisC1Spec,
    MetropolisC2Spec,
    RejectionSpec,
    PrefixSumSpec,
):
    jax.tree_util.register_static(_cls)


# ----------------------------------------------------------------------------
# The ONE family table: registry name -> (spec constructor kwargs, legacy fns).
# Everything name-keyed (spec_from_name, get_resampler, get_resampler_batch,
# list_resamplers) derives from this single surface.
# ----------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class _Family:
    spec_cls: type
    spec_fixed: Tuple[Tuple[str, Any], ...]  # kwargs frozen into the name
    legacy_single: Callable
    legacy_batch: Callable


_FAMILIES = {
    "megopolis": _Family(MegopolisSpec, (), megopolis, megopolis_batch),
    "metropolis": _Family(MetropolisSpec, (), metropolis, metropolis_batch),
    "metropolis_c1": _Family(MetropolisC1Spec, (), metropolis_c1, metropolis_c1_batch),
    "metropolis_c2": _Family(MetropolisC2Spec, (), metropolis_c2, metropolis_c2_batch),
    "rejection": _Family(RejectionSpec, (), rejection, rejection_batch),
    **{
        kind: _Family(
            PrefixSumSpec,
            (("kind", kind),),
            _PREFIX_SUM_KINDS[kind],
            {
                "multinomial": multinomial_batch,
                "systematic": systematic_batch,
                "improved_systematic": improved_systematic_batch,
                "stratified": stratified_batch,
                "residual": residual_batch,
            }[kind],
        )
        for kind in _PREFIX_SUM_KINDS
    },
}


def _unknown_name_error(name: str) -> KeyError:
    choices = sorted(_FAMILIES)
    hint = difflib.get_close_matches(str(name), choices, n=1)
    did_you_mean = f" — did you mean {hint[0]!r}?" if hint else ""
    return KeyError(f"unknown resampler {name!r}{did_you_mean}; choices: {choices}")


def _family(name: str) -> _Family:
    try:
        return _FAMILIES[name]
    except KeyError:
        raise _unknown_name_error(name) from None


def spec_from_name(name: str, **kwargs) -> ResamplerSpec:
    """Build the typed spec for a registry name: ``spec_from_name('megopolis',
    num_iters=24)`` == ``MegopolisSpec(num_iters=24)``.

    For legacy API uniformity a ``num_iters`` kwarg is tolerated (and
    dropped) on iteration-free families — the prefix-sum and rejection
    entries always ignored it.  Any other unknown kwarg raises eagerly.
    """
    fam = _family(name)
    fields = {f.name for f in dataclasses.fields(fam.spec_cls)}
    if "num_iters" not in fields:
        kwargs.pop("num_iters", None)
    unknown = sorted(set(kwargs) - fields)
    if unknown:
        raise TypeError(
            f"{name}: unknown spec argument(s) {unknown}; "
            f"{fam.spec_cls.__name__} fields are {sorted(fields)}"
        )
    return fam.spec_cls(**dict(fam.spec_fixed), **kwargs)


def spec_for_backend(
    name: str, backend: str, *, num_iters: Union[int, str] = 16,
    max_iters: int = 64, plane_dtype: str = "float32", guard: str = "off",
) -> ResamplerSpec:
    """A kernel-legal spec for any (family, backend) cell of the matrix.

    Sweep-driver convenience: fills in the tile-fixed geometry the pallas
    kernels require (``segment=KERNEL_SEGMENT`` for Megopolis,
    ``partition_size_bytes=KERNEL_PARTITION_BYTES`` for C1/C2) so drivers
    iterating family × backend (benchmarks/ais_bench.py, tests/test_ais.py)
    don't each re-encode the legality table.  ``tests/test_backend_parity.py``
    deliberately keeps its own copy — the parity gate pins the contract
    independently of this helper.
    """
    pallas = backend in PALLAS_BACKENDS
    fam = _family(name)
    if fam.spec_cls is MegopolisSpec:
        return MegopolisSpec(num_iters=num_iters,
                             segment=KERNEL_SEGMENT if pallas else DEFAULT_SEGMENT,
                             backend=backend, plane_dtype=plane_dtype,
                             guard=guard)
    if fam.spec_cls in (MetropolisC1Spec, MetropolisC2Spec):
        return fam.spec_cls(
            num_iters=num_iters,
            partition_size_bytes=KERNEL_PARTITION_BYTES if pallas else 128,
            backend=backend, plane_dtype=plane_dtype, guard=guard,
        )
    if fam.spec_cls is RejectionSpec:
        return RejectionSpec(max_iters=max_iters, backend=backend,
                             plane_dtype=plane_dtype, guard=guard)
    if fam.spec_cls is MetropolisSpec:
        return MetropolisSpec(num_iters=num_iters, backend=backend,
                              plane_dtype=plane_dtype, guard=guard)
    return PrefixSumSpec(kind=name, backend=backend, plane_dtype=plane_dtype,
                         guard=guard)


def coerce_spec(resampler: Union[str, ResamplerSpec], /, **defaults) -> ResamplerSpec:
    """Normalise ``str | ResamplerSpec`` to a spec, applying ``defaults`` only
    where the family actually has the field.

    The uniform-call-site helper: ``coerce_spec(name_or_spec, num_iters=b,
    segment=s)`` configures Megopolis/Metropolis variants and leaves the
    prefix-sum family untouched — no per-algorithm conditionals at call
    sites.  A spec passed in is returned with the same field filtering, so
    explicit specs can still be bulk-configured by a sweep driver.
    """
    spec = spec_from_name(resampler) if isinstance(resampler, str) else resampler
    if not isinstance(spec, ResamplerSpec):
        raise TypeError(
            f"expected a registry name or ResamplerSpec; got {type(resampler).__name__}"
        )
    fields = {f.name for f in dataclasses.fields(spec)}
    applicable = {k: v for k, v in defaults.items() if k in fields}
    return spec.replace(**applicable) if applicable else spec


def list_resamplers() -> list:
    return sorted(_FAMILIES)


def get_resampler(name: str) -> Callable:
    """Legacy lookup: ``fn(key, weights, num_iters, **kw) -> int32[N]``.

    .. deprecated:: prefer ``spec_from_name(name, **kw).build()`` — the spec
       carries hyperparameters and backend, so call sites stop threading
       ``num_iters``/kwargs.  This shim resolves through the same family
       table and returns the reference implementation unchanged.
    """
    return _family(name).legacy_single


def get_resampler_batch(name: str) -> Callable:
    """Legacy batched lookup (weights[B, N] -> int32[B, N]).

    .. deprecated:: prefer ``spec_from_name(name, **kw).build().batch`` —
       same family table, same reference implementation.
    """
    return _family(name).legacy_batch


# ---------------------------------------------------------------------------
# Static contracts (DESIGN.md §13)
#
# The declared per-cell invariants the analyzer (repro.analysis) audits the
# traced jaxprs against.  They live HERE — next to the registry — so adding
# a family forces the author to declare its launch budget in the same
# commit, and the analyzer can never drift from the registry's cell set.
# ---------------------------------------------------------------------------

#: Every registered entry point of a built ``Resampler``, audited per cell.
ENTRY_POINTS = (
    "call",
    "batch",
    "batch_rows",
    "apply",
    "apply_batch",
    "apply_rows",
    "step",
    "step_rows",
)

# Launch budgets on the pallas backends, per family shape (DESIGN.md §2/§11/
# §12).  Direct families (Megopolis/Metropolis/C1/C2/rejection) are ONE
# launch everywhere.  The prefix-sum family pays a normalise+cumsum launch
# before the search launch, except ``step``/``step_rows`` — the fused SMC
# step folds everything into one launch for EVERY family (the §12 tentpole).
# Residual additionally pays the deterministic-copy + count launches.
_DIRECT_BUDGET = {entry: 1 for entry in ENTRY_POINTS}
_PREFIX_BUDGET = {entry: 2 for entry in ENTRY_POINTS} | {"step": 1, "step_rows": 1}
_RESIDUAL_BUDGET = {
    "call": 5,
    "batch": 5,
    "batch_rows": 5,
    "apply": 4,
    "apply_batch": 4,
    "apply_rows": 4,
    "step": 1,
    "step_rows": 1,
}

LAUNCH_BUDGETS = {
    "megopolis": _DIRECT_BUDGET,
    "metropolis": _DIRECT_BUDGET,
    "metropolis_c1": _DIRECT_BUDGET,
    "metropolis_c2": _DIRECT_BUDGET,
    "rejection": _DIRECT_BUDGET,
    "multinomial": _PREFIX_BUDGET,
    "systematic": _PREFIX_BUDGET,
    "improved_systematic": _PREFIX_BUDGET,
    "stratified": _PREFIX_BUDGET,
    "residual": _RESIDUAL_BUDGET,
}


def launch_budget(name: str, backend: str, entry: str) -> int:
    """Declared max ``pallas_call`` count for one (family, backend, entry)
    cell.  The reference/xla backends are pure XLA by construction: 0."""
    if entry not in ENTRY_POINTS:
        raise KeyError(f"unknown entry point {entry!r}; choices: {ENTRY_POINTS}")
    if backend not in BACKENDS:
        raise KeyError(f"unknown backend {backend!r}; choices: {BACKENDS}")
    if backend not in PALLAS_BACKENDS:
        return 0
    try:
        return LAUNCH_BUDGETS[name][entry]
    except KeyError:
        raise KeyError(
            f"family {name!r} has no declared launch budget — every family in "
            "_FAMILIES must have a LAUNCH_BUDGETS row (DESIGN.md §13)"
        ) from None


def contract_cells(families=None, backends=None, entries=None):
    """Enumerate the audited (family, backend, entry) cells.

    The analyzer's cell source — driven off the same ``_FAMILIES`` registry
    as ``spec_for_backend`` so a newly registered family is audited (and
    must declare budgets) automatically.
    """
    for name in families if families is not None else list_resamplers():
        _family(name)  # raise (with the registry's nearest-match hint) early
        for backend in backends if backends is not None else BACKENDS:
            for entry in entries if entries is not None else ENTRY_POINTS:
                yield name, backend, entry
