"""Resampling quality metrics (paper §5.1, eqs. 14-21).

All metrics operate on offspring vectors ``o_k[i]`` = number of offspring of
particle ``i`` in Monte Carlo run ``k`` (derived from ancestors with
``offspring_counts``).
"""

from __future__ import annotations

import jax.numpy as jnp

from repro.reduction import tree_sum


def _guarded_shift(log_w: jnp.ndarray, axis: int) -> jnp.ndarray:
    """Shift-by-max stabiliser, guarded against an all-``-inf`` row: a
    non-finite max would turn the shift into ``-inf - -inf = nan``.  For
    finite maxima the guard is a bitwise no-op (``where`` returns the same
    value), so every consumer keeps its exact pre-guard arithmetic."""
    m = jnp.max(log_w, axis=axis, keepdims=True)
    return jnp.where(jnp.isfinite(m), m, jnp.zeros_like(m))


def degenerate_log_weights(log_w: jnp.ndarray, axis: int = -1) -> jnp.ndarray:
    """The degenerate-bank flag (DESIGN.md §16): True where a row of
    log-weights carries NO usable information — all ``-inf`` (max is
    ``-inf``), any ``nan`` (propagates through ``max``), or any ``+inf``
    (infinite relative weight poisons every ratio).  One cheap reduction,
    shared by ``normalise_log_weights`` and the fused step kernels
    (``kernels/common.step_stats``) so host and kernel agree bit-for-bit
    on which banks are degenerate.  One-hot rows (``-inf`` everywhere but
    one finite entry) are NOT degenerate — they still rank particles."""
    return ~jnp.isfinite(jnp.max(log_w, axis=axis))


def normalise_log_weights(log_w: jnp.ndarray, axis: int = -1) -> jnp.ndarray:
    """Shift-by-max linear weights ``exp(log_w - max(log_w))`` — THE
    normalisation every log-weight consumer shares (filter, AIS sampler,
    SMC decoding, and the fused ``Resampler.step`` composition), so the
    fused kernels and the host path can never disagree on the weights a
    resampler sees.  The result is in [0, 1] with at least one exact 1.0
    for finite inputs.

    Degenerate rows (``degenerate_log_weights``: all ``-inf``, any
    ``nan``/``+inf``) come back UNIFORM ``1/N`` instead of the all-zero /
    nan planes the pre-§16 code produced: no ratio survives a degenerate
    bank, so uniform is the only defensible answer, and it keeps ESS and
    every downstream division finite on all backends bit-identically.
    For non-degenerate rows the fallback is a bitwise no-op (``where``
    returns the untouched value)."""
    n = log_w.shape[axis]
    deg = degenerate_log_weights(log_w, axis=axis)
    deg = jnp.expand_dims(deg, axis)
    w = jnp.exp(log_w - _guarded_shift(log_w, axis))
    return jnp.where(deg, jnp.full_like(w, 1.0 / n), w)


def degenerate_weights(w: jnp.ndarray, axis: int = -1) -> jnp.ndarray:
    """Linear-weight twin of ``degenerate_log_weights`` for the
    weights-typed entries (``__call__``/``apply``): a bank is degenerate
    when its total mass is not a positive finite number — all-zero rows
    (sum 0), any ``nan`` (sum nan), any ``±inf``.  True means no ratio
    ``w_i / Σw`` is defined and the §16 recover policy substitutes the
    uniform bank."""
    s = jnp.sum(w, axis=axis)
    return ~(jnp.isfinite(s) & (s > 0))


def _tiny_floor(dtype) -> float:
    """Smallest safe positive floor for guards in ``dtype``: 1e-30 where
    that is a normal number (f32, bf16 — both carry the 8-bit exponent),
    else the dtype's min normal (f16: ~6.1e-5).  Anything below min normal
    flushes to zero under XLA and a ``log``/division guard built on it
    silently reintroduces the ``-inf``/``inf`` it was meant to stop."""
    return max(float(jnp.finfo(dtype).tiny), 1e-30)


def log_weights_from_linear(w: jnp.ndarray) -> jnp.ndarray:
    """Log-weights from unnormalised linear weights, floored dtype-aware.

    The floor must stay in the input dtype's NORMAL range (``_tiny_floor``):
    1e-30 for f32/bf16, min normal for f16.  Centralised from the ad-hoc
    filter-diagnostic guard so filter/AIS/decode all floor identically."""
    return jnp.log(jnp.maximum(w, _tiny_floor(w.dtype)))


def effective_sample_size(log_w: jnp.ndarray, axis: int = -1) -> jnp.ndarray:
    """ESS = (Σw)² / Σw² from log-weights, shift-by-max stabilised.

    THE single-host ESS helper (the resampling trigger of `smc/decode.py`,
    `pf/filter.py` diagnostics, and the `ais/` sampler).  Weights need not
    be normalised — ESS depends only on ratios, the same property the
    Metropolis-family resamplers rely on.  The multi-host psum form lives
    in ``repro.core.distributed.effective_sample_size``.

    The fused step kernels (``kernels/common.step_stats``) re-derive this
    decomposition term for term with the same ``tree_sum`` order, so the
    on-chip ESS is bit-identical to this host value wherever the two
    backends' ``exp`` agree.
    """
    w = normalise_log_weights(log_w, axis=axis)
    s1 = tree_sum(w, axis=axis)
    s2 = tree_sum(w * w, axis=axis)
    # Dtype-aware guard: 1e-30 is a flush-to-zero subnormal in f16, which
    # would leave the degenerate-row division at inf (bitwise unchanged for
    # f32/bf16 inputs).
    return jnp.square(s1) / jnp.maximum(s2, _tiny_floor(s2.dtype))


def log_mean_weight(log_w: jnp.ndarray, axis: int = -1) -> jnp.ndarray:
    """``log(mean(exp(log_w)))`` via the same shift-by-max decomposition the
    fused step kernels run on-chip: ``m + log(Σ exp(log_w - m)) - log(N)``.

    This is the per-step log-evidence increment of SMC (the
    ``logsumexp(log_w) - log(N)`` of the AIS sampler, re-expressed so host
    and kernel share one exact f32 formula — a fused ``step`` adds a
    bit-identical increment)."""
    m = _guarded_shift(log_w, axis)
    s1 = tree_sum(jnp.exp(log_w - m), axis=axis)
    n = log_w.shape[axis]
    return (jnp.squeeze(m, axis=axis) + jnp.log(s1)) - jnp.log(jnp.float32(n))


def max_normalised_weight(log_w: jnp.ndarray, axis: int = -1) -> jnp.ndarray:
    """Largest normalised weight ``max(w) / Σw`` — the degeneracy diagnostic
    complementing ESS (a population collapsing onto one particle drives this
    toward 1.0 while ESS drives toward 1/N).

    Shares the shift-by-max decomposition of ``effective_sample_size`` term
    for term; the fused step kernels (``kernels/common.step_stats``) compute
    the same ``max(w) / max(Σw, floor)`` with the same ``tree_sum`` order
    (DESIGN.md §15).
    """
    w = normalise_log_weights(log_w, axis=axis)
    s1 = tree_sum(w, axis=axis)
    return jnp.max(w, axis=axis) / jnp.maximum(s1, _tiny_floor(s1.dtype))


def unique_ancestor_count(ancestors: jnp.ndarray, axis: int = -1) -> jnp.ndarray:
    """Survivor count: the number of DISTINCT ancestors in an int32 ancestor
    vector (Murray–Lee–Jacob's unique-particle degeneracy diagnostic).

    Sort-and-count-breaks — deliberately no ``bincount``/scatter: integer
    sort is bit-exact on every backend AND keeps the §13 census pass clean
    (a scatter indexed by a kernel's ancestor output would grade as the HBM
    round-trip the fused path forbids).  Identity ancestors count N, a fully
    collapsed population counts 1.  Works on ``[N]`` and batched ``[..., N]``
    vectors alike; returns int32."""
    s = jnp.sort(ancestors, axis=axis)
    first = jnp.ones(s.shape[:-1] + (1,), jnp.int32)
    breaks = (
        jnp.moveaxis(s, axis, -1)[..., 1:] != jnp.moveaxis(s, axis, -1)[..., :-1]
    ).astype(jnp.int32)
    return jnp.sum(jnp.concatenate([first, breaks], axis=-1), axis=-1)


def offspring_counts(ancestors: jnp.ndarray, n: int) -> jnp.ndarray:
    """o[i] = #{j : ancestors[j] == i}."""
    return jnp.bincount(ancestors, length=n)


def expected_offspring(weights: jnp.ndarray) -> jnp.ndarray:
    """N * w_i / sum(w) (the target of eq. 14)."""
    n = weights.shape[0]
    return n * weights / jnp.sum(weights)


def squared_error(offspring: jnp.ndarray, weights: jnp.ndarray) -> jnp.ndarray:
    """SE(o_k), eq. (14)."""
    return jnp.sum((offspring - expected_offspring(weights)) ** 2)


def mse(offsprings: jnp.ndarray, weights: jnp.ndarray) -> jnp.ndarray:
    """MSE over K runs, eq. (15).  ``offsprings``: int[K, N]."""
    target = expected_offspring(weights)
    return jnp.mean(jnp.sum((offsprings - target) ** 2, axis=-1))


def bias_variance(offsprings: jnp.ndarray, weights: jnp.ndarray):
    """Decomposition eqs. (16)-(20): returns (var, bias_sq, mse).

    ``offsprings``: int[K, N] over K Monte Carlo runs of one weight vector.
    """
    k = offsprings.shape[0]
    target = expected_offspring(weights)
    o_hat = jnp.mean(offsprings.astype(jnp.float32), axis=0)  # eq. 19
    # K=1 carries no variance information: eq. (17)'s k-1 denominator would
    # be 0/0 = nan.  The deviations are identically zero there, so dividing
    # by 1 instead yields the defined limit var = 0 (mse degrades to bias²).
    var = jnp.sum(jnp.sum((offsprings - o_hat) ** 2, axis=0) / max(k - 1, 1))
    bias_sq = jnp.sum((o_hat - target) ** 2)  # eq. 18
    return var, bias_sq, var + bias_sq  # eq. 16


def bias_contribution(offsprings: jnp.ndarray, weights: jnp.ndarray) -> jnp.ndarray:
    """||Bias||^2 / MSE, eq. (21)."""
    var, bias_sq, total = bias_variance(offsprings, weights)
    return bias_sq / jnp.maximum(total, 1e-30)
