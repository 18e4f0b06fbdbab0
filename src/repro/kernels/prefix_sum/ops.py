"""Public wrappers: prefix-sum scan + the kernel-lane prefix-sum resamplers.

``prefix_sum_tpu`` is the raw 1-D inclusive scan.  ``prefix_resample_tpu``
composes the family's two memory-bound stages — block-scan CDF, then
coalesced binary search (``search.py``) — into the five registry kinds
(multinomial / systematic / improved_systematic / stratified / residual).

Randomness placement: the family's uniforms are drawn OUTSIDE the kernels
with ``jax.random``, by the *identical formulas* as the reference
implementations in ``repro.core.resamplers.prefix_sum`` (same key usage,
same strata arithmetic).  The kernels accelerate the O(N) memory-bound
stages; the draw is O(N) compute-bound and already fused by XLA.  The
kernel lane therefore differs from the reference lane only through the
tiled scan's f32 rounding — and is bit-exact against the ``ref.py`` oracle,
which replays that tiled scan.

``improved_systematic`` (paper Alg. 8) provably equals ``systematic``'s
searchsorted form (asserted for the reference pair in the test suite); the
bidirectional walk is a GPU warp-access pattern with no TPU analogue, so
its kernel lane IS the systematic search kernel with the same draws.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.common import (
    TILE,
    check_state_resident,
    check_vmem_resident,
    compress_plane,
    pack_state_planes,
    state_dim_of,
    state_itemsize,
    unpack_state_planes,
)
from repro.kernels.prefix_sum.prefix_sum import LANES, prefix_sum_pallas
from repro.kernels.prefix_sum.search import (
    residual_select_gather_pallas,
    searchsorted_gather_pallas,
    searchsorted_pallas,
)
from repro.kernels.prefix_sum.step import prefix_pallas_step

PREFIX_KINDS = (
    "multinomial",
    "systematic",
    "improved_systematic",
    "stratified",
    "residual",
)


def prefix_sum_tpu(x: jnp.ndarray, *, interpret: bool) -> jnp.ndarray:
    n = x.shape[0]
    if n % TILE != 0:
        raise ValueError(f"prefix_sum_tpu requires N % {TILE} == 0; got {n}")
    y2 = prefix_sum_pallas(x.reshape(n // LANES, LANES), interpret=interpret)
    return y2.reshape(n)


def searchsorted_tpu(
    cdf: jnp.ndarray, u: jnp.ndarray, *, side: str = "left", interpret: bool
) -> jnp.ndarray:
    n = cdf.shape[0]
    if n % TILE != 0 or u.shape != (n,):
        raise ValueError(
            f"searchsorted_tpu requires matching N % {TILE} == 0 shapes; "
            f"got cdf {cdf.shape}, u {u.shape}"
        )
    check_vmem_resident(
        n, "searchsorted_tpu", what="CDF",
        remedy="Use backend='reference'/'xla' for this family at larger N.",
    )
    k2 = searchsorted_pallas(
        cdf.reshape(n // LANES, LANES), u.reshape(n // LANES, LANES),
        side=side, interpret=interpret,
    )
    return k2.reshape(n)


def kind_draws(key: jax.Array, n: int, total, dtype, kind: str):
    """The family's uniform draws + search side, shared verbatim with the
    ``ref.py`` oracle.  Formulas match ``repro.core.resamplers.prefix_sum``
    exactly (same key usage, same strata arithmetic); ``total`` is the
    CDF's last element from whichever scan produced it."""
    if kind == "multinomial":
        return jax.random.uniform(key, (n,), dtype) * total, "right"
    if kind in ("systematic", "improved_systematic"):
        u0 = jax.random.uniform(key, (), dtype)
        return (jnp.arange(n, dtype=dtype) + u0) * (total / n), "left"
    if kind == "stratified":
        u = jax.random.uniform(key, (n,), dtype)
        return (jnp.arange(n, dtype=dtype) + u) * (total / n), "left"
    raise ValueError(f"no independent draw formula for kind {kind!r}")


def prefix_resample_tpu(
    key: jax.Array,
    weights: jnp.ndarray,
    kind: str = "systematic",
    *,
    interpret: bool,
    plane_dtype="float32",
) -> jnp.ndarray:
    """Resample via the scan + search kernels; returns int32[N] ancestors."""
    if kind not in PREFIX_KINDS:
        raise ValueError(f"kind must be one of {PREFIX_KINDS}; got {kind!r}")
    n = weights.shape[0]
    if n % TILE != 0:
        raise ValueError(
            f"prefix_resample_tpu requires N % {TILE} == 0 (one f32 VMEM tile); "
            f"got N={n}. Use the reference backend for unaligned N."
        )
    # The search stage keeps the CDF VMEM-resident (DESIGN.md §2) — check
    # here so the clear error comes before three scan launches.
    check_vmem_resident(
        n, "prefix_resample_tpu", what="CDF",
        remedy="Use backend='reference'/'xla' for this family at larger N.",
    )
    if kind == "residual":
        return _residual_tpu(key, weights, interpret=interpret,
                             plane_dtype=plane_dtype)
    # Only the scan INPUT travels compressed (DESIGN.md §14); the CDF the
    # scan emits — and hence every bisection boundary — is always f32.
    c = prefix_sum_tpu(compress_plane(weights, plane_dtype), interpret=interpret)
    u, side = kind_draws(key, n, c[-1], weights.dtype, kind)
    return searchsorted_tpu(c, u, side=side, interpret=interpret)


def prefix_resample_tpu_apply(
    key: jax.Array,
    weights: jnp.ndarray,
    particles: jnp.ndarray,
    kind: str = "systematic",
    *,
    interpret: bool,
    plane_dtype="float32",
):
    """Fused resample+gather for the prefix-sum family (DESIGN.md §11): the
    final search kernel also copies each slot's ancestor state from the
    resident plane stack — indices identical to ``prefix_resample_tpu``.
    ``particles``: ``[N]`` or ``[N, ...]``.  Returns ``(particles',
    ancestors)``."""
    if kind not in PREFIX_KINDS:
        raise ValueError(f"kind must be one of {PREFIX_KINDS}; got {kind!r}")
    n = weights.shape[0]
    if n % TILE != 0:
        raise ValueError(
            f"prefix_resample_tpu_apply requires N % {TILE} == 0 (one f32 VMEM "
            f"tile); got N={n}. Use the reference backend for unaligned N."
        )
    check_vmem_resident(
        n, "prefix_resample_tpu_apply", what="CDF",
        remedy="Use backend='reference'/'xla' for this family at larger N.",
    )
    check_state_resident(
        n, state_dim_of(particles, n, "prefix_resample_tpu_apply"),
        "prefix_resample_tpu_apply",
        itemsize=state_itemsize(particles, plane_dtype),
    )
    planes, state_shape = pack_state_planes(particles)
    planes = compress_plane(planes, plane_dtype)
    if kind == "residual":
        k2, out = _residual_tpu_fused(key, weights, planes, interpret=interpret,
                                      plane_dtype=plane_dtype)
    else:
        c = prefix_sum_tpu(compress_plane(weights, plane_dtype), interpret=interpret)
        u, side = kind_draws(key, n, c[-1], weights.dtype, kind)
        k2, out = searchsorted_gather_pallas(
            c.reshape(n // LANES, LANES), u.reshape(n // LANES, LANES), planes,
            side=side, interpret=interpret,
        )
    out = out.astype(particles.dtype)
    return unpack_state_planes(out, state_shape), k2.reshape(n)


def prefix_resample_tpu_step(
    key: jax.Array,
    log_weights: jnp.ndarray,
    particles: jnp.ndarray,
    ess_threshold,
    kind: str = "systematic",
    *,
    interpret: bool,
    plane_dtype="float32",
):
    """Fused SMC step for the prefix-sum family (DESIGN.md §12): normalise →
    ESS → conditional scan+search+gather in ONE launch — the family's
    biggest launch-count win (the composed residual path alone is five).
    The resample branch is bit-identical to ``prefix_resample_tpu_apply(key,
    normalise_log_weights(log_weights), particles, kind)``: the key-only
    draw bases below replicate ``kind_draws``'s key usage exactly, and the
    CDF-dependent scale is applied in-kernel over a bit-identical in-kernel
    scan.  Returns ``(particles', ancestors, stats f32[4])`` with ``stats``
    = (ess_norm, log_evidence_incr, resampled, max_weight) — DESIGN.md §15."""
    if kind not in PREFIX_KINDS:
        raise ValueError(f"kind must be one of {PREFIX_KINDS}; got {kind!r}")
    n = log_weights.shape[0]
    if n % TILE != 0:
        raise ValueError(
            f"prefix_resample_tpu_step requires N % {TILE} == 0 (one f32 VMEM "
            f"tile); got N={n}. Use the reference backend for unaligned N."
        )
    check_vmem_resident(
        n, "prefix_resample_tpu_step", what="CDF",
        remedy="Compose Resampler.step on the reference/xla backend above this size.",
    )
    check_state_resident(
        n, state_dim_of(particles, n, "prefix_resample_tpu_step"),
        "prefix_resample_tpu_step",
        itemsize=state_itemsize(particles, plane_dtype),
    )
    dtype = log_weights.dtype
    # Key-only halves of kind_draws, with IDENTICAL key usage per kind.
    if kind in ("systematic", "improved_systematic"):
        u0 = jax.random.uniform(key, (), dtype).reshape(1)
        ubase = jnp.zeros((n,), dtype)
    else:  # multinomial / stratified / residual: uniform(key, (n,))
        u0 = jnp.zeros((1,), dtype)
        ubase = jax.random.uniform(key, (n,), dtype)
    thr = jnp.asarray(ess_threshold, jnp.float32).reshape(1)
    planes, state_shape = pack_state_planes(particles)
    planes = compress_plane(planes, plane_dtype)
    lw2 = compress_plane(log_weights.reshape(n // LANES, LANES), plane_dtype)
    k2, out, stats = prefix_pallas_step(
        lw2, planes,
        ubase.reshape(n // LANES, LANES), u0, thr,
        kind=kind, interpret=interpret,
    )
    out = out.astype(particles.dtype)
    return unpack_state_planes(out, state_shape), k2.reshape(n), stats


def _residual_tpu_fused(key: jax.Array, weights: jnp.ndarray, planes, *,
                        interpret, plane_dtype="float32"):
    """The fused form of ``_residual_tpu``: same three block-scans, then ONE
    kernel runs both searches, the slot select and the state gather.  Only
    the FIRST scan's input compresses; counts and residual CDFs are derived
    f32 quantities (DESIGN.md §14)."""
    n = weights.shape[0]
    total = prefix_sum_tpu(compress_plane(weights, plane_dtype),
                           interpret=interpret)[-1]
    w = weights / total
    counts = jnp.floor(n * w)
    n_det = jnp.sum(counts).astype(jnp.int32).reshape(1)
    resid = n * w - counts

    cc = prefix_sum_tpu(counts, interpret=interpret)
    c = prefix_sum_tpu(resid, interpret=interpret)
    u = jax.random.uniform(key, (n,), weights.dtype) * c[-1]
    return residual_select_gather_pallas(
        cc.reshape(n // LANES, LANES), c.reshape(n // LANES, LANES),
        u.reshape(n // LANES, LANES), n_det, planes, interpret=interpret,
    )


def _residual_tpu(key: jax.Array, weights: jnp.ndarray, *, interpret: bool,
                  plane_dtype="float32") -> jnp.ndarray:
    """Residual resampling on the kernel lane (mirrors the reference's
    "deterministic offsets into the cumsum" form, Alg. of §6.5 extras).

    All three O(N) scans (normalising total, deterministic-copy counts,
    residual CDF) run on the block-scan kernel; both searches run on the
    search kernel.  Counts are scanned as f32 — exact for N <= 2^24."""
    n = weights.shape[0]
    total = prefix_sum_tpu(compress_plane(weights, plane_dtype),
                           interpret=interpret)[-1]
    w = weights / total
    counts = jnp.floor(n * w)  # f32 integer values
    n_det = jnp.sum(counts).astype(jnp.int32)
    resid = n * w - counts

    cc = prefix_sum_tpu(counts, interpret=interpret)
    c = prefix_sum_tpu(resid, interpret=interpret)
    slots = jnp.arange(n, dtype=jnp.int32)
    det = searchsorted_tpu(cc, slots.astype(weights.dtype), side="right", interpret=interpret)
    u = jax.random.uniform(key, (n,), weights.dtype) * c[-1]
    rnd = searchsorted_tpu(c, u, side="right", interpret=interpret)
    return jnp.where(slots < n_det, det, rnd)
