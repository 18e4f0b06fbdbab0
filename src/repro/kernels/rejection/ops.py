"""Public wrappers for the rejection TPU kernel (VMEM-resident baseline)."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.resamplers.batched import split_batch_keys
from repro.kernels.common import (
    check_state_resident,
    check_tile_aligned,
    check_vmem_resident,
    compress_plane,
    key_to_seed,
    pack_state_planes,
    plane_itemsize,
    run_fused_bank,
    run_step_bank,
    state_dim_of,
    state_itemsize,
    unpack_state_planes,
)
from repro.kernels.rejection.rejection import (
    LANES,
    rejection_pallas,
    rejection_pallas_batch,
    rejection_pallas_fused,
    rejection_pallas_fused_batch,
    rejection_pallas_step,
    rejection_pallas_step_rows,
)


def _check(n: int, who: str, plane_dtype="float32"):
    # Same residency cap as the Metropolis strawman (random full-array gather).
    check_tile_aligned(n, who)
    check_vmem_resident(n, who, itemsize=plane_itemsize(plane_dtype))


def rejection_tpu(
    key: jax.Array,
    weights: jnp.ndarray,
    *,
    max_iters: int = 1024,
    interpret: bool,
    plane_dtype="float32",
) -> jnp.ndarray:
    n = weights.shape[0]
    _check(n, "rejection_tpu", plane_dtype)
    seed = key_to_seed(key).reshape(1)
    w2 = compress_plane(weights.reshape(n // LANES, LANES), plane_dtype)
    k2 = rejection_pallas(w2, seed, max_iters=max_iters, interpret=interpret)
    return k2.reshape(n)


def rejection_tpu_batch(
    key: jax.Array,
    weights: jnp.ndarray,
    *,
    max_iters: int = 1024,
    interpret: bool,
    plane_dtype="float32",
) -> jnp.ndarray:
    """One ``[B, R, 128]`` launch; row b == ``rejection_tpu(split(key,B)[b],
    weights[b])`` bit-exactly (the §4 split-key contract, held on-kernel)."""
    if weights.ndim != 2:
        raise ValueError(f"rejection_tpu_batch expects weights[B, N]; got {weights.shape}")
    bsz, n = weights.shape
    _check(n, "rejection_tpu_batch", plane_dtype)
    seeds = key_to_seed(split_batch_keys(key, bsz))
    w3 = compress_plane(weights.reshape(bsz, n // LANES, LANES), plane_dtype)
    k3 = rejection_pallas_batch(w3, seeds, max_iters=max_iters, interpret=interpret)
    return k3.reshape(bsz, n)


def rejection_tpu_apply(
    key: jax.Array,
    weights: jnp.ndarray,
    particles: jnp.ndarray,
    *,
    max_iters: int = 1024,
    interpret: bool,
    plane_dtype="float32",
):
    """Fused resample+gather (DESIGN.md §11): ancestors identical to
    ``rejection_tpu``.  Returns ``(particles', ancestors)``."""
    n = weights.shape[0]
    _check(n, "rejection_tpu_apply", plane_dtype)
    check_state_resident(
        n, state_dim_of(particles, n, "rejection_tpu_apply"), "rejection_tpu_apply",
        itemsize=state_itemsize(particles, plane_dtype),
    )
    seed = key_to_seed(key).reshape(1)
    w2 = compress_plane(weights.reshape(n // LANES, LANES), plane_dtype)
    planes, state_shape = pack_state_planes(particles)
    planes = compress_plane(planes, plane_dtype)
    k2, out = rejection_pallas_fused(
        w2, planes, seed, max_iters=max_iters, interpret=interpret
    )
    out = out.astype(particles.dtype)
    return unpack_state_planes(out, state_shape), k2.reshape(n)


def _rejection_apply_bank(seeds, weights, particles, *, max_iters, interpret,
                          who, plane_dtype="float32"):
    _check(weights.shape[1], who, plane_dtype)
    return run_fused_bank(
        lambda w3, planes: rejection_pallas_fused_batch(
            w3, planes, seeds, max_iters=max_iters, interpret=interpret
        ),
        weights, particles, who, plane_dtype=plane_dtype,
    )


def rejection_tpu_apply_batch(
    key: jax.Array,
    weights: jnp.ndarray,
    particles: jnp.ndarray,
    *,
    max_iters: int = 1024,
    interpret: bool,
    plane_dtype="float32",
):
    """Fused bank launch under the §4 split-key contract; row b ==
    ``rejection_tpu_apply(split(key, B)[b], ...)`` bit-exactly."""
    if weights.ndim != 2:
        raise ValueError(
            f"rejection_tpu_apply_batch expects weights[B, N]; got {weights.shape}"
        )
    seeds = key_to_seed(split_batch_keys(key, weights.shape[0]))
    return _rejection_apply_bank(
        seeds, weights, particles, max_iters=max_iters, interpret=interpret,
        who="rejection_tpu_apply_batch", plane_dtype=plane_dtype,
    )


def rejection_tpu_step(
    key: jax.Array,
    log_weights: jnp.ndarray,
    particles: jnp.ndarray,
    ess_threshold,
    *,
    max_iters: int = 1024,
    interpret: bool,
    plane_dtype="float32",
):
    """Fused SMC step (DESIGN.md §12): normalise → ESS → conditional
    rejection chain → state copy in ONE launch; the resample branch is
    bit-identical to ``apply(key, normalise_log_weights(log_weights), ...)``.
    Returns ``(particles', ancestors, stats f32[4])`` with ``stats`` =
    (ess_norm, log_evidence_incr, resampled, max_weight) — DESIGN.md §15."""
    n = log_weights.shape[0]
    _check(n, "rejection_tpu_step", plane_dtype)
    check_state_resident(
        n, state_dim_of(particles, n, "rejection_tpu_step"), "rejection_tpu_step",
        itemsize=state_itemsize(particles, plane_dtype),
    )
    seed = key_to_seed(key).reshape(1)
    thr = jnp.asarray(ess_threshold, jnp.float32).reshape(1)
    lw2 = compress_plane(log_weights.reshape(n // LANES, LANES), plane_dtype)
    planes, state_shape = pack_state_planes(particles)
    planes = compress_plane(planes, plane_dtype)
    k2, out, stats = rejection_pallas_step(
        lw2, planes, seed, thr, max_iters=max_iters, interpret=interpret
    )
    out = out.astype(particles.dtype)
    return unpack_state_planes(out, state_shape), k2.reshape(n), stats


def rejection_tpu_step_rows(
    keys: jax.Array,
    log_weights: jnp.ndarray,
    particles: jnp.ndarray,
    ess_threshold,
    *,
    max_iters: int = 1024,
    interpret: bool,
    plane_dtype="float32",
):
    """Fused SMC-step bank over EXPLICIT per-row keys; row b ==
    ``rejection_tpu_step(keys[b], ...)`` bit-exactly, ONE launch.
    Returns ``(particles'[B, N, ...], ancestors, stats f32[B, 4])``."""
    if log_weights.ndim != 2:
        raise ValueError(
            f"rejection_tpu_step_rows expects log_weights[B, N]; got {log_weights.shape}"
        )
    _check(log_weights.shape[1], "rejection_tpu_step_rows", plane_dtype)
    seeds = key_to_seed(keys)
    thr = jnp.asarray(ess_threshold, jnp.float32).reshape(1)
    return run_step_bank(
        lambda lw3, planes: rejection_pallas_step_rows(
            lw3, planes, seeds, thr, max_iters=max_iters, interpret=interpret
        ),
        log_weights, particles, "rejection_tpu_step_rows",
        plane_dtype=plane_dtype,
    )


def rejection_tpu_apply_rows(
    keys: jax.Array,
    weights: jnp.ndarray,
    particles: jnp.ndarray,
    *,
    max_iters: int = 1024,
    interpret: bool,
    plane_dtype="float32",
):
    """Fused bank launch over EXPLICIT per-row keys; row b ==
    ``rejection_tpu_apply(keys[b], ...)`` bit-exactly, ONE launch."""
    if weights.ndim != 2:
        raise ValueError(
            f"rejection_tpu_apply_rows expects weights[B, N]; got {weights.shape}"
        )
    return _rejection_apply_bank(
        key_to_seed(keys), weights, particles, max_iters=max_iters,
        interpret=interpret, who="rejection_tpu_apply_rows",
        plane_dtype=plane_dtype,
    )
