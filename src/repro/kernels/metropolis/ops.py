"""Public wrappers for the Metropolis-family TPU kernels (Algs. 2-4).

``metropolis_tpu`` / ``metropolis_tpu_batch`` are the VMEM-resident
random-gather strawman; ``metropolis_c1_tpu`` / ``metropolis_c2_tpu`` are
the Dülger segment-local variants whose partition is one (8, 128) VMEM
tile (``c1c2.py``).  Batch contract (DESIGN.md §4): the key is split once
along the batch axis and row ``b`` is bit-identical to the single call
with ``split_batch_keys(key, B)[b]``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.resamplers.batched import split_batch_keys
from repro.kernels.common import (  # noqa: F401  (MAX_VMEM_PARTICLES re-export)
    MAX_VMEM_PARTICLES,
    TILE,
    check_state_resident,
    check_tile_aligned,
    check_vmem_resident,
    compress_plane,
    key_to_seed,
    pack_state_planes,
    plane_itemsize,
    run_fused_bank,
    state_dim_of,
    state_itemsize,
    unpack_state_planes,
)
from repro.kernels.common import run_step_bank
from repro.kernels.metropolis.c1c2 import (
    metropolis_c1_pallas,
    metropolis_c1_pallas_fused,
    metropolis_c1_pallas_step,
    metropolis_c2_pallas,
    metropolis_c2_pallas_fused,
    metropolis_c2_pallas_step,
)
from repro.kernels.metropolis.metropolis import (
    LANES,
    metropolis_pallas,
    metropolis_pallas_batch,
    metropolis_pallas_fused,
    metropolis_pallas_fused_batch,
    metropolis_pallas_step,
    metropolis_pallas_step_rows,
)


def metropolis_tpu(
    key: jax.Array,
    weights: jnp.ndarray,
    num_iters: int,
    *,
    interpret: bool,
    plane_dtype="float32",
) -> jnp.ndarray:
    n = weights.shape[0]
    check_tile_aligned(n, "metropolis_tpu")
    check_vmem_resident(n, "metropolis_tpu", itemsize=plane_itemsize(plane_dtype))
    seed = key_to_seed(key).reshape(1)
    w2 = compress_plane(weights.reshape(n // LANES, LANES), plane_dtype)
    k2 = metropolis_pallas(w2, seed, num_iters=num_iters, interpret=interpret)
    return k2.reshape(n)


def metropolis_tpu_batch(
    key: jax.Array,
    weights: jnp.ndarray,
    num_iters: int,
    *,
    interpret: bool,
    plane_dtype="float32",
) -> jnp.ndarray:
    """One ``[B, R, 128]`` launch; row b == ``metropolis_tpu(split(key,B)[b],
    weights[b])`` bit-exactly (the §4 split-key contract, held on-kernel)."""
    if weights.ndim != 2:
        raise ValueError(f"metropolis_tpu_batch expects weights[B, N]; got {weights.shape}")
    bsz, n = weights.shape
    check_tile_aligned(n, "metropolis_tpu_batch")
    check_vmem_resident(n, "metropolis_tpu_batch",
                        itemsize=plane_itemsize(plane_dtype))
    seeds = key_to_seed(split_batch_keys(key, bsz))
    w3 = compress_plane(weights.reshape(bsz, n // LANES, LANES), plane_dtype)
    k3 = metropolis_pallas_batch(w3, seeds, num_iters=num_iters, interpret=interpret)
    return k3.reshape(bsz, n)


def _pack_single(weights, particles, who, *, weights_resident: bool = True,
                 plane_dtype="float32"):
    n = weights.shape[0]
    check_tile_aligned(n, who)
    if weights_resident:  # C1/C2 only keep partition tiles resident
        check_vmem_resident(n, who, itemsize=plane_itemsize(plane_dtype))
    check_state_resident(n, state_dim_of(particles, n, who), who,
                         itemsize=state_itemsize(particles, plane_dtype))
    planes, state_shape = pack_state_planes(particles)
    w2 = compress_plane(weights.reshape(n // LANES, LANES), plane_dtype)
    return n, w2, compress_plane(planes, plane_dtype), state_shape


def metropolis_tpu_apply(
    key: jax.Array,
    weights: jnp.ndarray,
    particles: jnp.ndarray,
    num_iters: int,
    *,
    interpret: bool,
    plane_dtype="float32",
):
    """Fused resample+gather (DESIGN.md §11): ancestors identical to
    ``metropolis_tpu``; the state copy happens in VMEM.  Returns
    ``(particles', ancestors)``."""
    n, w2, planes, state_shape = _pack_single(
        weights, particles, "metropolis_tpu_apply", plane_dtype=plane_dtype
    )
    seed = key_to_seed(key).reshape(1)
    k2, out = metropolis_pallas_fused(
        w2, planes, seed, num_iters=num_iters, interpret=interpret
    )
    out = out.astype(particles.dtype)
    return unpack_state_planes(out, state_shape), k2.reshape(n)


def _metropolis_apply_bank(seeds, weights, particles, num_iters, *, interpret,
                           who, plane_dtype="float32"):
    n = weights.shape[1]
    check_tile_aligned(n, who)
    check_vmem_resident(n, who, itemsize=plane_itemsize(plane_dtype))
    return run_fused_bank(
        lambda w3, planes: metropolis_pallas_fused_batch(
            w3, planes, seeds, num_iters=num_iters, interpret=interpret
        ),
        weights, particles, who, plane_dtype=plane_dtype,
    )


def metropolis_tpu_apply_batch(
    key: jax.Array,
    weights: jnp.ndarray,
    particles: jnp.ndarray,
    num_iters: int,
    *,
    interpret: bool,
    plane_dtype="float32",
):
    """Fused ``[B, R, 128]`` bank launch under the §4 split-key contract;
    row b == ``metropolis_tpu_apply(split(key, B)[b], ...)`` bit-exactly."""
    if weights.ndim != 2:
        raise ValueError(
            f"metropolis_tpu_apply_batch expects weights[B, N]; got {weights.shape}"
        )
    seeds = key_to_seed(split_batch_keys(key, weights.shape[0]))
    return _metropolis_apply_bank(
        seeds, weights, particles, num_iters, interpret=interpret,
        who="metropolis_tpu_apply_batch", plane_dtype=plane_dtype,
    )


def metropolis_tpu_apply_rows(
    keys: jax.Array,
    weights: jnp.ndarray,
    particles: jnp.ndarray,
    num_iters: int,
    *,
    interpret: bool,
    plane_dtype="float32",
):
    """Fused bank launch over EXPLICIT per-row keys (the filter-bank path);
    row b == ``metropolis_tpu_apply(keys[b], ...)`` bit-exactly, in ONE
    leading-batch-grid launch."""
    if weights.ndim != 2:
        raise ValueError(
            f"metropolis_tpu_apply_rows expects weights[B, N]; got {weights.shape}"
        )
    return _metropolis_apply_bank(
        key_to_seed(keys), weights, particles, num_iters, interpret=interpret,
        who="metropolis_tpu_apply_rows", plane_dtype=plane_dtype,
    )


def metropolis_tpu_step(
    key: jax.Array,
    log_weights: jnp.ndarray,
    particles: jnp.ndarray,
    num_iters: int,
    ess_threshold,
    *,
    interpret: bool,
    plane_dtype="float32",
):
    """Fused SMC step (DESIGN.md §12): normalise → ESS → conditional Alg. 2
    resample → state copy in ONE launch; the resample branch is
    bit-identical to ``apply(key, normalise_log_weights(log_weights), ...)``.
    Returns ``(particles', ancestors, stats f32[4])`` with ``stats`` =
    (ess_norm, log_evidence_incr, resampled, max_weight) — DESIGN.md §15."""
    n, lw2, planes, state_shape = _pack_single(
        log_weights, particles, "metropolis_tpu_step", plane_dtype=plane_dtype
    )
    seed = key_to_seed(key).reshape(1)
    thr = jnp.asarray(ess_threshold, jnp.float32).reshape(1)
    k2, out, stats = metropolis_pallas_step(
        lw2, planes, seed, thr, num_iters=num_iters, interpret=interpret
    )
    out = out.astype(particles.dtype)
    return unpack_state_planes(out, state_shape), k2.reshape(n), stats


def metropolis_tpu_step_rows(
    keys: jax.Array,
    log_weights: jnp.ndarray,
    particles: jnp.ndarray,
    num_iters: int,
    ess_threshold,
    *,
    interpret: bool,
    plane_dtype="float32",
):
    """Fused SMC-step bank over EXPLICIT per-row keys; row b ==
    ``metropolis_tpu_step(keys[b], ...)`` bit-exactly, ONE launch.
    Returns ``(particles'[B, N, ...], ancestors, stats f32[B, 4])``."""
    if log_weights.ndim != 2:
        raise ValueError(
            f"metropolis_tpu_step_rows expects log_weights[B, N]; got {log_weights.shape}"
        )
    n = log_weights.shape[1]
    check_tile_aligned(n, "metropolis_tpu_step_rows")
    check_vmem_resident(n, "metropolis_tpu_step_rows",
                        itemsize=plane_itemsize(plane_dtype))
    seeds = key_to_seed(keys)
    thr = jnp.asarray(ess_threshold, jnp.float32).reshape(1)
    return run_step_bank(
        lambda lw3, planes: metropolis_pallas_step_rows(
            lw3, planes, seeds, thr, num_iters=num_iters, interpret=interpret
        ),
        log_weights, particles, "metropolis_tpu_step_rows",
        plane_dtype=plane_dtype,
    )


def metropolis_c1_tpu(
    key: jax.Array,
    weights: jnp.ndarray,
    num_iters: int,
    *,
    interpret: bool,
    plane_dtype="float32",
) -> jnp.ndarray:
    """Alg. 3 at tile granularity: ONE partition tile per own-tile, kept for
    all iterations.  Key split mirrors the reference ``metropolis_c1``:
    partition choice from the first subkey, accept/reject stream from the
    second."""
    n = weights.shape[0]
    check_tile_aligned(n, "metropolis_c1_tpu")
    num_tiles = n // TILE
    kp, kloop = jax.random.split(key)
    partitions = jax.random.randint(kp, (num_tiles,), 0, num_tiles, dtype=jnp.int32)
    seed = key_to_seed(kloop).reshape(1)
    w2 = compress_plane(weights.reshape(n // LANES, LANES), plane_dtype)
    k2 = metropolis_c1_pallas(w2, partitions, seed, num_iters=num_iters, interpret=interpret)
    return k2.reshape(n)


def metropolis_c2_tpu(
    key: jax.Array,
    weights: jnp.ndarray,
    num_iters: int,
    *,
    interpret: bool,
    plane_dtype="float32",
) -> jnp.ndarray:
    """Alg. 4 at tile granularity: a FRESH partition tile per (tile,
    iteration) — table laid out row-major by tile, ``p[t * B + b]``."""
    n = weights.shape[0]
    check_tile_aligned(n, "metropolis_c2_tpu")
    num_tiles = n // TILE
    kp, kloop = jax.random.split(key)
    partitions = jax.random.randint(
        kp, (num_tiles * num_iters,), 0, num_tiles, dtype=jnp.int32
    )
    seed = key_to_seed(kloop).reshape(1)
    w2 = compress_plane(weights.reshape(n // LANES, LANES), plane_dtype)
    k2 = metropolis_c2_pallas(w2, partitions, seed, num_iters=num_iters, interpret=interpret)
    return k2.reshape(n)


def metropolis_c1_tpu_apply(
    key: jax.Array,
    weights: jnp.ndarray,
    particles: jnp.ndarray,
    num_iters: int,
    *,
    interpret: bool,
    plane_dtype="float32",
):
    """Fused C1 resample+gather; same key split as ``metropolis_c1_tpu``.
    Returns ``(particles', ancestors)``."""
    n, w2, planes, state_shape = _pack_single(
        weights, particles, "metropolis_c1_tpu_apply", weights_resident=False,
        plane_dtype=plane_dtype,
    )
    num_tiles = n // TILE
    kp, kloop = jax.random.split(key)
    partitions = jax.random.randint(kp, (num_tiles,), 0, num_tiles, dtype=jnp.int32)
    seed = key_to_seed(kloop).reshape(1)
    k2, out = metropolis_c1_pallas_fused(
        w2, planes, partitions, seed, num_iters=num_iters, interpret=interpret
    )
    out = out.astype(particles.dtype)
    return unpack_state_planes(out, state_shape), k2.reshape(n)


def metropolis_c2_tpu_apply(
    key: jax.Array,
    weights: jnp.ndarray,
    particles: jnp.ndarray,
    num_iters: int,
    *,
    interpret: bool,
    plane_dtype="float32",
):
    """Fused C2 resample+gather; same key split as ``metropolis_c2_tpu``.
    Returns ``(particles', ancestors)``."""
    n, w2, planes, state_shape = _pack_single(
        weights, particles, "metropolis_c2_tpu_apply", weights_resident=False,
        plane_dtype=plane_dtype,
    )
    num_tiles = n // TILE
    kp, kloop = jax.random.split(key)
    partitions = jax.random.randint(
        kp, (num_tiles * num_iters,), 0, num_tiles, dtype=jnp.int32
    )
    seed = key_to_seed(kloop).reshape(1)
    k2, out = metropolis_c2_pallas_fused(
        w2, planes, partitions, seed, num_iters=num_iters, interpret=interpret
    )
    out = out.astype(particles.dtype)
    return unpack_state_planes(out, state_shape), k2.reshape(n)


def metropolis_c1_tpu_step(
    key: jax.Array,
    log_weights: jnp.ndarray,
    particles: jnp.ndarray,
    num_iters: int,
    ess_threshold,
    *,
    interpret: bool,
    plane_dtype="float32",
):
    """Fused C1 SMC step; same key split as ``metropolis_c1_tpu``.  Unlike
    the C1 apply form, the step prelude needs the WHOLE log-weight array
    resident (the ESS reduction), so the VMEM particle cap applies here.
    Returns ``(particles', ancestors, stats f32[4])``."""
    n, lw2, planes, state_shape = _pack_single(
        log_weights, particles, "metropolis_c1_tpu_step", plane_dtype=plane_dtype
    )
    num_tiles = n // TILE
    kp, kloop = jax.random.split(key)
    partitions = jax.random.randint(kp, (num_tiles,), 0, num_tiles, dtype=jnp.int32)
    seed = key_to_seed(kloop).reshape(1)
    thr = jnp.asarray(ess_threshold, jnp.float32).reshape(1)
    k2, out, stats = metropolis_c1_pallas_step(
        lw2, planes, partitions, seed, thr, num_iters=num_iters, interpret=interpret
    )
    out = out.astype(particles.dtype)
    return unpack_state_planes(out, state_shape), k2.reshape(n), stats


def metropolis_c2_tpu_step(
    key: jax.Array,
    log_weights: jnp.ndarray,
    particles: jnp.ndarray,
    num_iters: int,
    ess_threshold,
    *,
    interpret: bool,
    plane_dtype="float32",
):
    """Fused C2 SMC step; same key split as ``metropolis_c2_tpu``; the
    whole-log-weight residency cap applies as for the C1 step.
    Returns ``(particles', ancestors, stats f32[4])``."""
    n, lw2, planes, state_shape = _pack_single(
        log_weights, particles, "metropolis_c2_tpu_step", plane_dtype=plane_dtype
    )
    num_tiles = n // TILE
    kp, kloop = jax.random.split(key)
    partitions = jax.random.randint(
        kp, (num_tiles * num_iters,), 0, num_tiles, dtype=jnp.int32
    )
    seed = key_to_seed(kloop).reshape(1)
    thr = jnp.asarray(ess_threshold, jnp.float32).reshape(1)
    k2, out, stats = metropolis_c2_pallas_step(
        lw2, planes, partitions, seed, thr, num_iters=num_iters, interpret=interpret
    )
    out = out.astype(particles.dtype)
    return unpack_state_planes(out, state_shape), k2.reshape(n), stats
