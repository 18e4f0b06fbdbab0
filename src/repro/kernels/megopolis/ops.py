"""Public jit'd wrapper for the Megopolis TPU kernel.

Accepts the same ``(key, weights, num_iters)`` signature as the reference
resamplers in ``repro.core``.  Alignment contract: ``N % 1024 == 0`` (one
f32 VMEM tile); production particle counts are powers of two well above
this (the paper sweeps 2^6..2^22), and the wrapper raises a clear error
otherwise rather than silently padding (padding would perturb the
uniform-offset distribution over [0, N)).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.common import (
    TILE,
    check_vmem_resident,
    compress_plane,
    key_to_seed,
    pack_state_planes,
    plane_itemsize,
    run_fused_bank,
    run_step_bank,
    state_dim_of,
    unpack_state_planes,
)
from repro.kernels.megopolis.megopolis import (
    LANES,
    megopolis_pallas,
    megopolis_pallas_batch,
    megopolis_pallas_fused,
    megopolis_pallas_fused_rows,
    megopolis_pallas_step,
    megopolis_pallas_step_rows,
)


def megopolis_tpu(
    key: jax.Array,
    weights: jnp.ndarray,
    num_iters: int,
    *,
    interpret: bool,
    plane_dtype="float32",
) -> jnp.ndarray:
    """Resample with the Pallas Megopolis kernel; returns int32[N] ancestors.

    ``interpret=True`` runs the kernel body through the Pallas interpreter
    (CPU validation); ``interpret=False`` compiles it with Mosaic for the
    TPU.  There is no default: every caller names the mode it runs.
    """
    n = weights.shape[0]
    if n % TILE != 0:
        raise ValueError(
            f"megopolis_tpu requires N % {TILE} == 0 (one f32 VMEM tile); got N={n}. "
            "Use repro.core.megopolis for unaligned N."
        )
    key_off, key_seed = jax.random.split(key)
    offsets = jax.random.randint(key_off, (num_iters,), 0, n, dtype=jnp.int32)
    seed = key_to_seed(key_seed).reshape(1)
    w2 = compress_plane(weights.reshape(n // LANES, LANES), plane_dtype)
    k2 = megopolis_pallas(w2, offsets, seed, num_iters=num_iters, interpret=interpret)
    return k2.reshape(n)


def megopolis_tpu_batch(
    key: jax.Array,
    weights: jnp.ndarray,
    num_iters: int,
    *,
    interpret: bool,
    plane_dtype="float32",
) -> jnp.ndarray:
    """Resample a ``[B, N]`` weight bank in one kernel launch (DESIGN.md §4).

    The global offset table is drawn ONCE and shared by every row (the
    bank-level lift of Alg. 5's shared offset — one scalar-prefetch schedule
    for the whole launch); each row gets its own stateless-RNG seed, so rows
    stay statistically independent.  Returns int32[B, N] ancestors.
    """
    if weights.ndim != 2:
        raise ValueError(f"megopolis_tpu_batch expects weights[B, N]; got {weights.shape}")
    bsz, n = weights.shape
    if n % TILE != 0:
        raise ValueError(
            f"megopolis_tpu_batch requires N % {TILE} == 0 (one f32 VMEM tile); got N={n}. "
            "Use repro.core.megopolis_batch for unaligned N."
        )
    key_off, key_rows = jax.random.split(key)
    offsets = jax.random.randint(key_off, (num_iters,), 0, n, dtype=jnp.int32)
    seeds = key_to_seed(jax.random.split(key_rows, bsz))
    w3 = compress_plane(weights.reshape(bsz, n // LANES, LANES), plane_dtype)
    k3 = megopolis_pallas_batch(w3, offsets, seeds, num_iters=num_iters, interpret=interpret)
    return k3.reshape(bsz, n)


def megopolis_tpu_apply(
    key: jax.Array,
    weights: jnp.ndarray,
    particles: jnp.ndarray,
    num_iters: int,
    *,
    interpret: bool,
    plane_dtype="float32",
):
    """Fused resample+gather (DESIGN.md §11): ONE kernel launch selects the
    ancestors (identical stream to ``megopolis_tpu``) and carries each
    ancestor's state by value beside its weight, so no state cap applies.
    ``particles``: ``[N]`` or ``[N, ...]`` (trailing dims are the state).
    Returns ``(particles' , ancestors)``."""
    n = weights.shape[0]
    if n % TILE != 0:
        raise ValueError(
            f"megopolis_tpu_apply requires N % {TILE} == 0 (one f32 VMEM tile); got N={n}."
        )
    state_dim_of(particles, n, "megopolis_tpu_apply")  # validates the particle axis
    key_off, key_seed = jax.random.split(key)
    offsets = jax.random.randint(key_off, (num_iters,), 0, n, dtype=jnp.int32)
    seed = key_to_seed(key_seed).reshape(1)
    w2 = compress_plane(weights.reshape(n // LANES, LANES), plane_dtype)
    planes, state_shape = pack_state_planes(particles)
    planes = compress_plane(planes, plane_dtype)
    k2, out = megopolis_pallas_fused(
        w2, planes, offsets, seed, num_iters=num_iters, interpret=interpret
    )
    out = out.astype(particles.dtype)
    return unpack_state_planes(out, state_shape), k2.reshape(n)


def megopolis_tpu_apply_batch(
    key: jax.Array,
    weights: jnp.ndarray,
    particles: jnp.ndarray,
    num_iters: int,
    *,
    interpret: bool,
    plane_dtype="float32",
):
    """Fused bank launch under the ``megopolis_tpu_batch`` contract: the
    offset table is drawn ONCE (same key derivation) and shared by every
    row, per-row RNG seeds.  ``particles``: ``[B, N, ...]``.  Returns
    ``(particles'[B, N, ...], ancestors int32[B, N])``."""
    if weights.ndim != 2:
        raise ValueError(
            f"megopolis_tpu_apply_batch expects weights[B, N]; got {weights.shape}"
        )
    bsz, n = weights.shape
    if n % TILE != 0:
        raise ValueError(
            f"megopolis_tpu_apply_batch requires N % {TILE} == 0; got N={n}."
        )
    key_off, key_rows = jax.random.split(key)
    offsets = jax.random.randint(key_off, (num_iters,), 0, n, dtype=jnp.int32)
    offsets2d = jnp.broadcast_to(offsets[None, :], (bsz, num_iters))
    seeds = key_to_seed(jax.random.split(key_rows, bsz))
    return _apply_rows_launch(weights, particles, offsets2d, seeds,
                              num_iters=num_iters, interpret=interpret,
                              who="megopolis_tpu_apply_batch",
                              plane_dtype=plane_dtype)


def megopolis_tpu_apply_rows(
    keys: jax.Array,
    weights: jnp.ndarray,
    particles: jnp.ndarray,
    num_iters: int,
    *,
    interpret: bool,
    plane_dtype="float32",
):
    """Fused bank launch over EXPLICIT per-row keys (the filter-bank path):
    each row derives its own offset table and seed exactly as the single
    ``megopolis_tpu_apply`` would, so row b is bit-identical to the single
    call with ``keys[b]`` — in ONE leading-batch-grid launch."""
    if weights.ndim != 2:
        raise ValueError(
            f"megopolis_tpu_apply_rows expects weights[B, N]; got {weights.shape}"
        )
    bsz, n = weights.shape
    if n % TILE != 0:
        raise ValueError(
            f"megopolis_tpu_apply_rows requires N % {TILE} == 0; got N={n}."
        )
    split = jax.vmap(jax.random.split)(keys)
    keys_off, keys_seed = split[:, 0], split[:, 1]
    offsets2d = jax.vmap(
        lambda k: jax.random.randint(k, (num_iters,), 0, n, dtype=jnp.int32)
    )(keys_off)
    seeds = key_to_seed(keys_seed)
    return _apply_rows_launch(weights, particles, offsets2d, seeds,
                              num_iters=num_iters, interpret=interpret,
                              who="megopolis_tpu_apply_rows",
                              plane_dtype=plane_dtype)


def _apply_rows_launch(weights, particles, offsets2d, seeds, *, num_iters,
                       interpret, who, plane_dtype="float32"):
    return run_fused_bank(
        lambda w3, planes: megopolis_pallas_fused_rows(
            w3, planes, offsets2d, seeds, num_iters=num_iters, interpret=interpret
        ),
        weights, particles, who, plane_dtype=plane_dtype, state_resident=False,
    )


def megopolis_tpu_step(
    key: jax.Array,
    log_weights: jnp.ndarray,
    particles: jnp.ndarray,
    num_iters: int,
    ess_threshold,
    *,
    interpret: bool,
    plane_dtype="float32",
):
    """Fused SMC step (DESIGN.md §12): normalise → ESS → conditional
    resample → state copy in ONE kernel launch.  ``log_weights``: f32[N]
    UNNORMALISED; RNG/offset derivation is identical to
    ``megopolis_tpu_apply`` so the resample branch is bit-identical to
    ``apply(key, normalise_log_weights(log_weights), particles)``.
    Returns ``(particles', ancestors, stats f32[4])`` with ``stats`` =
    (ess_norm, log_evidence_incr, resampled, max_weight) — DESIGN.md §15."""
    n = log_weights.shape[0]
    if n % TILE != 0:
        raise ValueError(
            f"megopolis_tpu_step requires N % {TILE} == 0 (one f32 VMEM tile); got N={n}."
        )
    check_vmem_resident(n, "megopolis_tpu_step", "log-weight array",
                        remedy="Compose Resampler.step on the reference/xla backend "
                               "above this size.",
                        itemsize=plane_itemsize(plane_dtype))
    state_dim_of(particles, n, "megopolis_tpu_step")  # validates the particle axis
    key_off, key_seed = jax.random.split(key)
    offsets = jax.random.randint(key_off, (num_iters,), 0, n, dtype=jnp.int32)
    seed = key_to_seed(key_seed).reshape(1)
    thr = jnp.asarray(ess_threshold, jnp.float32).reshape(1)
    lw2 = compress_plane(log_weights.reshape(n // LANES, LANES), plane_dtype)
    planes, state_shape = pack_state_planes(particles)
    planes = compress_plane(planes, plane_dtype)
    k2, out, stats = megopolis_pallas_step(
        lw2, planes, offsets, seed, thr, num_iters=num_iters, interpret=interpret
    )
    out = out.astype(particles.dtype)
    return unpack_state_planes(out, state_shape), k2.reshape(n), stats


def megopolis_tpu_step_rows(
    keys: jax.Array,
    log_weights: jnp.ndarray,
    particles: jnp.ndarray,
    num_iters: int,
    ess_threshold,
    *,
    interpret: bool,
    plane_dtype="float32",
):
    """Fused SMC-step bank over EXPLICIT per-row keys: row b is
    bit-identical to ``megopolis_tpu_step(keys[b], ...)`` — each row takes
    its own on-chip resample decision in ONE leading-batch-grid launch.
    Returns ``(particles'[B, N, ...], ancestors int32[B, N],
    stats f32[B, 4])``."""
    if log_weights.ndim != 2:
        raise ValueError(
            f"megopolis_tpu_step_rows expects log_weights[B, N]; got {log_weights.shape}"
        )
    bsz, n = log_weights.shape
    if n % TILE != 0:
        raise ValueError(
            f"megopolis_tpu_step_rows requires N % {TILE} == 0; got N={n}."
        )
    check_vmem_resident(n, "megopolis_tpu_step_rows", "log-weight array",
                        remedy="Compose Resampler.step_rows on the reference/xla "
                               "backend above this size.",
                        itemsize=plane_itemsize(plane_dtype))
    split = jax.vmap(jax.random.split)(keys)
    keys_off, keys_seed = split[:, 0], split[:, 1]
    offsets2d = jax.vmap(
        lambda k: jax.random.randint(k, (num_iters,), 0, n, dtype=jnp.int32)
    )(keys_off)
    seeds = key_to_seed(keys_seed)
    thr = jnp.asarray(ess_threshold, jnp.float32).reshape(1)
    return run_step_bank(
        lambda lw3, planes: megopolis_pallas_step_rows(
            lw3, planes, offsets2d, seeds, thr, num_iters=num_iters,
            interpret=interpret
        ),
        log_weights, particles, "megopolis_tpu_step_rows",
        plane_dtype=plane_dtype, state_resident=False,
    )
