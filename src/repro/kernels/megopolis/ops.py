"""Public jit'd wrappers for the Megopolis TPU kernel.

Accepts the same ``(key, weights, num_iters)`` signature as the reference
resamplers in ``repro.core``.  Alignment contract: ``N % 1024 == 0`` (one
f32 VMEM tile); production particle counts are powers of two well above
this (the paper sweeps 2^6..2^22), and the wrapper raises a clear error
otherwise rather than silently padding (padding would perturb the
uniform-offset distribution over [0, N)).

Every entry launches the one bank grid of ``megopolis.py``; they differ in
how the keys become offset tables and seeds, ``(offsets2d int32[S, B],
seeds uint32[S])``: one key (``_one_key``, a one-row bank), one table shared
by a bank (``_shared_table``), or a key per row (``_row_keys``).
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
    megopolis_pallas_fused,
    megopolis_pallas_step,
)


def _check_aligned(n, who):
    if n % TILE != 0:
        raise ValueError(
            f"{who} requires N % {TILE} == 0 (one f32 VMEM tile); got N={n}. "
            "Use the reference or xla backend (repro.core) for unaligned N."
        )


def _check_bank(weights, who):
    """``(B, N)`` of a ``[B, N]`` bank, or a ValueError."""
    if weights.ndim != 2:
        raise ValueError(f"{who} expects a [B, N] bank; got shape {weights.shape}")
    _check_aligned(weights.shape[1], who)
    return weights.shape


def _one_key(key, n, num_iters):
    """The single call's offset table and seed, as a one-row bank."""
    key_off, key_seed = jax.random.split(key)
    offsets = jax.random.randint(key_off, (num_iters,), 0, n, dtype=jnp.int32)
    return offsets[None], key_to_seed(key_seed).reshape(1)


def _shared_table(key, bsz, n, num_iters):
    """One offset table drawn ONCE and shared by every row (the bank-level
    lift of Alg. 5's shared offset, DESIGN.md §4), a seed per row, so rows
    stay statistically independent."""
    key_off, key_rows = jax.random.split(key)
    offsets = jax.random.randint(key_off, (num_iters,), 0, n, dtype=jnp.int32)
    return (jnp.broadcast_to(offsets, (bsz, num_iters)),
            key_to_seed(jax.random.split(key_rows, bsz)))


def _row_keys(keys, n, num_iters):
    """A key per row: row s's table and seed are the single call's with
    ``keys[s]``, so row s is bit-identical to that call."""
    offsets, seeds = jax.vmap(lambda k: _one_key(k, n, num_iters))(keys)
    return offsets[:, 0], seeds[:, 0]


def _ancestors(weights, tables, num_iters, interpret, plane_dtype, name):
    """Ancestors of a ``[S, N]`` bank: the kernel without state planes."""
    bsz, n = weights.shape
    w3 = compress_plane(weights.reshape(bsz, n // LANES, LANES), plane_dtype)
    (k3,) = megopolis_pallas_fused(w3, None, *tables, num_iters=num_iters,
                                   interpret=interpret, name=name)
    return k3.reshape(bsz, n)


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
    _check_aligned(n, "megopolis_tpu")
    return _ancestors(weights[None], _one_key(key, n, num_iters), num_iters,
                      interpret, plane_dtype, "megopolis_pallas")[0]


def megopolis_tpu_batch(
    key: jax.Array,
    weights: jnp.ndarray,
    num_iters: int,
    *,
    interpret: bool,
    plane_dtype="float32",
) -> jnp.ndarray:
    """Resample a ``[B, N]`` weight bank in one kernel launch (DESIGN.md §4)
    under one offset table shared by every row (``_shared_table``: one
    scalar-prefetch schedule for the whole launch).  Returns int32[B, N]
    ancestors."""
    bsz, n = _check_bank(weights, "megopolis_tpu_batch")
    return _ancestors(weights, _shared_table(key, bsz, n, num_iters), num_iters,
                      interpret, plane_dtype, "megopolis_pallas_batch")


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
    _check_aligned(n, "megopolis_tpu_apply")
    state_dim_of(particles, n, "megopolis_tpu_apply")  # validates the particle axis
    offsets, seed = _one_key(key, n, num_iters)
    w3 = compress_plane(weights.reshape(1, n // LANES, LANES), plane_dtype)
    planes, state_shape = pack_state_planes(particles)
    planes = compress_plane(planes, plane_dtype)
    k3, out = megopolis_pallas_fused(w3, planes[None], offsets, seed,
                                     num_iters=num_iters, interpret=interpret)
    out = out[0].astype(particles.dtype)
    return unpack_state_planes(out, state_shape), k3.reshape(n)


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
    bsz, n = _check_bank(weights, "megopolis_tpu_apply_batch")
    tables = _shared_table(key, bsz, n, num_iters)
    return run_fused_bank(
        lambda w3, planes: megopolis_pallas_fused(
            w3, planes, *tables, num_iters=num_iters, interpret=interpret,
            name="megopolis_pallas_apply_rows"),
        weights, particles, "megopolis_tpu_apply_batch",
        plane_dtype=plane_dtype, state_resident=False,
    )


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
    call with ``keys[b]`` — in ONE launch."""
    _, n = _check_bank(weights, "megopolis_tpu_apply_rows")
    tables = _row_keys(keys, n, num_iters)
    return run_fused_bank(
        lambda w3, planes: megopolis_pallas_fused(
            w3, planes, *tables, num_iters=num_iters, interpret=interpret,
            name="megopolis_pallas_apply_rows"),
        weights, particles, "megopolis_tpu_apply_rows",
        plane_dtype=plane_dtype, state_resident=False,
    )


def _check_step(n, who, plane_dtype):
    _check_aligned(n, who)
    check_vmem_resident(n, who, "log-weight array",
                        remedy=f"Compose Resampler.{who.removeprefix('megopolis_tpu_')} "
                               "on the reference/xla backend above this size.",
                        itemsize=plane_itemsize(plane_dtype))


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
    _check_step(n, "megopolis_tpu_step", plane_dtype)
    state_dim_of(particles, n, "megopolis_tpu_step")  # validates the particle axis
    offsets, seed = _one_key(key, n, num_iters)
    thr = jnp.asarray(ess_threshold, jnp.float32).reshape(1)
    lw3 = compress_plane(log_weights.reshape(1, n // LANES, LANES), plane_dtype)
    planes, state_shape = pack_state_planes(particles)
    planes = compress_plane(planes, plane_dtype)
    k3, out, stats = megopolis_pallas_step(
        lw3, planes[None], offsets, seed, thr, num_iters=num_iters, interpret=interpret)
    out = out[0].astype(particles.dtype)
    return unpack_state_planes(out, state_shape), k3.reshape(n), stats[0]


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
    its own on-chip resample decision in ONE launch.
    Returns ``(particles'[B, N, ...], ancestors int32[B, N],
    stats f32[B, 4])``."""
    _, n = _check_bank(log_weights, "megopolis_tpu_step_rows")
    _check_step(n, "megopolis_tpu_step_rows", plane_dtype)
    thr = jnp.asarray(ess_threshold, jnp.float32).reshape(1)
    tables = _row_keys(keys, n, num_iters)
    return run_step_bank(
        lambda lw3, planes: megopolis_pallas_step(
            lw3, planes, *tables, thr, num_iters=num_iters, interpret=interpret,
            name="megopolis_pallas_step_rows"),
        log_weights, particles, "megopolis_tpu_step_rows",
        plane_dtype=plane_dtype, state_resident=False,
    )
