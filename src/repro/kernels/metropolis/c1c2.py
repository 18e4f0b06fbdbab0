"""Metropolis-C1/C2 — Pallas TPU kernels (paper Algorithms 3-4, Dülger).

The CUDA originals constrain each warp's proposal index to a shared random
partition of ``N_w`` weights so the warp's gathers land in one cache line
(paper Fig. 3).  The TPU translation keeps that contract at tile
granularity: the partition is one aligned ``(8, 128)`` f32 VMEM tile
(``SEG = 1024`` particles = 4096 bytes), and the "warp" that shares it is
the whole tile of lanes.

  * **C1** (Alg. 3): ONE partition tile per own-tile, chosen up front and
    kept for every iteration — a scalar-prefetched table ``p[num_tiles]``
    drives the comparison BlockSpec, so the partition is fetched once per
    tile and re-used for all B sweeps (one transaction amortised over B).
  * **C2** (Alg. 4): a FRESH partition tile per (tile, iteration) — table
    ``p[num_tiles * num_iters]``, comparison block re-fetched every sweep
    (B transactions, the cost C2 pays for C1's quality pathology).

Within the partition the proposal ``j_local ~ U{0, SEG-1}`` is a random
in-VMEM gather — the analogue of the CUDA originals' random access inside
the shared-memory partition; no HBM traffic.  RNG lane layout matches the
Metropolis kernel: ``hash_bits(seed, i, b)`` proposes, ``hash_uniform(seed,
i + N, b)`` accepts.

Validated bit-exactly against ``ref.metropolis_c1_ref`` /
``ref.metropolis_c2_ref`` in interpret mode.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import (
    LANES,
    SUBLANES,
    gather_state,
    hash_bits,
    hash_uniform,
    step_select,
    step_stats,
    tile_lane_ids,
)

SEG = SUBLANES * LANES
# One (8,128) f32 VMEM tile — the kernel's partition, in bytes (Algs. 3-4
# parametrise the partition by bytes; the TPU tile is 1024 f32 = 4 KiB).
PARTITION_BYTES = SEG * 4


def _sweep_partition(t, b, p_tile, seed, w_own, w_part, k_prev, wk_prev, n_total):
    """One segment-local accept/reject sweep (Algs. 3-4 lines 7-13).

    ``w_part`` is the partition tile ``p_tile`` (already fetched by the
    BlockSpec); the proposal is a random lane of that tile."""
    i_global = tile_lane_ids(t)

    k = jnp.where(b == 0, i_global, k_prev)
    wk = jnp.where(b == 0, w_own, wk_prev)

    # j = p * N_w + U{0, N_w-1}: random access INSIDE the resident tile.
    j_local = (hash_bits(seed, i_global, b) % jnp.uint32(SEG)).astype(jnp.int32)
    w_j = jnp.take(w_part.reshape(SEG), j_local.reshape(-1), axis=0).reshape(
        SUBLANES, LANES
    )
    j_global = p_tile * SEG + j_local

    u = hash_uniform(seed, i_global + n_total, b, dtype=w_j.dtype)
    accept = u * wk <= w_j
    return jnp.where(accept, j_global, k), jnp.where(accept, w_j, wk)


def _kernel_c1(p_ref, seed_ref, w_own_ref, w_part_ref, k_ref, wk_ref):
    t = pl.program_id(0)
    b = pl.program_id(1)
    n_total = pl.num_programs(0) * SEG
    k_new, wk_new = _sweep_partition(
        t, b, p_ref[t], seed_ref[0],
        w_own_ref[...].astype(jnp.float32), w_part_ref[...].astype(jnp.float32),
        k_ref[...], wk_ref[...], n_total,
    )
    k_ref[...] = k_new
    wk_ref[...] = wk_new


def _make_kernel_c2(num_iters: int):
    def _kernel_c2(p_ref, seed_ref, w_own_ref, w_part_ref, k_ref, wk_ref):
        t = pl.program_id(0)
        b = pl.program_id(1)
        n_total = pl.num_programs(0) * SEG
        k_new, wk_new = _sweep_partition(
            t, b, p_ref[t * num_iters + b], seed_ref[0],
            w_own_ref[...].astype(jnp.float32),
            w_part_ref[...].astype(jnp.float32),
            k_ref[...], wk_ref[...], n_total,
        )
        k_ref[...] = k_new
        wk_ref[...] = wk_new

    return _kernel_c2


def _kernel_c1_fused(p_ref, seed_ref, w_own_ref, w_part_ref, planes_ref,
                     k_ref, out_ref, wk_ref):
    """Fused C1 grid step: segment-local sweep + last-iteration state copy
    (DESIGN.md §11).  The partition keeps C1's one-fetch contract; the
    state plane stack is resident because the SELECTED ancestor may live in
    any tile (``j_global`` ranges over all N across iterations)."""
    t = pl.program_id(0)
    b = pl.program_id(1)
    n_total = pl.num_programs(0) * SEG
    k_new, wk_new = _sweep_partition(
        t, b, p_ref[t], seed_ref[0],
        w_own_ref[...].astype(jnp.float32), w_part_ref[...].astype(jnp.float32),
        k_ref[...], wk_ref[...], n_total,
    )
    k_ref[...] = k_new
    wk_ref[...] = wk_new

    @pl.when(b == pl.num_programs(1) - 1)
    def _copy_state():
        out_ref[...] = gather_state(planes_ref[...], k_new)


def _make_kernel_c2_fused(num_iters: int):
    def _kernel_c2_fused(p_ref, seed_ref, w_own_ref, w_part_ref, planes_ref,
                         k_ref, out_ref, wk_ref):
        t = pl.program_id(0)
        b = pl.program_id(1)
        n_total = pl.num_programs(0) * SEG
        k_new, wk_new = _sweep_partition(
            t, b, p_ref[t * num_iters + b], seed_ref[0],
            w_own_ref[...].astype(jnp.float32),
            w_part_ref[...].astype(jnp.float32),
            k_ref[...], wk_ref[...], n_total,
        )
        k_ref[...] = k_new
        wk_ref[...] = wk_new

        @pl.when(b == pl.num_programs(1) - 1)
        def _copy_state():
            out_ref[...] = gather_state(planes_ref[...], k_new)

    return _kernel_c2_fused


def _make_kernel_step(p_at):
    """Fused STEP kernel body shared by C1 and C2 — they differ only in how
    the partition table is indexed (``p_at(p_ref, t, b)``).  The (0, 0)
    prelude latches (m, do) from a NEW resident log-weight input; the
    segment-local sweep runs on ``exp(lw - m)`` tiles and the last
    iteration commits selection or identity."""

    def _kernel_step(p_ref, seed_ref, thr_ref, lw_own_ref, lw_part_ref,
                     lw_full_ref, planes_ref, k_ref, out_ref, stats_ref,
                     wk_ref, st_ref):
        t = pl.program_id(0)
        b = pl.program_id(1)
        n_total = pl.num_programs(0) * SEG

        @pl.when((t == 0) & (b == 0))
        def _prelude():
            m, ess_norm, incr, maxw, deg = step_stats(
                lw_full_ref[...].astype(jnp.float32), n_total
            )
            do = ess_norm < thr_ref[0]
            st_ref[0] = m
            st_ref[1] = jnp.where(do, jnp.float32(1.0), jnp.float32(0.0))
            st_ref[2] = jnp.where(deg, jnp.float32(1.0), jnp.float32(0.0))
            stats_ref[0] = ess_norm
            stats_ref[1] = jnp.where(do, incr, jnp.float32(0.0))
            stats_ref[2] = jnp.where(do, jnp.float32(1.0), jnp.float32(0.0))
            stats_ref[3] = maxw

        m = st_ref[0]
        do = st_ref[1] > 0.5
        deg = st_ref[2] > 0.5
        # Normalised weights re-land on the plane-dtype grid (the composed
        # path quantises at the public ``apply`` boundary); a no-op at f32.
        # The §16 degenerate latch substitutes the uniform bank first.
        w_own = jnp.exp(lw_own_ref[...].astype(jnp.float32) - m)
        w_part = jnp.exp(lw_part_ref[...].astype(jnp.float32) - m)
        w_own = jnp.where(deg, jnp.float32(1.0 / n_total), w_own)
        w_part = jnp.where(deg, jnp.float32(1.0 / n_total), w_part)
        w_own = w_own.astype(lw_own_ref.dtype).astype(jnp.float32)
        w_part = w_part.astype(lw_part_ref.dtype).astype(jnp.float32)
        k_new, wk_new = _sweep_partition(
            t, b, p_at(p_ref, t, b), seed_ref[0],
            w_own, w_part, k_ref[...], wk_ref[...], n_total,
        )
        k_ref[...] = k_new
        wk_ref[...] = wk_new

        @pl.when(b == pl.num_programs(1) - 1)
        def _commit():
            k_sel = step_select(do, k_new, t)
            k_ref[...] = k_sel
            out_ref[...] = gather_state(planes_ref[...], k_sel)

    return _kernel_step


def _c1c2_step_call(kernel, log_weights2d, planes, partitions, seed, thr, *,
                    num_iters, part_index, interpret):
    """Shared fused-step pallas_call builder for the C1/C2 pair: the fused
    apply layout plus a resident whole-log-weight input for the prelude and
    an SMEM stats output."""
    rows, lanes = log_weights2d.shape
    assert lanes == LANES and rows % SUBLANES == 0
    d_pad = planes.shape[0]
    assert planes.shape[1:] == (rows, lanes)
    num_tiles = rows // SUBLANES

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,  # partitions + seed + f32 ESS threshold
        grid=(num_tiles, num_iters),
        in_specs=[
            pl.BlockSpec((SUBLANES, LANES), lambda t, b, p, se, r: (t, 0)),
            pl.BlockSpec((SUBLANES, LANES), part_index),
            pl.BlockSpec((rows, LANES), lambda t, b, p, se, r: (0, 0)),
            pl.BlockSpec((d_pad, rows, LANES), lambda t, b, p, se, r: (0, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((SUBLANES, LANES), lambda t, b, p, se, r: (t, 0)),
            pl.BlockSpec((d_pad, SUBLANES, LANES), lambda t, b, p, se, r: (0, t, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        scratch_shapes=[
            pltpu.VMEM((SUBLANES, LANES), jnp.float32),
            pltpu.SMEM((3,), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((rows, lanes), jnp.int32),
            jax.ShapeDtypeStruct((d_pad, rows, lanes), planes.dtype),
            jax.ShapeDtypeStruct((4,), jnp.float32),
        ],
        interpret=interpret,
    )(partitions, seed, thr, log_weights2d, log_weights2d, log_weights2d, planes)


@functools.partial(jax.jit, static_argnames=("num_iters", "interpret"))
def metropolis_c1_pallas_step(
    log_weights2d: jnp.ndarray,
    planes: jnp.ndarray,
    partitions: jnp.ndarray,
    seed: jnp.ndarray,
    thr: jnp.ndarray,
    *,
    num_iters: int,
    interpret: bool,
):
    """Fused C1 SMC step: normalise → ESS → conditional Alg. 3 resample →
    state copy, ONE launch.  Returns ``(int32[R, 128], [d_pad, R, 128],
    f32[4] = (ess_norm, incr, resampled, max_weight))``."""
    return _c1c2_step_call(
        _make_kernel_step(lambda p, t, b: p[t]),
        log_weights2d, planes, partitions, seed, thr,
        num_iters=num_iters,
        part_index=lambda t, b, p, se, r: (p[t], 0),
        interpret=interpret,
    )


@functools.partial(jax.jit, static_argnames=("num_iters", "interpret"))
def metropolis_c2_pallas_step(
    log_weights2d: jnp.ndarray,
    planes: jnp.ndarray,
    partitions: jnp.ndarray,
    seed: jnp.ndarray,
    thr: jnp.ndarray,
    *,
    num_iters: int,
    interpret: bool,
):
    """Fused C2 SMC step: as C1 but with a fresh partition per (t, b)
    (Alg. 4).  Returns ``(int32[R, 128], [d_pad, R, 128], f32[4])``."""
    return _c1c2_step_call(
        _make_kernel_step(lambda p, t, b: p[t * num_iters + b]),
        log_weights2d, planes, partitions, seed, thr,
        num_iters=num_iters,
        part_index=lambda t, b, p, se, r: (p[t * num_iters + b], 0),
        interpret=interpret,
    )


def _c1c2_fused_call(kernel, weights2d, planes, partitions, seed, *,
                     num_iters, part_index, interpret):
    """Shared fused pallas_call builder for the C1/C2 pair — identical
    except for the partition BlockSpec index map."""
    rows, lanes = weights2d.shape
    assert lanes == LANES and rows % SUBLANES == 0
    d_pad = planes.shape[0]
    assert planes.shape[1:] == (rows, lanes)
    num_tiles = rows // SUBLANES

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(num_tiles, num_iters),
        in_specs=[
            pl.BlockSpec((SUBLANES, LANES), lambda t, b, p, seed: (t, 0)),
            pl.BlockSpec((SUBLANES, LANES), part_index),
            pl.BlockSpec((d_pad, rows, LANES), lambda t, b, p, seed: (0, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((SUBLANES, LANES), lambda t, b, p, seed: (t, 0)),
            pl.BlockSpec((d_pad, SUBLANES, LANES), lambda t, b, p, seed: (0, t, 0)),
        ],
        scratch_shapes=[pltpu.VMEM((SUBLANES, LANES), jnp.float32)],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((rows, lanes), jnp.int32),
            jax.ShapeDtypeStruct((d_pad, rows, lanes), planes.dtype),
        ],
        interpret=interpret,
    )(partitions, seed, weights2d, weights2d, planes)


@functools.partial(jax.jit, static_argnames=("num_iters", "interpret"))
def metropolis_c1_pallas_fused(
    weights2d: jnp.ndarray,
    planes: jnp.ndarray,
    partitions: jnp.ndarray,
    seed: jnp.ndarray,
    *,
    num_iters: int,
    interpret: bool,
):
    """Fused C1: ancestors identical to ``metropolis_c1_pallas``; returns
    ``(int32[R, 128], [d_pad, R, 128])``."""
    return _c1c2_fused_call(
        _kernel_c1_fused, weights2d, planes, partitions, seed,
        num_iters=num_iters,
        part_index=lambda t, b, p, seed: (p[t], 0),
        interpret=interpret,
    )


@functools.partial(jax.jit, static_argnames=("num_iters", "interpret"))
def metropolis_c2_pallas_fused(
    weights2d: jnp.ndarray,
    planes: jnp.ndarray,
    partitions: jnp.ndarray,
    seed: jnp.ndarray,
    *,
    num_iters: int,
    interpret: bool,
):
    """Fused C2: ancestors identical to ``metropolis_c2_pallas``; returns
    ``(int32[R, 128], [d_pad, R, 128])``."""
    return _c1c2_fused_call(
        _make_kernel_c2_fused(num_iters), weights2d, planes, partitions, seed,
        num_iters=num_iters,
        part_index=lambda t, b, p, seed: (p[t * num_iters + b], 0),
        interpret=interpret,
    )


@functools.partial(jax.jit, static_argnames=("num_iters", "interpret"))
def metropolis_c1_pallas(
    weights2d: jnp.ndarray,
    partitions: jnp.ndarray,
    seed: jnp.ndarray,
    *,
    num_iters: int,
    interpret: bool,
) -> jnp.ndarray:
    """``weights2d``: f32[R, 128] with R % 8 == 0; ``partitions``:
    int32[num_tiles] (one fixed partition tile per own-tile); ``seed``:
    uint32[1].  Returns int32[R, 128] ancestors."""
    rows, lanes = weights2d.shape
    assert lanes == LANES and rows % SUBLANES == 0
    num_tiles = rows // SUBLANES

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(num_tiles, num_iters),
        in_specs=[
            pl.BlockSpec((SUBLANES, LANES), lambda t, b, p, seed: (t, 0)),
            # partition block constant in b -> fetched ONCE per tile (C1's
            # whole point: one transaction amortised over all B sweeps)
            pl.BlockSpec((SUBLANES, LANES), lambda t, b, p, seed: (p[t], 0)),
        ],
        out_specs=pl.BlockSpec((SUBLANES, LANES), lambda t, b, p, seed: (t, 0)),
        scratch_shapes=[pltpu.VMEM((SUBLANES, LANES), jnp.float32)],
    )
    return pl.pallas_call(
        _kernel_c1,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((rows, lanes), jnp.int32),
        interpret=interpret,
    )(partitions, seed, weights2d, weights2d)


@functools.partial(jax.jit, static_argnames=("num_iters", "interpret"))
def metropolis_c2_pallas(
    weights2d: jnp.ndarray,
    partitions: jnp.ndarray,
    seed: jnp.ndarray,
    *,
    num_iters: int,
    interpret: bool,
) -> jnp.ndarray:
    """``partitions``: int32[num_tiles * num_iters], row-major by tile —
    ``partitions[t * num_iters + b]`` is tile t's partition at iteration b
    (a fresh fetch per sweep, Alg. 4's cost).  Returns int32[R, 128]."""
    rows, lanes = weights2d.shape
    assert lanes == LANES and rows % SUBLANES == 0
    num_tiles = rows // SUBLANES

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(num_tiles, num_iters),
        in_specs=[
            pl.BlockSpec((SUBLANES, LANES), lambda t, b, p, seed: (t, 0)),
            # fresh partition block EVERY (t, b) grid step
            pl.BlockSpec(
                (SUBLANES, LANES), lambda t, b, p, seed: (p[t * num_iters + b], 0)
            ),
        ],
        out_specs=pl.BlockSpec((SUBLANES, LANES), lambda t, b, p, seed: (t, 0)),
        scratch_shapes=[pltpu.VMEM((SUBLANES, LANES), jnp.float32)],
    )
    return pl.pallas_call(
        _make_kernel_c2(num_iters),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((rows, lanes), jnp.int32),
        interpret=interpret,
    )(partitions, seed, weights2d, weights2d)
