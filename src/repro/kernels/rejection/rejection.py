"""Rejection resampling — Pallas TPU kernel (Murray's unbiased baseline).

The paper positions Metropolis/Megopolis against rejection (§1): rejection
is unbiased but each particle's iteration count is a geometric random
variable — divergent control flow on SIMD hardware.  The kernel reproduces
that SIMD reality honestly: every lane runs the SAME fixed-trip proposal
loop (capped at ``max_iters``) with a ``done`` mask, so a tile pays for its
slowest lane — the divergence cost the paper describes, surfaced as wasted
masked work instead of warp serialisation.

Memory contract: proposals ``j ~ U{0, N-1}`` gather from the FULL weight
array, so like the Metropolis strawman the weights must stay VMEM-resident
(same cap, same scaling wall).  ``sup w`` is reduced in-register from the
resident array.  RNG lane layout matches the Metropolis kernel —
``hash_bits(seed, i, t)`` proposes, ``hash_uniform(seed, i + N, t)``
accepts — with ``t = 0`` reserved for the self-proposal round (particle i
first proposes itself, accepted w.p. ``w_i / sup w``), mirroring
``repro.core.resamplers.rejection``.

Grid = (num_tiles,) only: the proposal loop lives INSIDE the kernel body
(a ``fori_loop``), because unlike the Metropolis family there is no
carried cross-iteration memory schedule to coalesce — every iteration's
gather is random anyway.

Validated bit-exactly against ``ref.rejection_ref`` in interpret mode.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
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


def _rejection_loop(t, seed, w_max, w_full, w_own, max_iters: int):
    """The whole per-tile rejection chain (shared with nothing — rejection
    has no cross-iteration state beyond the done mask).  ``w_max`` (sup w)
    is scalar-prefetched: reduced ONCE by the wrapper, not once per grid
    step."""
    n_total = w_full.shape[0] * LANES
    i_global = tile_lane_ids(t)

    w_flat = w_full.reshape(n_total)

    # Round 0: particle i proposes itself (accept w.p. w_i / sup w).
    u0 = hash_uniform(seed, i_global + n_total, 0, dtype=w_own.dtype)
    done0 = u0 * w_max <= w_own
    k0 = i_global

    def body(tt, state):
        k, done = state
        j = (hash_bits(seed, i_global, tt) % jnp.uint32(n_total)).astype(jnp.int32)
        w_j = jnp.take(w_flat, j.reshape(-1), axis=0).reshape(SUBLANES, LANES)
        u = hash_uniform(seed, i_global + n_total, tt, dtype=w_j.dtype)
        accept = (~done) & (u * w_max <= w_j)
        return jnp.where(accept, j, k), done | accept

    k, _ = lax.fori_loop(1, max_iters + 1, body, (k0, done0))
    return k


def _make_kernel(max_iters: int):
    def _kernel(seed_ref, wmax_ref, w_full_ref, w_own_ref, k_ref):
        t = pl.program_id(0)
        k_ref[...] = _rejection_loop(
            t, seed_ref[0], wmax_ref[0], w_full_ref[...].astype(jnp.float32),
            w_own_ref[...].astype(jnp.float32), max_iters
        )

    return _kernel


def _make_kernel_batch(max_iters: int):
    def _kernel(seeds_ref, wmax_ref, w_full_ref, w_own_ref, k_ref):
        s = pl.program_id(0)
        t = pl.program_id(1)
        k_ref[0] = _rejection_loop(
            t, seeds_ref[s], wmax_ref[s], w_full_ref[0].astype(jnp.float32),
            w_own_ref[0].astype(jnp.float32), max_iters
        )

    return _kernel


def _make_kernel_fused(max_iters: int):
    def _kernel(seed_ref, wmax_ref, w_full_ref, w_own_ref, planes_ref, k_ref,
                out_ref):
        t = pl.program_id(0)
        k = _rejection_loop(
            t, seed_ref[0], wmax_ref[0], w_full_ref[...].astype(jnp.float32),
            w_own_ref[...].astype(jnp.float32), max_iters
        )
        k_ref[...] = k
        out_ref[...] = gather_state(planes_ref[...], k)

    return _kernel


def _make_kernel_fused_batch(max_iters: int):
    def _kernel(seeds_ref, wmax_ref, w_full_ref, w_own_ref, planes_ref, k_ref,
                out_ref):
        s = pl.program_id(0)
        t = pl.program_id(1)
        k = _rejection_loop(
            t, seeds_ref[s], wmax_ref[s], w_full_ref[0].astype(jnp.float32),
            w_own_ref[0].astype(jnp.float32), max_iters
        )
        k_ref[0] = k
        out_ref[0] = gather_state(planes_ref[0], k)

    return _kernel


def _make_kernel_step(max_iters: int):
    def _kernel(seed_ref, thr_ref, lw_full_ref, lw_own_ref, planes_ref,
                k_ref, out_ref, stats_ref, st_ref):
        """Fused STEP grid step (t,): the t == 0 prelude latches (m, do)
        AND ``sup w`` (an order-free max of ``exp(lw - m)``, so it equals
        the wrapper-side reduction of the composed path bitwise); each tile
        then runs the whole rejection chain on the normalised weights and
        commits selection or identity in the same grid step."""
        t = pl.program_id(0)
        n_total = lw_full_ref.shape[0] * LANES

        @pl.when(t == 0)
        def _prelude():
            m, ess_norm, incr, maxw, deg = step_stats(
                lw_full_ref[...].astype(jnp.float32), n_total
            )
            do = ess_norm < thr_ref[0]
            st_ref[0] = m
            st_ref[1] = jnp.where(do, jnp.float32(1.0), jnp.float32(0.0))
            w_all = jnp.exp(lw_full_ref[...].astype(jnp.float32) - m)
            w_all = jnp.where(deg, jnp.float32(1.0 / n_total), w_all)
            st_ref[2] = jnp.max(
                w_all.astype(lw_full_ref.dtype).astype(jnp.float32))
            st_ref[3] = jnp.where(deg, jnp.float32(1.0), jnp.float32(0.0))
            stats_ref[0] = ess_norm
            stats_ref[1] = jnp.where(do, incr, jnp.float32(0.0))
            stats_ref[2] = jnp.where(do, jnp.float32(1.0), jnp.float32(0.0))
            stats_ref[3] = maxw

        m = st_ref[0]
        do = st_ref[1] > 0.5
        deg = st_ref[3] > 0.5
        # Normalised weights re-land on the plane-dtype grid (the composed
        # path quantises at the public ``apply`` boundary); a no-op at f32.
        # The §16 degenerate latch substitutes the uniform bank first.
        w_full = jnp.exp(lw_full_ref[...].astype(jnp.float32) - m)
        w_own = jnp.exp(lw_own_ref[...].astype(jnp.float32) - m)
        w_full = jnp.where(deg, jnp.float32(1.0 / n_total), w_full)
        w_own = jnp.where(deg, jnp.float32(1.0 / n_total), w_own)
        w_full = w_full.astype(lw_full_ref.dtype).astype(jnp.float32)
        w_own = w_own.astype(lw_own_ref.dtype).astype(jnp.float32)
        k = _rejection_loop(t, seed_ref[0], st_ref[2], w_full, w_own, max_iters)
        k_sel = step_select(do, k, t)
        k_ref[...] = k_sel
        out_ref[...] = gather_state(planes_ref[...], k_sel)

    return _kernel


def _make_kernel_step_rows(max_iters: int):
    def _kernel(seeds_ref, thr_ref, lw_full_ref, lw_own_ref, planes_ref,
                k_ref, out_ref, stats_ref, st_ref):
        """Fused STEP over a bank, grid (s, t): per-row seeds; the prelude
        re-latches (m, do, sup w) at each row's t == 0 and writes that
        row's ``stats[s]``."""
        s = pl.program_id(0)
        t = pl.program_id(1)
        n_total = lw_full_ref.shape[1] * LANES

        @pl.when(t == 0)
        def _prelude():
            m, ess_norm, incr, maxw, deg = step_stats(
                lw_full_ref[0].astype(jnp.float32), n_total
            )
            do = ess_norm < thr_ref[0]
            st_ref[0] = m
            st_ref[1] = jnp.where(do, jnp.float32(1.0), jnp.float32(0.0))
            w_all = jnp.exp(lw_full_ref[0].astype(jnp.float32) - m)
            w_all = jnp.where(deg, jnp.float32(1.0 / n_total), w_all)
            st_ref[2] = jnp.max(
                w_all.astype(lw_full_ref.dtype).astype(jnp.float32))
            st_ref[3] = jnp.where(deg, jnp.float32(1.0), jnp.float32(0.0))
            stats_ref[s, 0] = ess_norm
            stats_ref[s, 1] = jnp.where(do, incr, jnp.float32(0.0))
            stats_ref[s, 2] = jnp.where(do, jnp.float32(1.0), jnp.float32(0.0))
            stats_ref[s, 3] = maxw

        m = st_ref[0]
        do = st_ref[1] > 0.5
        deg = st_ref[3] > 0.5
        w_full = jnp.exp(lw_full_ref[0].astype(jnp.float32) - m)
        w_own = jnp.exp(lw_own_ref[0].astype(jnp.float32) - m)
        w_full = jnp.where(deg, jnp.float32(1.0 / n_total), w_full)
        w_own = jnp.where(deg, jnp.float32(1.0 / n_total), w_own)
        w_full = w_full.astype(lw_full_ref.dtype).astype(jnp.float32)
        w_own = w_own.astype(lw_own_ref.dtype).astype(jnp.float32)
        k = _rejection_loop(t, seeds_ref[s], st_ref[2], w_full, w_own, max_iters)
        k_sel = step_select(do, k, t)
        k_ref[0] = k_sel
        out_ref[0] = gather_state(planes_ref[0], k_sel)

    return _kernel


@functools.partial(jax.jit, static_argnames=("max_iters", "interpret"))
def rejection_pallas_step(
    log_weights2d: jnp.ndarray,
    planes: jnp.ndarray,
    seed: jnp.ndarray,
    thr: jnp.ndarray,
    *,
    max_iters: int,
    interpret: bool,
):
    """Fused SMC-step pallas_call: normalise → ESS → conditional rejection
    chain → state copy, ONE launch.  ``log_weights2d``: f32[R, 128]
    UNNORMALISED; ``sup w`` is reduced IN-kernel from the resident array
    (order-free max — bit-identical to the composed wrapper's reduction).
    Returns ``(int32[R, 128], [d_pad, R, 128], f32[4] = (ess_norm, incr,
    resampled, max_weight))``."""
    rows, lanes = log_weights2d.shape
    assert lanes == LANES and rows % SUBLANES == 0
    d_pad = planes.shape[0]
    assert planes.shape[1:] == (rows, lanes)
    num_tiles = rows // SUBLANES

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,  # seed + f32 ESS threshold
        grid=(num_tiles,),
        in_specs=[
            pl.BlockSpec((rows, LANES), lambda t, seed, thr: (0, 0)),
            pl.BlockSpec((SUBLANES, LANES), lambda t, seed, thr: (t, 0)),
            pl.BlockSpec((d_pad, rows, LANES), lambda t, seed, thr: (0, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((SUBLANES, LANES), lambda t, seed, thr: (t, 0)),
            pl.BlockSpec((d_pad, SUBLANES, LANES), lambda t, seed, thr: (0, t, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        scratch_shapes=[pltpu.SMEM((4,), jnp.float32)],  # (m, do, sup w, deg)
    )
    return pl.pallas_call(
        _make_kernel_step(max_iters),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((rows, lanes), jnp.int32),
            jax.ShapeDtypeStruct((d_pad, rows, lanes), planes.dtype),
            jax.ShapeDtypeStruct((4,), jnp.float32),
        ],
        interpret=interpret,
    )(seed, thr, log_weights2d, log_weights2d, planes)


@functools.partial(jax.jit, static_argnames=("max_iters", "interpret"))
def rejection_pallas_step_rows(
    log_weights3d: jnp.ndarray,
    planes4d: jnp.ndarray,
    seeds: jnp.ndarray,
    thr: jnp.ndarray,
    *,
    max_iters: int,
    interpret: bool,
):
    """Fused SMC-step bank launch; row s is bit-identical to
    ``rejection_pallas_step(log_weights3d[s], planes4d[s], seeds[s:s+1],
    thr, ...)``.  Returns ``(int32[Bz, R, 128], [Bz, d_pad, R, 128],
    f32[Bz, 4])``."""
    bsz, rows, lanes = log_weights3d.shape
    assert lanes == LANES and rows % SUBLANES == 0
    d_pad = planes4d.shape[1]
    assert planes4d.shape == (bsz, d_pad, rows, lanes)
    num_tiles = rows // SUBLANES

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(bsz, num_tiles),
        in_specs=[
            pl.BlockSpec((1, rows, LANES), lambda s, t, se, r: (s, 0, 0)),
            pl.BlockSpec((1, SUBLANES, LANES), lambda s, t, se, r: (s, t, 0)),
            pl.BlockSpec(
                (1, d_pad, rows, LANES), lambda s, t, se, r: (s, 0, 0, 0)
            ),
        ],
        out_specs=[
            pl.BlockSpec((1, SUBLANES, LANES), lambda s, t, se, r: (s, t, 0)),
            pl.BlockSpec(
                (1, d_pad, SUBLANES, LANES), lambda s, t, se, r: (s, 0, t, 0)
            ),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        scratch_shapes=[pltpu.SMEM((4,), jnp.float32)],
    )
    return pl.pallas_call(
        _make_kernel_step_rows(max_iters),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((bsz, rows, lanes), jnp.int32),
            jax.ShapeDtypeStruct((bsz, d_pad, rows, lanes), planes4d.dtype),
            jax.ShapeDtypeStruct((bsz, 4), jnp.float32),
        ],
        interpret=interpret,
    )(seeds, thr, log_weights3d, log_weights3d, planes4d)


@functools.partial(jax.jit, static_argnames=("max_iters", "interpret"))
def rejection_pallas_fused(
    weights2d: jnp.ndarray,
    planes: jnp.ndarray,
    seed: jnp.ndarray,
    *,
    max_iters: int,
    interpret: bool,
):
    """Fused resample+gather (DESIGN.md §11): the rejection chain runs
    entirely inside the kernel body, so the state copy follows it in the
    SAME grid step — rejection needs no last-iteration gating.  Ancestors
    identical to ``rejection_pallas``; returns ``(int32[R, 128],
    [d_pad, R, 128])``."""
    rows, lanes = weights2d.shape
    assert lanes == LANES and rows % SUBLANES == 0
    d_pad = planes.shape[0]
    assert planes.shape[1:] == (rows, lanes)
    num_tiles = rows // SUBLANES
    w_max = jnp.max(weights2d).astype(jnp.float32).reshape(1)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(num_tiles,),
        in_specs=[
            pl.BlockSpec((rows, LANES), lambda t, seed, wmax: (0, 0)),
            pl.BlockSpec((SUBLANES, LANES), lambda t, seed, wmax: (t, 0)),
            pl.BlockSpec((d_pad, rows, LANES), lambda t, seed, wmax: (0, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((SUBLANES, LANES), lambda t, seed, wmax: (t, 0)),
            pl.BlockSpec((d_pad, SUBLANES, LANES), lambda t, seed, wmax: (0, t, 0)),
        ],
    )
    return pl.pallas_call(
        _make_kernel_fused(max_iters),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((rows, lanes), jnp.int32),
            jax.ShapeDtypeStruct((d_pad, rows, lanes), planes.dtype),
        ],
        interpret=interpret,
    )(seed, w_max, weights2d, weights2d, planes)


@functools.partial(jax.jit, static_argnames=("max_iters", "interpret"))
def rejection_pallas_fused_batch(
    weights3d: jnp.ndarray,
    planes4d: jnp.ndarray,
    seeds: jnp.ndarray,
    *,
    max_iters: int,
    interpret: bool,
):
    """Fused bank launch (leading batch grid dim); row s is bit-identical to
    ``rejection_pallas_fused(weights3d[s], planes4d[s], seeds[s:s+1])``."""
    bsz, rows, lanes = weights3d.shape
    assert lanes == LANES and rows % SUBLANES == 0
    d_pad = planes4d.shape[1]
    assert planes4d.shape == (bsz, d_pad, rows, lanes)
    num_tiles = rows // SUBLANES
    w_max = jnp.max(weights3d, axis=(1, 2)).astype(jnp.float32)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(bsz, num_tiles),
        in_specs=[
            pl.BlockSpec((1, rows, LANES), lambda s, t, seeds, wmax: (s, 0, 0)),
            pl.BlockSpec((1, SUBLANES, LANES), lambda s, t, seeds, wmax: (s, t, 0)),
            pl.BlockSpec(
                (1, d_pad, rows, LANES), lambda s, t, seeds, wmax: (s, 0, 0, 0)
            ),
        ],
        out_specs=[
            pl.BlockSpec((1, SUBLANES, LANES), lambda s, t, seeds, wmax: (s, t, 0)),
            pl.BlockSpec(
                (1, d_pad, SUBLANES, LANES), lambda s, t, seeds, wmax: (s, 0, t, 0)
            ),
        ],
    )
    return pl.pallas_call(
        _make_kernel_fused_batch(max_iters),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((bsz, rows, lanes), jnp.int32),
            jax.ShapeDtypeStruct((bsz, d_pad, rows, lanes), planes4d.dtype),
        ],
        interpret=interpret,
    )(seeds, w_max, weights3d, weights3d, planes4d)


@functools.partial(jax.jit, static_argnames=("max_iters", "interpret"))
def rejection_pallas(
    weights2d: jnp.ndarray,
    seed: jnp.ndarray,
    *,
    max_iters: int,
    interpret: bool,
) -> jnp.ndarray:
    """``weights2d``: f32[R, 128] with R % 8 == 0; ``seed``: uint32[1].
    Returns int32[R, 128] ancestors (last proposal kept past the cap)."""
    rows, lanes = weights2d.shape
    assert lanes == LANES and rows % SUBLANES == 0
    num_tiles = rows // SUBLANES
    w_max = jnp.max(weights2d).astype(jnp.float32).reshape(1)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,  # seed + sup w (reduced once, host of the grid)
        grid=(num_tiles,),
        in_specs=[
            pl.BlockSpec((rows, LANES), lambda t, seed, wmax: (0, 0)),
            pl.BlockSpec((SUBLANES, LANES), lambda t, seed, wmax: (t, 0)),
        ],
        out_specs=pl.BlockSpec((SUBLANES, LANES), lambda t, seed, wmax: (t, 0)),
    )
    return pl.pallas_call(
        _make_kernel(max_iters),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((rows, lanes), jnp.int32),
        interpret=interpret,
    )(seed, w_max, weights2d, weights2d)


@functools.partial(jax.jit, static_argnames=("max_iters", "interpret"))
def rejection_pallas_batch(
    weights3d: jnp.ndarray,
    seeds: jnp.ndarray,
    *,
    max_iters: int,
    interpret: bool,
) -> jnp.ndarray:
    """Batched launch over a ``[Bz, R, 128]`` bank (leading batch grid dim);
    row s is bit-identical to ``rejection_pallas(weights3d[s], seeds[s:s+1])``."""
    bsz, rows, lanes = weights3d.shape
    assert lanes == LANES and rows % SUBLANES == 0
    num_tiles = rows // SUBLANES
    w_max = jnp.max(weights3d, axis=(1, 2)).astype(jnp.float32)  # per-row sup w

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(bsz, num_tiles),
        in_specs=[
            pl.BlockSpec((1, rows, LANES), lambda s, t, seeds, wmax: (s, 0, 0)),
            pl.BlockSpec((1, SUBLANES, LANES), lambda s, t, seeds, wmax: (s, t, 0)),
        ],
        out_specs=pl.BlockSpec((1, SUBLANES, LANES), lambda s, t, seeds, wmax: (s, t, 0)),
    )
    return pl.pallas_call(
        _make_kernel_batch(max_iters),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((bsz, rows, lanes), jnp.int32),
        interpret=interpret,
    )(seeds, w_max, weights3d, weights3d)
