"""Metropolis resampling — Pallas TPU kernel (the paper's Alg. 2 strawman).

A faithful port of Metropolis needs a random per-(particle, iteration)
gather over the FULL weight array: the uncoalesced pattern of the paper's
Fig. 2.  On TPU the only way to honour those semantics is to keep the whole
weight array VMEM-resident and gather in-register, which caps N at the VMEM
budget (~1M f32 = 4 MB comfortably).  That cap is itself the finding: the
random-access algorithm does not scale on TPU, while Megopolis streams
aligned tiles from HBM at any N.  The benchmark suite reports this next to
the transaction-model numbers.
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


def _sweep(t, b, seed, w_full, w_own, k_prev, wk_prev):
    """One Alg. 2 accept/reject sweep of one (8,128) tile.

    Shared by the single and batched kernel bodies (same discipline as the
    Megopolis ``_sweep``) so the two can never drift arithmetically."""
    i_global = tile_lane_ids(t)
    k = jnp.where(b == 0, i_global, k_prev)
    wk = jnp.where(b == 0, w_own, wk_prev)

    n_total = w_full.shape[0] * LANES
    # Alg. 2 line 5: j ~ U{0, N-1} per (particle, iteration) — random gather.
    j = (hash_bits(seed, i_global, b) % jnp.uint32(n_total)).astype(jnp.int32)
    w_flat = w_full.reshape(n_total)
    w_j = jnp.take(w_flat, j.reshape(-1), axis=0).reshape(SUBLANES, LANES)

    u = hash_uniform(seed, i_global + n_total, b, dtype=w_j.dtype)
    accept = u * wk <= w_j
    return jnp.where(accept, j, k), jnp.where(accept, w_j, wk)


def _kernel(seed_ref, w_full_ref, w_own_ref, k_ref, wk_ref):
    t = pl.program_id(0)
    b = pl.program_id(1)
    k_new, wk_new = _sweep(
        t, b, seed_ref[0], w_full_ref[...].astype(jnp.float32),
        w_own_ref[...].astype(jnp.float32), k_ref[...], wk_ref[...]
    )
    k_ref[...] = k_new
    wk_ref[...] = wk_new


def _kernel_batch(seeds_ref, w_full_ref, w_own_ref, k_ref, wk_ref):
    """Grid step (s, t, b): row s of the bank, tile t, iteration b.

    One whole ``[B, R, 128]`` bank per pallas_call; each row keeps its own
    VMEM-resident weight copy (the strawman's cost, paid per row) and its
    own stateless-RNG seed ``seeds[s]``, so row s is bit-identical to the
    single-bank kernel run with that seed."""
    s = pl.program_id(0)
    t = pl.program_id(1)
    b = pl.program_id(2)
    k_new, wk_new = _sweep(
        t, b, seeds_ref[s], w_full_ref[0].astype(jnp.float32),
        w_own_ref[0].astype(jnp.float32), k_ref[0], wk_ref[...]
    )
    k_ref[0] = k_new
    wk_ref[...] = wk_new


def _kernel_fused(seed_ref, w_full_ref, w_own_ref, planes_ref, k_ref, out_ref,
                  wk_ref):
    """Fused grid step (t, b): Alg. 2 sweep + last-iteration state copy from
    the resident plane stack (DESIGN.md §11) — the weights AND the state
    are both VMEM-resident here (the strawman's residency cost, now paid
    once for selection and copy together)."""
    t = pl.program_id(0)
    b = pl.program_id(1)
    k_new, wk_new = _sweep(
        t, b, seed_ref[0], w_full_ref[...].astype(jnp.float32),
        w_own_ref[...].astype(jnp.float32), k_ref[...], wk_ref[...]
    )
    k_ref[...] = k_new
    wk_ref[...] = wk_new

    @pl.when(b == pl.num_programs(1) - 1)
    def _copy_state():
        out_ref[...] = gather_state(planes_ref[...], k_new)


def _kernel_fused_batch(seeds_ref, w_full_ref, w_own_ref, planes_ref, k_ref,
                        out_ref, wk_ref):
    """Fused grid step (s, t, b): row s of the bank, per-row seed — row s is
    bit-identical to the fused single kernel with ``seeds[s]``."""
    s = pl.program_id(0)
    t = pl.program_id(1)
    b = pl.program_id(2)
    k_new, wk_new = _sweep(
        t, b, seeds_ref[s], w_full_ref[0].astype(jnp.float32),
        w_own_ref[0].astype(jnp.float32), k_ref[0], wk_ref[...]
    )
    k_ref[0] = k_new
    wk_ref[...] = wk_new

    @pl.when(b == pl.num_programs(2) - 1)
    def _copy_state():
        out_ref[0] = gather_state(planes_ref[0], k_new)


def _kernel_step(seed_ref, thr_ref, lw_full_ref, lw_own_ref, planes_ref,
                 k_ref, out_ref, stats_ref, wk_ref, st_ref):
    """Fused STEP grid step (t, b): the (0, 0) prelude latches (m, do) from
    the resident log-weights; every sweep runs on ``exp(lw - m)`` — the
    same normalised weights the composed path hands to ``apply`` — and the
    last-iteration epilogue commits either the selection or the identity."""
    t = pl.program_id(0)
    b = pl.program_id(1)
    n_total = lw_full_ref.shape[0] * LANES

    @pl.when((t == 0) & (b == 0))
    def _prelude():
        m, ess_norm, incr, maxw, deg = step_stats(
            lw_full_ref[...].astype(jnp.float32), n_total)
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
    # Normalised weights re-land on the plane-dtype grid (the composed path
    # quantises at the public ``apply`` boundary); a no-op at f32.  The §16
    # degenerate latch substitutes the uniform bank BEFORE the requantise.
    w_full = jnp.exp(lw_full_ref[...].astype(jnp.float32) - m)
    w_own = jnp.exp(lw_own_ref[...].astype(jnp.float32) - m)
    w_full = jnp.where(deg, jnp.float32(1.0 / n_total), w_full)
    w_own = jnp.where(deg, jnp.float32(1.0 / n_total), w_own)
    w_full = w_full.astype(lw_full_ref.dtype).astype(jnp.float32)
    w_own = w_own.astype(lw_own_ref.dtype).astype(jnp.float32)
    k_new, wk_new = _sweep(
        t, b, seed_ref[0], w_full, w_own, k_ref[...], wk_ref[...]
    )
    k_ref[...] = k_new
    wk_ref[...] = wk_new

    @pl.when(b == pl.num_programs(1) - 1)
    def _commit():
        k_sel = step_select(do, k_new, t)
        k_ref[...] = k_sel
        out_ref[...] = gather_state(planes_ref[...], k_sel)


def _kernel_step_rows(seeds_ref, thr_ref, lw_full_ref, lw_own_ref, planes_ref,
                      k_ref, out_ref, stats_ref, wk_ref, st_ref):
    """Fused STEP over a bank, grid (s, t, b): per-row seeds; the prelude
    re-latches (m, do) at each row's (t, b) == (0, 0) and writes that row's
    ``stats[s]``."""
    s = pl.program_id(0)
    t = pl.program_id(1)
    b = pl.program_id(2)
    n_total = lw_full_ref.shape[1] * LANES

    @pl.when((t == 0) & (b == 0))
    def _prelude():
        m, ess_norm, incr, maxw, deg = step_stats(
            lw_full_ref[0].astype(jnp.float32), n_total)
        do = ess_norm < thr_ref[0]
        st_ref[0] = m
        st_ref[1] = jnp.where(do, jnp.float32(1.0), jnp.float32(0.0))
        st_ref[2] = jnp.where(deg, jnp.float32(1.0), jnp.float32(0.0))
        stats_ref[s, 0] = ess_norm
        stats_ref[s, 1] = jnp.where(do, incr, jnp.float32(0.0))
        stats_ref[s, 2] = jnp.where(do, jnp.float32(1.0), jnp.float32(0.0))
        stats_ref[s, 3] = maxw

    m = st_ref[0]
    do = st_ref[1] > 0.5
    deg = st_ref[2] > 0.5
    w_full = jnp.exp(lw_full_ref[0].astype(jnp.float32) - m)
    w_own = jnp.exp(lw_own_ref[0].astype(jnp.float32) - m)
    w_full = jnp.where(deg, jnp.float32(1.0 / n_total), w_full)
    w_own = jnp.where(deg, jnp.float32(1.0 / n_total), w_own)
    w_full = w_full.astype(lw_full_ref.dtype).astype(jnp.float32)
    w_own = w_own.astype(lw_own_ref.dtype).astype(jnp.float32)
    k_new, wk_new = _sweep(
        t, b, seeds_ref[s], w_full, w_own, k_ref[0], wk_ref[...]
    )
    k_ref[0] = k_new
    wk_ref[...] = wk_new

    @pl.when(b == pl.num_programs(2) - 1)
    def _commit():
        k_sel = step_select(do, k_new, t)
        k_ref[0] = k_sel
        out_ref[0] = gather_state(planes_ref[0], k_sel)


@functools.partial(jax.jit, static_argnames=("num_iters", "interpret"))
def metropolis_pallas(
    weights2d: jnp.ndarray,
    seed: jnp.ndarray,
    *,
    num_iters: int,
    interpret: bool,
) -> jnp.ndarray:
    rows, lanes = weights2d.shape
    assert lanes == LANES and rows % SUBLANES == 0
    num_tiles = rows // SUBLANES

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(num_tiles, num_iters),
        in_specs=[
            # whole weight array resident (the uncoalesced strawman's cost)
            pl.BlockSpec((rows, LANES), lambda t, b, seed: (0, 0)),
            pl.BlockSpec((SUBLANES, LANES), lambda t, b, seed: (t, 0)),
        ],
        out_specs=pl.BlockSpec((SUBLANES, LANES), lambda t, b, seed: (t, 0)),
        scratch_shapes=[pltpu.VMEM((SUBLANES, LANES), jnp.float32)],
    )
    return pl.pallas_call(
        _kernel,
        grid_spec=grid_spec,
        name="metropolis_pallas",
        out_shape=jax.ShapeDtypeStruct((rows, lanes), jnp.int32),
        interpret=interpret,
    )(seed, weights2d, weights2d)


@functools.partial(jax.jit, static_argnames=("num_iters", "interpret"))
def metropolis_pallas_batch(
    weights3d: jnp.ndarray,
    seeds: jnp.ndarray,
    *,
    num_iters: int,
    interpret: bool,
) -> jnp.ndarray:
    """Batched pallas_call: a ``[Bz, R, 128]`` weight bank in ONE launch.

    Same leading batch-grid dimension as the Megopolis bank kernel —
    grid (Bz, num_tiles, num_iters), iteration axis innermost so the VMEM
    ``w[k]`` carry runs the full chain per (row, tile).  ``seeds``:
    uint32[Bz], one stateless-RNG stream per row.  Returns int32[Bz, R, 128];
    row s is bit-identical to ``metropolis_pallas(weights3d[s],
    seeds[s:s+1], ...)``.
    """
    bsz, rows, lanes = weights3d.shape
    assert lanes == LANES and rows % SUBLANES == 0
    num_tiles = rows // SUBLANES

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(bsz, num_tiles, num_iters),
        in_specs=[
            # row s's whole weight array resident (per-row strawman cost)
            pl.BlockSpec((1, rows, LANES), lambda s, t, b, seeds: (s, 0, 0)),
            pl.BlockSpec((1, SUBLANES, LANES), lambda s, t, b, seeds: (s, t, 0)),
        ],
        out_specs=pl.BlockSpec((1, SUBLANES, LANES), lambda s, t, b, seeds: (s, t, 0)),
        scratch_shapes=[pltpu.VMEM((SUBLANES, LANES), jnp.float32)],
    )
    return pl.pallas_call(
        _kernel_batch,
        grid_spec=grid_spec,
        name="metropolis_pallas_batch",
        out_shape=jax.ShapeDtypeStruct((bsz, rows, lanes), jnp.int32),
        interpret=interpret,
    )(seeds, weights3d, weights3d)


@functools.partial(jax.jit, static_argnames=("num_iters", "interpret"))
def metropolis_pallas_fused(
    weights2d: jnp.ndarray,
    planes: jnp.ndarray,
    seed: jnp.ndarray,
    *,
    num_iters: int,
    interpret: bool,
):
    """Fused resample+gather pallas_call: ancestors identical to
    ``metropolis_pallas``; ``planes`` ``[d_pad, R, 128]`` resident.  Returns
    ``(int32[R, 128], [d_pad, R, 128])``."""
    rows, lanes = weights2d.shape
    assert lanes == LANES and rows % SUBLANES == 0
    d_pad = planes.shape[0]
    assert planes.shape[1:] == (rows, lanes)
    num_tiles = rows // SUBLANES

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(num_tiles, num_iters),
        in_specs=[
            pl.BlockSpec((rows, LANES), lambda t, b, seed: (0, 0)),
            pl.BlockSpec((SUBLANES, LANES), lambda t, b, seed: (t, 0)),
            pl.BlockSpec((d_pad, rows, LANES), lambda t, b, seed: (0, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((SUBLANES, LANES), lambda t, b, seed: (t, 0)),
            pl.BlockSpec((d_pad, SUBLANES, LANES), lambda t, b, seed: (0, t, 0)),
        ],
        scratch_shapes=[pltpu.VMEM((SUBLANES, LANES), jnp.float32)],
    )
    return pl.pallas_call(
        _kernel_fused,
        grid_spec=grid_spec,
        name="metropolis_pallas_apply",
        out_shape=[
            jax.ShapeDtypeStruct((rows, lanes), jnp.int32),
            jax.ShapeDtypeStruct((d_pad, rows, lanes), planes.dtype),
        ],
        interpret=interpret,
    )(seed, weights2d, weights2d, planes)


@functools.partial(jax.jit, static_argnames=("num_iters", "interpret"))
def metropolis_pallas_fused_batch(
    weights3d: jnp.ndarray,
    planes4d: jnp.ndarray,
    seeds: jnp.ndarray,
    *,
    num_iters: int,
    interpret: bool,
):
    """Fused bank launch: one leading-batch-grid pallas_call; row s is
    bit-identical to ``metropolis_pallas_fused(weights3d[s], planes4d[s],
    seeds[s:s+1], ...)``.  Returns ``(int32[Bz, R, 128], [Bz, d_pad, R, 128])``."""
    bsz, rows, lanes = weights3d.shape
    assert lanes == LANES and rows % SUBLANES == 0
    d_pad = planes4d.shape[1]
    assert planes4d.shape == (bsz, d_pad, rows, lanes)
    num_tiles = rows // SUBLANES

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(bsz, num_tiles, num_iters),
        in_specs=[
            pl.BlockSpec((1, rows, LANES), lambda s, t, b, seeds: (s, 0, 0)),
            pl.BlockSpec((1, SUBLANES, LANES), lambda s, t, b, seeds: (s, t, 0)),
            pl.BlockSpec(
                (1, d_pad, rows, LANES), lambda s, t, b, seeds: (s, 0, 0, 0)
            ),
        ],
        out_specs=[
            pl.BlockSpec((1, SUBLANES, LANES), lambda s, t, b, seeds: (s, t, 0)),
            pl.BlockSpec(
                (1, d_pad, SUBLANES, LANES), lambda s, t, b, seeds: (s, 0, t, 0)
            ),
        ],
        scratch_shapes=[pltpu.VMEM((SUBLANES, LANES), jnp.float32)],
    )
    return pl.pallas_call(
        _kernel_fused_batch,
        grid_spec=grid_spec,
        name="metropolis_pallas_apply_rows",
        out_shape=[
            jax.ShapeDtypeStruct((bsz, rows, lanes), jnp.int32),
            jax.ShapeDtypeStruct((bsz, d_pad, rows, lanes), planes4d.dtype),
        ],
        interpret=interpret,
    )(seeds, weights3d, weights3d, planes4d)


@functools.partial(jax.jit, static_argnames=("num_iters", "interpret"))
def metropolis_pallas_step(
    log_weights2d: jnp.ndarray,
    planes: jnp.ndarray,
    seed: jnp.ndarray,
    thr: jnp.ndarray,
    *,
    num_iters: int,
    interpret: bool,
):
    """Fused SMC-step pallas_call: normalise → ESS → conditional Alg. 2
    resample → state copy, ONE launch.  ``log_weights2d``: f32[R, 128]
    UNNORMALISED (already whole-array resident here — the strawman's
    residency is exactly what the step prelude needs anyway).  Returns
    ``(int32[R, 128], [d_pad, R, 128], f32[4] = (ess_norm, incr,
    resampled, max_weight))``."""
    rows, lanes = log_weights2d.shape
    assert lanes == LANES and rows % SUBLANES == 0
    d_pad = planes.shape[0]
    assert planes.shape[1:] == (rows, lanes)
    num_tiles = rows // SUBLANES

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,  # seed + f32 ESS threshold
        grid=(num_tiles, num_iters),
        in_specs=[
            pl.BlockSpec((rows, LANES), lambda t, b, seed, thr: (0, 0)),
            pl.BlockSpec((SUBLANES, LANES), lambda t, b, seed, thr: (t, 0)),
            pl.BlockSpec((d_pad, rows, LANES), lambda t, b, seed, thr: (0, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((SUBLANES, LANES), lambda t, b, seed, thr: (t, 0)),
            pl.BlockSpec((d_pad, SUBLANES, LANES), lambda t, b, seed, thr: (0, t, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        scratch_shapes=[
            pltpu.VMEM((SUBLANES, LANES), jnp.float32),
            pltpu.SMEM((3,), jnp.float32),
        ],
    )
    return pl.pallas_call(
        _kernel_step,
        grid_spec=grid_spec,
        name="metropolis_pallas_step",
        out_shape=[
            jax.ShapeDtypeStruct((rows, lanes), jnp.int32),
            jax.ShapeDtypeStruct((d_pad, rows, lanes), planes.dtype),
            jax.ShapeDtypeStruct((4,), jnp.float32),
        ],
        interpret=interpret,
    )(seed, thr, log_weights2d, log_weights2d, planes)


@functools.partial(jax.jit, static_argnames=("num_iters", "interpret"))
def metropolis_pallas_step_rows(
    log_weights3d: jnp.ndarray,
    planes4d: jnp.ndarray,
    seeds: jnp.ndarray,
    thr: jnp.ndarray,
    *,
    num_iters: int,
    interpret: bool,
):
    """Fused SMC-step bank launch: row s is bit-identical to
    ``metropolis_pallas_step(log_weights3d[s], planes4d[s], seeds[s:s+1],
    thr, ...)``.  Returns ``(int32[Bz, R, 128], [Bz, d_pad, R, 128],
    f32[Bz, 4])``."""
    bsz, rows, lanes = log_weights3d.shape
    assert lanes == LANES and rows % SUBLANES == 0
    d_pad = planes4d.shape[1]
    assert planes4d.shape == (bsz, d_pad, rows, lanes)
    num_tiles = rows // SUBLANES

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(bsz, num_tiles, num_iters),
        in_specs=[
            pl.BlockSpec((1, rows, LANES), lambda s, t, b, se, r: (s, 0, 0)),
            pl.BlockSpec((1, SUBLANES, LANES), lambda s, t, b, se, r: (s, t, 0)),
            pl.BlockSpec(
                (1, d_pad, rows, LANES), lambda s, t, b, se, r: (s, 0, 0, 0)
            ),
        ],
        out_specs=[
            pl.BlockSpec((1, SUBLANES, LANES), lambda s, t, b, se, r: (s, t, 0)),
            pl.BlockSpec(
                (1, d_pad, SUBLANES, LANES), lambda s, t, b, se, r: (s, 0, t, 0)
            ),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        scratch_shapes=[
            pltpu.VMEM((SUBLANES, LANES), jnp.float32),
            pltpu.SMEM((3,), jnp.float32),
        ],
    )
    return pl.pallas_call(
        _kernel_step_rows,
        grid_spec=grid_spec,
        name="metropolis_pallas_step_rows",
        out_shape=[
            jax.ShapeDtypeStruct((bsz, rows, lanes), jnp.int32),
            jax.ShapeDtypeStruct((bsz, d_pad, rows, lanes), planes4d.dtype),
            jax.ShapeDtypeStruct((bsz, 4), jnp.float32),
        ],
        interpret=interpret,
    )(seeds, thr, log_weights3d, log_weights3d, planes4d)
