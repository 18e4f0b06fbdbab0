"""Coalesced binary search over a resident CDF — Pallas TPU kernel.

Second stage of the prefix-sum resamplers (paper §6.5, Algs. 7-8): after
the block-scan kernel has produced the inclusive CDF, every output slot
``i`` finds its ancestor by bisecting the CDF for its draw ``u_i``.

Memory contract: the search positions are data-dependent, so the CDF stays
VMEM-resident (same residency cap as the Metropolis strawman — the
prefix-sum family's own scaling wall on this hardware); the ``u`` draws
stream through in aligned (8, 128) tiles, one grid step per tile, and the
output ancestors store coalesced.  Each of the ``ceil(log2(N+1))``
bisection steps is one in-register gather across the tile's 1024 lanes —
no HBM traffic after the single CDF fetch.

``side`` follows ``jnp.searchsorted``: 'left' returns the first index with
``c[idx] >= u`` (systematic/stratified), 'right' the first with
``c[idx] > u`` (multinomial/residual).  Results are clipped to N-1 so they
are always valid ancestor indices even for ``u >= c[-1]`` edge draws.

Validated bit-exactly against ``jnp.searchsorted`` in ``ref.py``.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import gather_state, tile_lane_ids

SUBLANES = 8
LANES = 128
SEG = SUBLANES * LANES


def _bisect_any(c_flat, u, side: str, n_total: int):
    """Shape-generic bisection core: ``u`` may be any 2-D tile (the search
    kernels pass (8, 128) blocks; the fused step kernel passes the whole
    (R, 128) array).  Each lane's trajectory depends only on its own
    ``u`` value and the shared CDF — same loop count either way — so a
    full-array call is bit-identical per lane to the per-tile calls."""
    n_steps = max(1, math.ceil(math.log2(n_total + 1)))
    lo = jnp.zeros(u.shape, jnp.int32)
    hi = jnp.full(u.shape, n_total, jnp.int32)

    def step(_, state):
        lo, hi = state
        active = lo < hi
        mid = (lo + hi) // 2
        cm = jnp.take(c_flat, mid.reshape(-1), axis=0).reshape(u.shape)
        pred = (cm < u) if side == "left" else (cm <= u)
        lo = jnp.where(active & pred, mid + 1, lo)
        hi = jnp.where(active & ~pred, mid, hi)
        return lo, hi

    lo, _ = jax.lax.fori_loop(0, n_steps, step, (lo, hi))
    return jnp.minimum(lo, n_total - 1)


def _bisect(c_flat, u, side: str, n_total: int):
    """The tile-parallel bisection every search kernel shares: int32[8, 128]
    first index with ``c[idx] >= u`` ('left') / ``c[idx] > u`` ('right'),
    clipped to N-1.  One in-register gather per step."""
    return _bisect_any(c_flat, u, side, n_total)


def _make_kernel(n_total: int, side: str):
    def _kernel(c_ref, u_ref, k_ref):
        c_flat = c_ref[...].reshape(n_total)
        k_ref[...] = _bisect(c_flat, u_ref[...], side, n_total)

    return _kernel


@functools.partial(jax.jit, static_argnames=("side", "interpret"))
def searchsorted_pallas(
    cdf2d: jnp.ndarray,
    u2d: jnp.ndarray,
    *,
    side: str = "left",
    interpret: bool,
) -> jnp.ndarray:
    """``cdf2d``: non-decreasing f32[R, 128] (flat row-major CDF);
    ``u2d``: f32[R, 128] of search values.  Returns int32[R, 128] indices
    (clipped to N-1)."""
    assert side in ("left", "right")
    rows, lanes = cdf2d.shape
    assert lanes == LANES and rows % SUBLANES == 0
    assert u2d.shape == (rows, lanes)
    num_tiles = rows // SUBLANES
    n_total = rows * lanes

    return pl.pallas_call(
        _make_kernel(n_total, side),
        grid=(num_tiles,),
        in_specs=[
            # whole CDF resident; fetched once (block index constant in t)
            pl.BlockSpec((rows, LANES), lambda t: (0, 0)),
            pl.BlockSpec((SUBLANES, LANES), lambda t: (t, 0)),
        ],
        out_specs=pl.BlockSpec((SUBLANES, LANES), lambda t: (t, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, lanes), jnp.int32),
        interpret=interpret,
    )(cdf2d, u2d)


def _make_kernel_fused(n_total: int, side: str):
    def _kernel(c_ref, u_ref, planes_ref, k_ref, out_ref):
        c_flat = c_ref[...].reshape(n_total)
        k = _bisect(c_flat, u_ref[...], side, n_total)
        k_ref[...] = k
        out_ref[...] = gather_state(planes_ref[...], k)

    return _kernel


@functools.partial(jax.jit, static_argnames=("side", "interpret"))
def searchsorted_gather_pallas(
    cdf2d: jnp.ndarray,
    u2d: jnp.ndarray,
    planes: jnp.ndarray,
    *,
    side: str = "left",
    interpret: bool,
):
    """Fused search+gather (DESIGN.md §11): the bisection result indexes the
    resident state plane stack in the SAME grid step — the prefix-sum
    family's ancestor indices never leave VMEM.  Returns ``(int32[R, 128],
    [d_pad, R, 128])``; indices identical to ``searchsorted_pallas``."""
    assert side in ("left", "right")
    rows, lanes = cdf2d.shape
    assert lanes == LANES and rows % SUBLANES == 0
    assert u2d.shape == (rows, lanes)
    d_pad = planes.shape[0]
    assert planes.shape[1:] == (rows, lanes)
    num_tiles = rows // SUBLANES
    n_total = rows * lanes

    return pl.pallas_call(
        _make_kernel_fused(n_total, side),
        grid=(num_tiles,),
        in_specs=[
            pl.BlockSpec((rows, LANES), lambda t: (0, 0)),
            pl.BlockSpec((SUBLANES, LANES), lambda t: (t, 0)),
            pl.BlockSpec((d_pad, rows, LANES), lambda t: (0, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((SUBLANES, LANES), lambda t: (t, 0)),
            pl.BlockSpec((d_pad, SUBLANES, LANES), lambda t: (0, t, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((rows, lanes), jnp.int32),
            jax.ShapeDtypeStruct((d_pad, rows, lanes), planes.dtype),
        ],
        interpret=interpret,
    )(cdf2d, u2d, planes)


def _make_kernel_residual_fused(n_total: int):
    def _kernel(ndet_ref, cc_ref, c_ref, u_ref, planes_ref, k_ref, out_ref):
        t = pl.program_id(0)
        slots = tile_lane_ids(t)
        cc_flat = cc_ref[...].reshape(n_total)
        c_flat = c_ref[...].reshape(n_total)
        # Both searches of the residual composition run in ONE grid step:
        # deterministic copies bisect the counts CDF at the slot index,
        # stochastic slots bisect the residual CDF at their draw.
        det = _bisect(cc_flat, slots.astype(c_flat.dtype), "right", n_total)
        rnd = _bisect(c_flat, u_ref[...], "right", n_total)
        k = jnp.where(slots < ndet_ref[0], det, rnd)
        k_ref[...] = k
        out_ref[...] = gather_state(planes_ref[...], k)

    return _kernel


@functools.partial(jax.jit, static_argnames=("interpret",))
def residual_select_gather_pallas(
    cc2d: jnp.ndarray,
    c2d: jnp.ndarray,
    u2d: jnp.ndarray,
    n_det: jnp.ndarray,
    planes: jnp.ndarray,
    *,
    interpret: bool,
):
    """Fused residual tail (DESIGN.md §11): deterministic-copy search,
    residual search, slot select and state gather in one kernel.  ``cc2d``:
    the deterministic-count CDF; ``c2d``: the residual CDF; ``u2d``: the
    residual draws (already scaled by the CDF total); ``n_det``: int32[1]
    deterministic slot count (scalar-prefetched).  Index arithmetic is
    bit-identical to the two-``searchsorted_pallas`` + ``jnp.where``
    composition in ``ops._residual_tpu``."""
    rows, lanes = cc2d.shape
    assert lanes == LANES and rows % SUBLANES == 0
    assert c2d.shape == (rows, lanes) and u2d.shape == (rows, lanes)
    d_pad = planes.shape[0]
    assert planes.shape[1:] == (rows, lanes)
    num_tiles = rows // SUBLANES
    n_total = rows * lanes

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(num_tiles,),
        in_specs=[
            pl.BlockSpec((rows, LANES), lambda t, nd: (0, 0)),
            pl.BlockSpec((rows, LANES), lambda t, nd: (0, 0)),
            pl.BlockSpec((SUBLANES, LANES), lambda t, nd: (t, 0)),
            pl.BlockSpec((d_pad, rows, LANES), lambda t, nd: (0, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((SUBLANES, LANES), lambda t, nd: (t, 0)),
            pl.BlockSpec((d_pad, SUBLANES, LANES), lambda t, nd: (0, t, 0)),
        ],
    )
    return pl.pallas_call(
        _make_kernel_residual_fused(n_total),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((rows, lanes), jnp.int32),
            jax.ShapeDtypeStruct((d_pad, rows, lanes), planes.dtype),
        ],
        interpret=interpret,
    )(n_det, cc2d, c2d, u2d, planes)
