"""Fused SMC step for the prefix-sum family — ONE pallas_call (DESIGN.md §12).

The composed prefix-sum path is the family's launch-count worst case: a
block-scan launch (three for residual), plus a search launch, plus host-side
normalise/ESS/branch glue.  The fused step folds the WHOLE composition into
a single grid=(1,) kernel over resident arrays:

  log-weights → (m, ESS, logZ incr) prelude → exp(lw - m) → in-kernel tile
  scan (``prefix_sum.scan_tiles``, bit-identical to the scan kernel) →
  draw scaling → full-array bisection (``search._bisect_any``) → slot select
  (residual) → identity-or-selection commit → state gather.

Randomness placement keeps the family's host/kernel split (ops.py): the
KEY-dependent part of every draw — ``uniform(key, (n,))`` or the scalar
``uniform(key, ())`` — is drawn OUTSIDE with ``jax.random`` exactly as
``kind_draws`` does, while the CDF-dependent SCALE (``total`` or
``total / n``) is applied in-kernel.  Because the in-kernel CDF is
bit-identical to the scan kernel's and the scaling expressions are the
same f32 ops, every draw — and therefore every ancestor — matches the
composed path bitwise.

Residency: everything (log-weights, draw bases, CDFs, state planes) is
VMEM-resident, so the family's usual CDF cap applies (checked in ops.py).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import gather_state_full, step_stats
from repro.kernels.prefix_sum.prefix_sum import LANES, SUBLANES, scan_tiles
from repro.kernels.prefix_sum.search import _bisect_any


def _full_lane_ids(rows: int) -> jnp.ndarray:
    """Flat row-major particle index of every lane of the whole (rows, 128)
    array — the full-array analogue of ``tile_lane_ids``."""
    row = lax.broadcasted_iota(jnp.int32, (rows, LANES), 0)
    col = lax.broadcasted_iota(jnp.int32, (rows, LANES), 1)
    return row * LANES + col


def _make_kernel_step(n_total: int, rows: int, kind: str):
    def _kernel(u0_ref, thr_ref, lw_ref, ubase_ref, planes_ref,
                k_ref, out_ref, stats_ref):
        m, ess_norm, incr, maxw, deg = step_stats(
            lw_ref[...].astype(jnp.float32), n_total)
        do = ess_norm < thr_ref[0]
        stats_ref[0] = ess_norm
        stats_ref[1] = jnp.where(do, incr, jnp.float32(0.0))
        stats_ref[2] = jnp.where(do, jnp.float32(1.0), jnp.float32(0.0))
        stats_ref[3] = maxw

        # Normalised weights re-land on the plane-dtype grid (the composed
        # path quantises at the public ``apply`` boundary); a no-op at f32.
        # The §16 degenerate substitution precedes the requantise, exactly
        # as ``normalise_log_weights`` orders it on the host.
        w2d = jnp.exp(lw_ref[...].astype(jnp.float32) - m)
        w2d = jnp.where(deg, jnp.float32(1.0 / n_total), w2d)
        w2d = w2d.astype(lw_ref.dtype).astype(jnp.float32)
        slots = _full_lane_ids(rows)

        if kind == "residual":
            # the three-scan residual composition, in-value (ops._residual_tpu_fused)
            total = scan_tiles(w2d).reshape(n_total)[-1]
            wn = w2d / total
            counts = jnp.floor(jnp.float32(n_total) * wn)
            n_det = jnp.sum(counts.reshape(n_total)).astype(jnp.int32)
            resid = jnp.float32(n_total) * wn - counts
            cc_flat = scan_tiles(counts).reshape(n_total)
            c_flat = scan_tiles(resid).reshape(n_total)
            u2d = ubase_ref[...] * c_flat[-1]
            det = _bisect_any(cc_flat, slots.astype(c_flat.dtype), "right", n_total)
            rnd = _bisect_any(c_flat, u2d, "right", n_total)
            k = jnp.where(slots < n_det, det, rnd)
        else:
            c_flat = scan_tiles(w2d).reshape(n_total)
            total = c_flat[-1]
            if kind == "multinomial":
                u2d, side = ubase_ref[...] * total, "right"
            elif kind in ("systematic", "improved_systematic"):
                idx = slots.astype(c_flat.dtype)
                u2d, side = (idx + u0_ref[0]) * (total / n_total), "left"
            else:  # stratified
                idx = slots.astype(c_flat.dtype)
                u2d, side = (idx + ubase_ref[...]) * (total / n_total), "left"
            k = _bisect_any(c_flat, u2d, side, n_total)

        k_sel = jnp.where(do, k, slots)
        k_ref[...] = k_sel
        out_ref[...] = gather_state_full(planes_ref[...], k_sel)

    return _kernel


@functools.partial(jax.jit, static_argnames=("kind", "interpret"))
def prefix_pallas_step(
    log_weights2d: jnp.ndarray,
    planes: jnp.ndarray,
    ubase2d: jnp.ndarray,
    u0: jnp.ndarray,
    thr: jnp.ndarray,
    *,
    kind: str,
    interpret: bool,
):
    """Fused SMC-step pallas_call for one prefix-sum kind.  ``ubase2d``:
    the key-only uniform base draws reshaped (R, 128) (zeros for the
    systematic pair); ``u0``: f32[1] scalar base (zeros unless systematic).
    Returns ``(int32[R, 128], [d_pad, R, 128], f32[4] = (ess_norm, incr,
    resampled, max_weight))``."""
    rows, lanes = log_weights2d.shape
    assert lanes == LANES and rows % SUBLANES == 0
    d_pad = planes.shape[0]
    assert planes.shape[1:] == (rows, lanes)
    assert ubase2d.shape == (rows, lanes)
    n_total = rows * lanes

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,  # scalar draw base + f32 ESS threshold
        grid=(1,),
        in_specs=[
            pl.BlockSpec((rows, LANES), lambda i, u0, thr: (0, 0)),
            pl.BlockSpec((rows, LANES), lambda i, u0, thr: (0, 0)),
            pl.BlockSpec((d_pad, rows, LANES), lambda i, u0, thr: (0, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((rows, LANES), lambda i, u0, thr: (0, 0)),
            pl.BlockSpec((d_pad, rows, LANES), lambda i, u0, thr: (0, 0, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
    )
    return pl.pallas_call(
        _make_kernel_step(n_total, rows, kind),
        grid_spec=grid_spec,
        name="prefix_sum_pallas_step",
        out_shape=[
            jax.ShapeDtypeStruct((rows, lanes), jnp.int32),
            jax.ShapeDtypeStruct((d_pad, rows, lanes), planes.dtype),
            jax.ShapeDtypeStruct((4,), jnp.float32),
        ],
        interpret=interpret,
    )(u0, thr, log_weights2d, ubase2d, planes)
