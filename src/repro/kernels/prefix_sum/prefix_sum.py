"""Inclusive prefix sum — Pallas TPU kernel (block scan + sequential carry).

Backs the prefix-sum resamplers (multinomial Alg. 7, systematic Alg. 8)
the paper compares against in §6.5.  The TPU grid is sequential, so the
cross-block carry is a single SMEM scalar threaded through grid steps —
no second pass, no atomics (contrast the GPU's Blelloch two-phase scan).

The f32 numerical-instability story the paper tells (§1) is reproducible
with this kernel: summing 2^22 weights in f32 loses ~2-3 digits vs f64,
which is what inflates multinomial/systematic bias at large N (Fig. 8).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

SUBLANES = 8
LANES = 128
SEG = SUBLANES * LANES


def _kernel(x_ref, y_ref, carry_ref):
    t = pl.program_id(0)

    @pl.when(t == 0)
    def _init():
        carry_ref[0] = jnp.zeros((), jnp.float32)

    # Operand tiles may arrive compressed (DESIGN.md §14); the scan itself —
    # and the CDF it emits — is always f32, so bisection boundaries match the
    # f32 kernels bitwise.
    flat = x_ref[...].astype(jnp.float32).reshape(SEG)
    local = jnp.cumsum(flat)
    y_ref[...] = (local + carry_ref[0]).reshape(SUBLANES, LANES)
    carry_ref[0] = carry_ref[0] + local[-1]


def scan_tiles(x2d: jnp.ndarray) -> jnp.ndarray:
    """In-VALUE replica of ``_kernel``'s grid walk, for use INSIDE other
    kernel bodies (the fused step): cumsum per (8, 128) tile flattened to
    SEG lanes, scalar carry across tiles.  The per-tile arithmetic is
    ``_kernel``'s line for line, so the resulting CDF is bit-identical to
    ``prefix_sum_pallas`` on the same input — the property the fused-step
    parity gate rests on."""
    rows = x2d.shape[0]
    num_tiles = rows // SUBLANES

    def body(carry, tile):
        local = jnp.cumsum(tile.astype(jnp.float32).reshape(SEG))
        y = local + carry
        return carry + local[-1], y.reshape(SUBLANES, LANES)

    _, ys = jax.lax.scan(
        body, jnp.zeros((), jnp.float32), x2d.reshape(num_tiles, SUBLANES, LANES)
    )
    return ys.reshape(x2d.shape)


@functools.partial(jax.jit, static_argnames=("interpret",))
def prefix_sum_pallas(x2d: jnp.ndarray, *, interpret: bool) -> jnp.ndarray:
    rows, lanes = x2d.shape
    assert lanes == LANES and rows % SUBLANES == 0
    num_tiles = rows // SUBLANES
    return pl.pallas_call(
        _kernel,
        grid=(num_tiles,),
        in_specs=[pl.BlockSpec((SUBLANES, LANES), lambda t: (t, 0))],
        out_specs=pl.BlockSpec((SUBLANES, LANES), lambda t: (t, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, lanes), jnp.float32),
        scratch_shapes=[pltpu.SMEM((1,), jnp.float32)],
        interpret=interpret,
    )(x2d)
