"""The one f32 summation order shared by the host helpers and the kernels.

XLA picks its own order for a ``reduce`` and changes it with the shape and
the fusion around it; Mosaic picks another.  A statistic that the fused step
kernels compute on-chip and ``repro.core.metrics`` computes on the host must
come out bit-identical, so both sum through the explicit pairwise tree below.
"""

from __future__ import annotations

import jax.numpy as jnp
from jax import lax

_LANES = 128


def _halve(x: jnp.ndarray, axis: int) -> jnp.ndarray:
    """One level of the pairwise tree: ``x[:h] + x[n-h:]`` along ``axis``
    (``h = n // 2``), the middle element of an odd ``n`` carried through."""
    n = x.shape[axis]
    h, r = n // 2, n - n // 2
    s = lax.slice_in_dim(x, 0, h, axis=axis) + lax.slice_in_dim(x, r, n, axis=axis)
    if r != h:
        s = jnp.concatenate([s, lax.slice_in_dim(x, h, r, axis=axis)], axis=axis)
    return s


def block_tree_sum(x: jnp.ndarray) -> jnp.ndarray:
    """Sum a ``[..., rows, 128]`` block over its last two axes in ONE fixed
    order: a pairwise tree over rows, then over lanes.

    Every add is an explicit elementwise op, which neither XLA nor Mosaic
    reassociates, so the f32 result is the same on every backend and in
    every fusion context: the fused step kernels call this on their
    resident ``(rows, 128)`` log-weight block, the ``repro.core.metrics``
    helpers (through ``tree_sum``) on the same layout, and the two agree bit
    for bit wherever their ``exp`` agrees."""
    while x.shape[-2] > 1:
        x = _halve(x, x.ndim - 2)
    while x.shape[-1] > 1:
        x = _halve(x, x.ndim - 1)
    return x[..., 0, 0]


def tree_sum(x: jnp.ndarray, axis: int = -1) -> jnp.ndarray:
    """Sum over ``axis`` in the ``block_tree_sum`` order: the axis is laid
    out as ``(rows, 128)`` row-major, zero-padded to whole rows (adding
    zero is exact), exactly as the kernels lay out a particle plane."""
    x = jnp.moveaxis(x, axis, -1)
    n = x.shape[-1]
    pad = -n % _LANES
    if pad:
        x = jnp.concatenate([x, jnp.zeros(x.shape[:-1] + (pad,), x.dtype)], axis=-1)
    return block_tree_sum(x.reshape(x.shape[:-1] + ((n + pad) // _LANES, _LANES)))
