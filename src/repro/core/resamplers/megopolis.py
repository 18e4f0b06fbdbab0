"""Megopolis resampling (paper Algorithm 5) — reference JAX implementation.

The key structural idea: the ``B`` random comparison indices are drawn ONCE,
globally, as offsets ``o[b] ~ U{0, N-1}`` shared by all particles.  At
iteration ``b`` particle ``i`` compares its current ancestor ``k`` against

    j = (aligned(i) + aligned(o[b]) + (i + o[b]) mod S) mod N

where ``S`` is the coalescing segment size (32 on the paper's GPU warps;
1024 = one (8,128) f32 VMEM tile for the TPU kernel in
``repro.kernels.megopolis``).  For each fixed ``o[b]`` the map ``i -> j`` is
a segment-aligned global rotation — a bijection — so every particle is
exposed exactly once per iteration, which is what drives Megopolis' lower
offspring variance (paper §6.1).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.resamplers.batched import split_batch_keys

DEFAULT_SEGMENT = 32  # paper-faithful warp size; TPU kernel uses 1024.


def megopolis_indices(i: jnp.ndarray, offset, segment: int, n: int) -> jnp.ndarray:
    """The Megopolis comparison-index map (Alg. 5 lines 7-11), vectorised.

    Exposed separately so the Pallas kernel's ``ref.py``, the distributed
    shard_map version, and property tests all share one definition.
    """
    i_aligned = i - (i % segment)
    o_aligned = offset - (offset % segment)
    o_unaligned = (i + offset) % segment
    return (i_aligned + o_aligned + o_unaligned) % n


def megopolis(
    key: jax.Array,
    weights: jnp.ndarray,
    num_iters: int,
    *,
    segment: int = DEFAULT_SEGMENT,
    offsets: Optional[jnp.ndarray] = None,
) -> jnp.ndarray:
    """Resample; returns int32 ancestor indices (paper Algorithm 5).

    Args:
      key: PRNG key.
      weights: ``f32[N]`` unnormalised, non-negative particle weights.
      num_iters: ``B`` — accept/reject iterations (see ``select_iterations``).
      segment: coalescing segment size ``S``; any ``S >= 1`` is valid
        (Proposition 1 needs only bijectivity + uniformity, both independent
        of ``S``).
      offsets: optional pre-drawn ``int[num_iters]`` global offsets.  When
        given they replace the internal draw (the accept/reject uniforms are
        unchanged — the key is split identically either way); this is the
        injection point the shared-offset batched mode builds on.
    """
    n = weights.shape[0]
    key_off, key_u = jax.random.split(key)
    if offsets is None:
        offsets = jax.random.randint(key_off, (num_iters,), 0, n)
    i = jnp.arange(n, dtype=jnp.int32)

    def body(b, carry):
        k, wk = carry
        j = megopolis_indices(i, offsets[b], segment, n).astype(jnp.int32)
        w_j = _comparison_weights(weights, offsets[b], segment, j)
        u = jax.random.uniform(jax.random.fold_in(key_u, b), (n,), weights.dtype)
        # u <= w[j] / w[k]  <=>  u * w[k] <= w[j]   (division-free, w >= 0)
        accept = u * wk <= w_j
        return jnp.where(accept, j, k), jnp.where(accept, w_j, wk)

    # w[k] is carried by value beside k (exactly ``weights[k]``), as the
    # kernel carries it, so no iteration gathers by the ancestor.
    k, _ = jax.lax.fori_loop(0, num_iters, body, (i, weights))
    return k


def _comparison_weights(weights: jnp.ndarray, offset, segment: int, j) -> jnp.ndarray:
    """``weights[j]`` for the comparison map of one offset.

    Where N is whole segments the map is a rotation of the ``(N/S, S)``
    view — segments by ``o // S``, lanes within a segment by ``o mod S`` —
    so the values come from two rolls instead of an N-element gather (the
    paper's coalescing, in XLA form; an XLA gather of 2^20 words costs
    milliseconds on a TPU).  Otherwise the index map wraps mid-segment and
    ``weights[j]`` is gathered."""
    n = weights.shape[0]
    if n % segment:
        return weights[j]
    w2 = weights.reshape(n // segment, segment)
    w2 = jnp.roll(w2, -(offset // segment), axis=0)
    return jnp.roll(w2, -(offset % segment), axis=1).reshape(n)


def megopolis_batch(
    key: jax.Array,
    weights: jnp.ndarray,
    num_iters: int,
    *,
    segment: int = DEFAULT_SEGMENT,
    shared_offsets: bool = False,
) -> jnp.ndarray:
    """Batched Megopolis over ``weights[B, N]`` — one launch (DESIGN.md §4).

    ``shared_offsets=False`` (registry default): the standard batched
    contract — row ``b`` is bit-identical to
    ``megopolis(split(key, B)[b], weights[b], ...)``; every row draws its
    own offset table.

    ``shared_offsets=True`` (hand-batched, Alg. 5's structure): the global
    offsets ``o[1..num_iters]`` are drawn ONCE and shared by every row, so
    per iteration the comparison map ``i -> j`` is one index vector for the
    whole bank and the ``w[:, j]`` gather is a single batch-uniform pattern
    — the batch-axis analogue of the paper's warp-shared offset (and what
    the batched Pallas kernel scalar-prefetches).  Row ``b`` then equals
    ``megopolis(split(key, B)[b], weights[b], ..., offsets=offsets)``;
    accept/reject uniforms stay per-row independent.
    """
    if weights.ndim != 2:
        raise ValueError(f"megopolis_batch expects weights[B, N]; got shape {weights.shape}")
    bsz, n = weights.shape
    keys = split_batch_keys(key, bsz)
    if not shared_offsets:
        return jax.vmap(lambda k, w: megopolis(k, w, num_iters, segment=segment))(keys, weights)

    # One global offset table for the whole bank (drawn from key, not from
    # any row key, so no row's uniform stream is correlated with it).
    offsets = jax.random.randint(jax.random.fold_in(key, num_iters), (num_iters,), 0, n)
    keys_u = jax.vmap(lambda k: jax.random.split(k)[1])(keys)
    i = jnp.arange(n, dtype=jnp.int32)

    def body(b, k):
        j = megopolis_indices(i, offsets[b], segment, n).astype(jnp.int32)
        u = jax.vmap(
            lambda kk: jax.random.uniform(jax.random.fold_in(kk, b), (n,), weights.dtype)
        )(keys_u)
        w_k = jnp.take_along_axis(weights, k, axis=1)
        w_j = weights[:, j]  # shared j: one gather pattern bank-wide
        accept = u * w_k <= w_j
        return jnp.where(accept, j[None, :], k)

    return jax.lax.fori_loop(0, num_iters, body, jnp.broadcast_to(i, (bsz, n)))
