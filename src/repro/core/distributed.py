"""Distributed Megopolis — the paper's coalescing contract at chip level.

The paper coalesces at the warp/segment level; we add one more level of the
same decomposition for a sharded particle population (DESIGN.md §3):

    o  ~ U{0, N-1}           (one global offset per iteration, as in Alg. 5)
    o  = o_shard * L + o_local          (L = particles per shard)
    j  = ((s + o_shard) mod D) * L  +  megopolis_local(i_local, o_local)

Properties preserved: (i) per-iteration ``i -> j`` is a bijection (shard
rotation x within-shard Megopolis bijection); (ii) ``j | o`` is uniform over
[0, N) (``(o_shard, o_local)`` uniform over D x L).  Proposition 1 therefore
carries over verbatim — same B, same convergence rate.

Communication per iteration is ONE contiguous block exchange (the inter-chip
analogue of a coalesced transaction):

  * ``schedule="static"``  — the shard-level offsets are derived from a
    host-known seed at trace time, so each iteration lowers to a single
    ``ppermute`` (1x block traffic).  The within-shard offset stays runtime-
    random.  Theory note: uniformity of ``j`` then holds over the schedule
    draw rather than per-trace; MSE/bias parity is verified empirically.
  * ``schedule="dynamic"`` — shard offsets are runtime-random; the dynamic
    rotation is routed as a hypercube composition of log2(D) conditional
    static ppermutes (exact Proposition-1 uniformity, log2(D)x traffic).

Ancestor payloads: ``gather_ancestors`` (exact, all-gather) or
``island_exchange`` (local resampling + periodic ring mixing, Vergé et al.).
"""

from __future__ import annotations

from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.core.resamplers.megopolis import megopolis_indices
from repro.kernels.common import hash_uniform, key_to_seed, murmur3_fmix


def _rotate_blocks_dynamic(x, shift, axis_name: str, n_shards: int):
    """Rotate shard-local blocks by a *traced* shift: value at shard s ends
    up at shard (s - shift) mod D.  Hypercube: log2(D) conditional hops."""
    assert n_shards & (n_shards - 1) == 0, "shard count must be a power of two"
    bit = 0
    step = 1
    while step < n_shards:
        perm = [(src, (src - step) % n_shards) for src in range(n_shards)]
        x_shifted = lax.ppermute(x, axis_name, perm)
        take = ((shift >> bit) & 1) == 1
        x = jnp.where(take, x_shifted, x)
        bit += 1
        step <<= 1
    return x


def _rotate_blocks_static(x, shift: int, axis_name: str, n_shards: int):
    shift = int(shift) % n_shards
    if shift == 0:
        return x
    perm = [(src, (src - shift) % n_shards) for src in range(n_shards)]
    return lax.ppermute(x, axis_name, perm)


def _static_shard_schedule(seed: int, num_iters: int, n_shards: int) -> list[int]:
    """Host-side deterministic shard-offset schedule (trace-time ints)."""
    out = []
    x = np.uint32(seed)
    for b in range(num_iters):
        x = np.asarray(murmur3_fmix(jnp.uint32(int(x) + b + 1)))
        out.append(int(x) % n_shards)
    return out


def megopolis_shard(
    seed: jnp.ndarray,
    offsets_local: jnp.ndarray,
    offsets_shard,
    local_weights: jnp.ndarray,
    *,
    axis_name: str,
    num_iters: int,
    segment: int = 1024,
    schedule: str = "static",
) -> jnp.ndarray:
    """Runs INSIDE shard_map.  Returns int32[L] GLOBAL ancestor indices.

    ``offsets_local``: int32[B] traced, uniform over [0, L).
    ``offsets_shard``: list[int] (static mode) or int32[B] traced (dynamic).
    """
    n_local = local_weights.shape[0]
    n_shards = lax.axis_size(axis_name)
    s = lax.axis_index(axis_name)
    i_local = jnp.arange(n_local, dtype=jnp.int32)
    i_global = s * n_local + i_local

    k = i_global
    wk = local_weights
    rotated: dict = {}  # static schedule: distinct shard offsets <= D, so
    # rotations dedupe — with B > D this cuts ppermute traffic ~B/D-fold
    # (§Perf iteration: hypothesis confirmed, EXPERIMENTS.md)
    for b in range(num_iters):
        o_l = offsets_local[b]
        if schedule == "static":
            o_s = int(offsets_shard[b]) % int(n_shards)
            if o_s not in rotated:
                rotated[o_s] = _rotate_blocks_static(
                    local_weights, o_s, axis_name, int(n_shards))
            w_blk = rotated[o_s]
            src_shard = (s + o_s) % n_shards
        else:
            o_s = offsets_shard[b]
            w_blk = _rotate_blocks_dynamic(local_weights, o_s, axis_name, int(n_shards))
            src_shard = (s + o_s) % n_shards
        j_local = megopolis_indices(i_local, o_l, segment, n_local).astype(jnp.int32)
        w_j = jnp.take(w_blk, j_local, axis=0)
        j_global = src_shard.astype(jnp.int32) * n_local + j_local
        u = hash_uniform(seed, i_global, b, dtype=local_weights.dtype)
        accept = u * wk <= w_j
        k = jnp.where(accept, j_global, k)
        wk = jnp.where(accept, w_j, wk)
    return k


def gather_ancestors(x_local: jnp.ndarray, ancestors_global: jnp.ndarray, *, axis_name: str):
    """Exact cross-shard payload gather (all-gather strategy).

    Fine for PF-scale payloads (the paper's states are scalars/small
    vectors); for LM KV caches use island mode instead.
    """
    x_all = lax.all_gather(x_local, axis_name, axis=0, tiled=True)
    return jnp.take(x_all, ancestors_global, axis=0)


def island_exchange(x_local: jnp.ndarray, *, axis_name: str, fraction: float = 0.25):
    """Ring-mix a leading fraction of local particles with the next shard
    (island-model particle exchange; Vergé et al. [46])."""
    n_shards = lax.axis_size(axis_name)
    m = max(1, int(x_local.shape[0] * fraction))
    perm = [(src, (src + 1) % n_shards) for src in range(int(n_shards))]
    head = lax.ppermute(x_local[:m], axis_name, perm)
    return jnp.concatenate([head, x_local[m:]], axis=0)


def effective_sample_size(local_weights: jnp.ndarray, *, axis_name: str):
    """Global ESS = (sum w)^2 / sum w^2 via psum (resampling trigger)."""
    s1 = lax.psum(jnp.sum(local_weights), axis_name)
    s2 = lax.psum(jnp.sum(local_weights**2), axis_name)
    return s1 * s1 / jnp.maximum(s2, 1e-30)


def make_distributed_resampler(
    mesh,
    *,
    spec=None,
    axis_name: str = "data",
    num_iters: int = 32,
    segment: int = 1024,
    schedule: str = "static",
    static_seed: int = 0xA5A5,
):
    """Build a jitted global-array resampler over ``mesh``.

    ``spec`` (a ``MegopolisSpec``, DESIGN.md §9) supplies ``num_iters`` and
    ``segment`` in one typed object, overriding the loose kwargs; the
    distributed-only knobs (``axis_name``, ``schedule``, ``static_seed``)
    stay here — they configure the chip-level decomposition, not the
    algorithm family.  ``num_iters`` must be concrete (the per-iteration
    ppermute schedule is built at trace time), so ``num_iters='auto'``
    specs are rejected eagerly.

    Returns ``fn(key, weights_global) -> ancestors_global`` where weights are
    sharded ``P(axis_name)`` and ancestors come back with the same sharding.
    """
    if spec is not None:
        from repro.core.spec import MegopolisSpec

        if not isinstance(spec, MegopolisSpec):
            raise TypeError(
                f"make_distributed_resampler takes a MegopolisSpec; got {type(spec).__name__} "
                "(the hierarchical decomposition is Alg. 5 specific)"
            )
        if not isinstance(spec.num_iters, int):
            raise ValueError(
                "make_distributed_resampler needs a concrete num_iters (the "
                "shard-offset schedule is built per iteration at trace time); "
                f"got num_iters={spec.num_iters!r}"
            )
        if spec.backend not in ("reference", "xla"):
            raise ValueError(
                "make_distributed_resampler runs its own shard_map decomposition, "
                f"not the single-chip Pallas kernel; got backend={spec.backend!r} "
                "(use backend='reference')"
            )
        num_iters, segment = spec.num_iters, spec.segment
    if schedule not in ("static", "dynamic"):
        raise ValueError(f"schedule must be 'static' or 'dynamic'; got {schedule!r}")
    n_shards = int(np.prod([mesh.shape[a] for a in (axis_name,)]))
    shard_sched = _static_shard_schedule(static_seed, num_iters, n_shards)

    def impl(seed, offsets_local, offsets_shard_dyn, weights):
        offsets_shard = shard_sched if schedule == "static" else offsets_shard_dyn
        return megopolis_shard(
            seed,
            offsets_local,
            offsets_shard,
            weights,
            axis_name=axis_name,
            num_iters=num_iters,
            segment=segment,
            schedule=schedule,
        )

    shard_fn = jax.shard_map(
        impl,
        mesh=mesh,
        in_specs=(P(), P(), P(), P(axis_name)),
        out_specs=P(axis_name),
        check_vma=False,
    )

    @jax.jit
    def resample(key, weights):
        n = weights.shape[0]
        n_local = n // n_shards
        k_seed, k_loc, k_shard = jax.random.split(key, 3)
        seed = key_to_seed(k_seed)
        offsets_local = jax.random.randint(k_loc, (num_iters,), 0, n_local, jnp.int32)
        offsets_shard_dyn = jax.random.randint(k_shard, (num_iters,), 0, n_shards, jnp.int32)
        return shard_fn(seed, offsets_local, offsets_shard_dyn, weights)

    return resample


def megopolis_hier_ref(
    seed,
    offsets_local,
    offsets_shard: Sequence[int],
    weights: jnp.ndarray,
    *,
    n_shards: int,
    num_iters: int,
    segment: int = 1024,
) -> jnp.ndarray:
    """Single-device oracle of the hierarchical index map (for exactness
    tests against the shard_map implementation)."""
    n = weights.shape[0]
    n_local = n // n_shards
    i = jnp.arange(n, dtype=jnp.int32)
    s = i // n_local
    i_local = i % n_local
    k = i
    wk = weights
    for b in range(num_iters):
        o_s = int(offsets_shard[b]) if not isinstance(offsets_shard, jnp.ndarray) else offsets_shard[b]
        j_local = megopolis_indices(i_local, offsets_local[b], segment, n_local).astype(jnp.int32)
        j_global = ((s + o_s) % n_shards) * n_local + j_local
        w_j = weights[j_global]
        u = hash_uniform(seed, i, b, dtype=weights.dtype)
        accept = u * wk <= w_j
        k = jnp.where(accept, j_global, k)
        wk = jnp.where(accept, w_j, wk)
    return k
