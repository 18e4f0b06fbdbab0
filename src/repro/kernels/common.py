"""Shared kernel utilities: counter-based hash RNG + tile flat-roll.

Both are defined ONCE here and imported by the Pallas kernel bodies *and*
the ``ref.py`` oracles so kernel-vs-ref comparisons are bit-exact.

RNG rationale (DESIGN.md §2): the paper pays coalesced loads/stores for
CURAND XORWOW state.  A counter-based hash (murmur3 finalizer over
``(seed, lane, iteration)``) is stateless — zero memory traffic — and is
TPU-friendly (integer mul/xor/shift on the VPU).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental.pallas import tpu as pltpu

from repro.obs.trace import span
from repro.reduction import block_tree_sum
from repro.resilience.errors import VmemBudgetExceeded

# NOTE: all scalar constants below are *numpy* scalars so they inline as
# jaxpr literals — Pallas kernel bodies may not close over device constants.
_GOLDEN = np.uint32(0x9E3779B9)
LANES = 128
SUBLANES = 8
_LANE = LANES
_SUBLANES = SUBLANES
TILE = SUBLANES * LANES  # 1024 particles per (8,128) f32 VMEM tile


def tile_lane_ids(t) -> jnp.ndarray:
    """Global particle index of every lane of tile ``t``: int32[8, 128] with
    flat row-major value ``t * 1024 + row * 128 + col`` — the ONE lane->
    particle map every kernel body shares."""
    row = lax.broadcasted_iota(jnp.int32, (SUBLANES, LANES), 0)
    col = lax.broadcasted_iota(jnp.int32, (SUBLANES, LANES), 1)
    return t * TILE + row * LANES + col

# Residency budget for kernels that keep a whole f32[N] array VMEM-resident
# (the Metropolis/rejection random gather, the search kernel's CDF, the fused
# step's prelude): 4 MiB.  On a TPU v5e (128 MiB of VMEM per core) a kernel
# may use 16 MiB unless it raises ``vmem_limit_bytes``, and a block whose
# index map is constant over the grid is held once (both as the installed
# compiler accepts them, DESIGN.md §2).  ONE definition — DESIGN.md §2
# cites it, the ops modules enforce it.  The budget is BYTES underneath
# (MAX_VMEM_PARTICLE_BYTES): compressed planes (DESIGN.md §14) double the
# admissible N because a bf16/f16 word is half an f32 word.
MAX_VMEM_PARTICLES = 1 << 20
MAX_VMEM_PARTICLE_BYTES = 4 * MAX_VMEM_PARTICLES

# ---------------------------------------------------------------------------
# Compressed particle planes (DESIGN.md §14)
#
# The ``plane_dtype`` spec axis compresses what the fused path MOVES — the
# weight/CDF tiles and the float state planes — while every kernel body
# upcasts its loads so selection arithmetic, RNG, ESS/log-evidence stats and
# bisection boundaries stay f32 on-chip.  ``quantise_plane`` is the ONE
# rounding point (idempotent, applied at the Resampler entry for every
# backend); ``compress_plane`` is the lossless wire-narrowing the ops
# wrappers apply to already-quantised operands.
# ---------------------------------------------------------------------------

#: Spec-level names for the plane-compression axis.  float16 is experimental:
#: its 5-bit exponent underflows genuinely small weights (min normal ~6.1e-5)
#: so only bf16 (f32 exponent range) is quality-gated.
PLANE_DTYPES = ("float32", "bfloat16", "float16")


def canonical_plane_dtype(plane_dtype) -> jnp.dtype:
    """Validate and canonicalise a ``plane_dtype`` spec value to a dtype."""
    if plane_dtype is None:
        return jnp.dtype(jnp.float32)
    name = (
        plane_dtype if isinstance(plane_dtype, str) else jnp.dtype(plane_dtype).name
    )
    if name not in PLANE_DTYPES:
        raise ValueError(
            f"plane_dtype must be one of {PLANE_DTYPES}; got {plane_dtype!r}"
        )
    return jnp.dtype(name)


def plane_itemsize(plane_dtype) -> int:
    """Bytes per compressed-plane word (4, 2, 2)."""
    return canonical_plane_dtype(plane_dtype).itemsize


def quantise_plane(x: jnp.ndarray, plane_dtype) -> jnp.ndarray:
    """Round ``x`` onto the ``plane_dtype`` grid, keeping its own dtype.

    Identity for f32 planes (a same-dtype convert is elided from the
    jaxpr, preserving the structural identical-program gates) and for
    NON-float arrays (int particle states pass through untouched).
    Idempotent: ``quantise(quantise(x)) == quantise(x)`` bitwise, which is
    what makes the ops-layer ``compress_plane`` narrowing lossless.
    """
    x = jnp.asarray(x)
    if not jnp.issubdtype(x.dtype, jnp.floating):
        return x
    dt = canonical_plane_dtype(plane_dtype)
    return x.astype(dt).astype(x.dtype)


def compress_plane(x: jnp.ndarray, plane_dtype) -> jnp.ndarray:
    """Narrow an (already quantised) float plane to the wire dtype the
    kernel DMAs.  Non-float planes (int state) keep their dtype — the
    compression axis only ever touches float planes."""
    x = jnp.asarray(x)
    if not jnp.issubdtype(x.dtype, jnp.floating):
        return x
    return x.astype(canonical_plane_dtype(plane_dtype))

# ---------------------------------------------------------------------------
# Fused resample+gather state layout (DESIGN.md §11)
#
# The fused ``apply`` kernels keep the particle STATE resident in VMEM as a
# stack of flat (R, 128) planes — one plane per (padded) state component —
# so the post-selection copy ``x[k]`` is an in-register gather, never an
# HBM index round-trip.  Helpers below are shared by every family's fused
# kernel AND its wrapper so pack/gather/unpack can never disagree.
# ---------------------------------------------------------------------------

# Plane-stack padding granularity: state planes are padded to whole sublane
# groups so every per-tile state copy ([d_pad, 8, 128] block) is an integral
# number of (8, 128) VMEM tiles with full-stride DMAs on hardware.  A scalar
# state (state_dim == 1) is exempt — it degenerates to the weights' own
# (R, 128) layout and needs no padding.
STATE_PLANE_TILE = SUBLANES

# Resident-state budget in f32 words (n * d_pad): 8 MiB, for the families
# that gather from a resident plane stack (Megopolis carries state by value
# and has no such cap).  With 4 MiB of resident weights that is 12 MiB, inside
# the 16 MiB a v5e kernel may use.  Bytes underneath (MAX_VMEM_STATE_BYTES):
# compressed planes double the edge.
MAX_VMEM_STATE = 2 * MAX_VMEM_PARTICLES
MAX_VMEM_STATE_BYTES = 4 * MAX_VMEM_STATE


# Static per-launch footprint budget (DESIGN.md §13, pass 4): the analyzer
# prices every pallas_call's VMEM-resident bytes straight off its traced
# BlockSpecs — whole-array operands + per-grid-step blocks + vmem scratch —
# and checks the total against the residency budgets above.  The slack term
# covers what the word budgets deliberately exclude: grid-blocked operand
# windows, scratch accumulators and f32 output tiles.
VMEM_FOOTPRINT_SLACK_BYTES = 2 << 20


def vmem_budget_bytes() -> int:
    """Static VMEM byte budget for ONE kernel launch.

    Every plane the word budgets admit may be resident at most TWICE —
    pallas kernels take inputs and outputs as separate refs, so a fused
    step at the residency edge holds state in + state out plus a few
    weight planes (the analyzer's estimate for the prefix-family fused step
    at N*pad_state_dim == MAX_VMEM_STATE is 19.0 MiB).  At the defaults this
    is 2 * (4 MiB + 8 MiB) + 2 MiB = 26 MiB: more than the 16 MiB a v5e
    kernel may use without raising ``vmem_limit_bytes``, which of the
    compiled kernels only the Megopolis fused step does (DESIGN.md §2).  It
    prices the resident-stack families, none of which Mosaic compiles yet;
    the Megopolis kernels stay below it."""
    return 2 * 4 * (MAX_VMEM_PARTICLES + MAX_VMEM_STATE) + VMEM_FOOTPRINT_SLACK_BYTES


def block_bytes(shape, dtype) -> int:
    """Resident bytes of one kernel operand/scratch block."""
    size = 1
    for s in shape:
        size *= int(s)
    return size * np.dtype(dtype).itemsize


def pad_state_dim(state_dim: int) -> int:
    """Padded plane count for a ``state_dim``-component particle state."""
    if state_dim <= 1:
        return 1
    return -(-state_dim // STATE_PLANE_TILE) * STATE_PLANE_TILE


def check_state_resident(n: int, state_dim: int, who: str, itemsize: int = 4):
    """Raise when the fused kernels' resident plane stack exceeds the VMEM
    state budget: ``n * pad_state_dim(state_dim) * itemsize`` bytes against
    ``MAX_VMEM_STATE_BYTES``.  At the f32 default this is the historical
    word cap ``n * d_pad <= MAX_VMEM_STATE``; compressed planes
    (``itemsize == 2``) double the residency edge (DESIGN.md §14)."""
    d_pad = pad_state_dim(state_dim)
    if n * d_pad * itemsize > MAX_VMEM_STATE_BYTES:
        raise VmemBudgetExceeded(
            f"{who} keeps the whole particle state VMEM-resident and caps "
            f"N * pad_state_dim(state_dim) * itemsize at {MAX_VMEM_STATE_BYTES} "
            f"bytes (got N={n}, state_dim={state_dim}, itemsize={itemsize} -> "
            f"{n * d_pad * itemsize}). Use apply on the reference/xla backend "
            "(index + XLA gather) above this size."
        )


def state_dim_of(particles: jnp.ndarray, n: int, who: str, lead: int = 1) -> int:
    """Flattened state component count of ``particles``, validating that the
    particle axis (``lead``-th axis: 1 = ``[N, ...]``, 2 = ``[B, N, ...]``)
    matches ``n``.  The ONE lead-axis/state-dim check every fused ops
    wrapper shares."""
    if particles.ndim < lead or particles.shape[lead - 1] != n:
        raise ValueError(
            f"{who}: particles must carry the particle axis at position "
            f"{lead - 1} ({'[B, N, ...]' if lead == 2 else '[N, ...]'}); got "
            f"{particles.shape} for N={n}"
        )
    d = 1
    for s in particles.shape[lead:]:
        d *= s
    return d


def state_itemsize(particles: jnp.ndarray, plane_dtype) -> int:
    """Resident bytes per state word under the compression axis: the plane
    dtype's width for float states, the state's own width otherwise (int
    states never compress)."""
    if jnp.issubdtype(jnp.asarray(particles).dtype, jnp.floating):
        return plane_itemsize(plane_dtype)
    return jnp.dtype(particles.dtype).itemsize


def run_fused_bank(launch, weights: jnp.ndarray, particles: jnp.ndarray, who: str,
                   plane_dtype="float32", state_resident=True):
    """Shared bank scaffolding for every family's fused apply launch:
    residency check (for kernels that keep the whole state stack in VMEM,
    ``state_resident``), per-row plane pack (+ §14 wire narrowing),
    ``launch(w3, planes4d) -> (k3, out4d)``, per-row unpack.  Returns
    ``(particles'[B, N, ...], ancestors int32[B, N])``."""
    bsz, n = weights.shape
    d = state_dim_of(particles, n, who, lead=2)
    if state_resident:
        check_state_resident(n, d, who, itemsize=state_itemsize(particles, plane_dtype))
    w3 = compress_plane(weights.reshape(bsz, n // LANES, LANES), plane_dtype)
    planes = compress_plane(
        jax.vmap(lambda p: pack_state_planes(p)[0])(particles), plane_dtype
    )
    k3, out = launch(w3, planes)
    state_shape = particles.shape[2:]
    out_rows = jax.vmap(lambda o: unpack_state_planes(o, state_shape))(
        out.astype(particles.dtype)
    )
    return out_rows, k3.reshape(bsz, n)


def pack_state_planes(particles: jnp.ndarray):
    """``[N]`` or ``[N, ...]`` particles -> ``[d_pad, N // 128, 128]`` plane
    stack (zero-padded), plus the trailing state shape for ``unpack``.

    Plane ``d`` holds component ``d`` of every particle in the SAME flat
    row-major (R, 128) layout the weight kernels use, so ``tile_lane_ids``
    indexes state exactly like it indexes weights.  Both directions run
    under the ``resample/planes`` scope: a profile names the fused ops
    rooted in a vector state's per-step pack and unpack by it.
    """
    n = particles.shape[0]
    state_shape = particles.shape[1:]
    d = 1
    for s in state_shape:
        d *= s
    d_pad = pad_state_dim(d)
    with span("resample/planes"):
        flat = particles.reshape(n, d).T  # [d, N]
        if d_pad != d:
            flat = jnp.concatenate(
                [flat, jnp.zeros((d_pad - d, n), flat.dtype)], axis=0
            )
        return flat.reshape(d_pad, n // LANES, LANES), state_shape


def unpack_state_planes(planes: jnp.ndarray, state_shape) -> jnp.ndarray:
    """Invert ``pack_state_planes``: ``[d_pad, R, 128]`` -> ``[N, *shape]``."""
    d_pad = planes.shape[0]
    n = planes.shape[-2] * planes.shape[-1]
    d = 1
    for s in state_shape:
        d *= s
    with span("resample/planes"):
        out = planes.reshape(d_pad, n)[:d].T  # [N, d]
        return out.reshape((n,) + tuple(state_shape))


def gather_state(planes: jnp.ndarray, k_global: jnp.ndarray) -> jnp.ndarray:
    """In-register state copy: ``out[:, i] = planes[:, k_global[i]]``.

    ``planes``: the resident ``[d_pad, rows, 128]`` plane-stack VALUE;
    ``k_global``: int32[8, 128] ancestor ids of one output tile.  Returns
    the gathered ``[d_pad, 8, 128]`` state block — the tile the fused
    kernels write straight to the output ref (Alg. 5's state copy, fused)."""
    d_pad, rows, lanes = planes.shape
    flat = planes.reshape(d_pad, rows * lanes)
    return jnp.take(flat, k_global.reshape(-1), axis=1).reshape(
        d_pad, SUBLANES, LANES
    )


def step_stats(lw: jnp.ndarray, n_total: int):
    """Fused-step prelude statistics from a resident ``(rows, 128)``
    log-weight block: ``(m, ess_norm, log_evidence_incr, max_weight,
    degenerate)``.

    Mirrors ``repro.core.metrics`` term for term — guarded shift-by-max
    (``normalise_log_weights``), ``(Σw)²/max(Σw², 1e-30)``
    (``effective_sample_size``), the ``m + log(Σw) - log(N)`` decomposition
    (``log_mean_weight``), and ``max(w)/max(Σw, 1e-30)``
    (``max_normalised_weight``) — with every sum in the host helpers'
    ``block_tree_sum`` order, so the stats are bit-identical to the host's
    wherever the two ``exp`` agree (always in interpret mode on CPU).  The
    block stays 2-D as it lies in VMEM: a flat ``f32[N]`` value costs
    Mosaic one (8, 128) tile per 128 words and overflows VMEM at N = 2^18.

    ``degenerate`` is the §16 collapsed-bank flag (``~isfinite(max)``:
    all-``-inf``, any nan/+inf — ``metrics.degenerate_log_weights``).  Where
    it is set, ESS and max-weight are computed from the SAME uniform-``1/N``
    fallback bank ``normalise_log_weights`` substitutes on the host, so the
    on-chip trigger stays bit-identical to the composed oracle; ``incr``
    keeps the raw ``log_mean_weight`` decomposition (``-inf``/nan there is
    the truthful evidence of a dead bank, and the step's where-select zeroes
    it on the untriggered branch exactly as the host does).
    """
    m_raw = jnp.max(lw)
    deg = ~jnp.isfinite(m_raw)
    m = jnp.where(deg, jnp.zeros_like(m_raw), m_raw)
    w_raw = jnp.exp(lw - m)
    incr = (m + jnp.log(block_tree_sum(w_raw))) - jnp.log(jnp.float32(n_total))
    w = jnp.where(deg, jnp.full_like(w_raw, 1.0 / n_total), w_raw)
    s1 = block_tree_sum(w)
    s2 = block_tree_sum(w * w)
    ess = jnp.square(s1) / jnp.maximum(s2, 1e-30)
    ess_norm = ess / jnp.float32(n_total)
    maxw = jnp.max(w) / jnp.maximum(s1, 1e-30)
    return m, ess_norm, incr, maxw, deg


def step_select(do, k_new: jnp.ndarray, t) -> jnp.ndarray:
    """The fused step's on-chip resample branch for one output tile: the
    freshly selected ancestors when the ESS trigger fired, else the identity
    permutation (``tile_lane_ids``) that makes the state copy a no-op."""
    return jnp.where(do, k_new, tile_lane_ids(t))


def gather_state_full(planes: jnp.ndarray, k_global: jnp.ndarray) -> jnp.ndarray:
    """Whole-array variant of ``gather_state`` for single-grid-step kernels
    (the prefix-sum fused step): gathers ALL rows at once, returning a full
    ``[d_pad, rows, 128]`` block for a ``k_global`` of shape (rows, 128)."""
    d_pad, rows, lanes = planes.shape
    flat = planes.reshape(d_pad, rows * lanes)
    return jnp.take(flat, k_global.reshape(-1), axis=1).reshape(d_pad, rows, lanes)


def run_step_bank(launch, log_weights: jnp.ndarray, particles: jnp.ndarray, who: str,
                  plane_dtype="float32", state_resident=True):
    """Bank scaffolding for every family's fused STEP launch — the step
    analogue of ``run_fused_bank``: residency check (``state_resident``),
    per-row plane pack, ``launch(lw3, planes4d) -> (k3, out4d, stats4)``
    with ``stats4`` =
    f32[B, 4] rows of (ess_norm, log_evidence_incr, resampled, max_weight)
    — the in-kernel StepStats vector of DESIGN.md §15 — then per-row
    unpack.  Returns ``(particles'[B, N, ...], ancestors int32[B, N],
    stats f32[B, 4])``."""
    bsz, n = log_weights.shape
    d = state_dim_of(particles, n, who, lead=2)
    if state_resident:
        check_state_resident(n, d, who, itemsize=state_itemsize(particles, plane_dtype))
    lw3 = compress_plane(log_weights.reshape(bsz, n // LANES, LANES), plane_dtype)
    planes = compress_plane(
        jax.vmap(lambda p: pack_state_planes(p)[0])(particles), plane_dtype
    )
    k3, out, stats = launch(lw3, planes)
    state_shape = particles.shape[2:]
    out_rows = jax.vmap(lambda o: unpack_state_planes(o, state_shape))(
        out.astype(particles.dtype)
    )
    return out_rows, k3.reshape(bsz, n), stats


def check_tile_aligned(n: int, who: str):
    """Raise unless N is whole (8, 128) f32 VMEM tiles."""
    if n % TILE != 0:
        raise ValueError(f"{who} requires N % {TILE} == 0; got {n}")


def check_vmem_resident(
    n: int,
    who: str,
    what: str = "weight array",
    remedy: str = "Use megopolis_tpu (streams tiles at any N).",
    itemsize: int = 4,
):
    """Raise when a whole-array-resident kernel exceeds the VMEM budget
    (``n * itemsize`` bytes against ``MAX_VMEM_PARTICLE_BYTES``; the f32
    default reproduces the historical ``n <= MAX_VMEM_PARTICLES`` cap)."""
    if n * itemsize > MAX_VMEM_PARTICLE_BYTES:
        raise VmemBudgetExceeded(
            f"{who} keeps the whole {what} VMEM-resident and caps N * itemsize "
            f"at {MAX_VMEM_PARTICLE_BYTES} bytes — the scaling wall the "
            f"paper's coalescing removes. {remedy}"
        )


def murmur3_fmix(x: jnp.ndarray) -> jnp.ndarray:
    """murmur3 32-bit finalizer; full-avalanche integer hash."""
    x = x.astype(jnp.uint32)
    x = x ^ (x >> np.uint32(16))
    x = x * np.uint32(0x85EBCA6B)
    x = x ^ (x >> np.uint32(13))
    x = x * np.uint32(0xC2B2AE35)
    x = x ^ (x >> np.uint32(16))
    return x


def hash_bits(seed, lane_index, iteration) -> jnp.ndarray:
    """uint32 stream indexed by (seed, lane, iteration) — order-free."""
    if isinstance(iteration, (int, np.integer)):
        # wrap in Python ints to avoid numpy overflow RuntimeWarnings
        inc = np.uint32((int(iteration) * int(_GOLDEN)) & 0xFFFFFFFF)
    else:
        inc = jnp.asarray(iteration).astype(jnp.uint32) * _GOLDEN
    if isinstance(seed, (int, np.integer)) and isinstance(inc, np.uint32):
        s = np.uint32((int(seed) + int(inc)) & 0xFFFFFFFF)
    else:
        s = _as_u32(seed) + inc
    return murmur3_fmix(murmur3_fmix(s) ^ (lane_index.astype(jnp.uint32) * _GOLDEN))


def _as_u32(x):
    if isinstance(x, (int, np.integer)):
        return np.uint32(x)
    return jnp.asarray(x).astype(jnp.uint32)


def hash_uniform(seed, lane_index, iteration, dtype=jnp.float32) -> jnp.ndarray:
    """U[0,1) with 24 bits of mantissa entropy."""
    bits = hash_bits(seed, lane_index, iteration)
    # uint32 -> int32 -> float: exact (the value is below 2^24), and Mosaic
    # has no unsigned-to-float conversion.
    return (bits >> np.uint32(8)).astype(jnp.int32).astype(dtype) * (1.0 / (1 << 24))


def hash_randint(seed, lane_index, iteration, bound) -> jnp.ndarray:
    """uint32 in [0, bound) via modulo (bias < 2^-20 for bound <= 2^12)."""
    return (hash_bits(seed, lane_index, iteration) % _as_u32(bound)).astype(jnp.int32)


@jax.jit
def flat_roll(x: jnp.ndarray, shift) -> jnp.ndarray:
    """Roll a (rows, 128) tile by ``shift`` in FLAT row-major order:
    ``out.flat[p] = x.flat[(p + shift) % size]``; leading axes (a stack of
    state planes) are rolled alike, plane by plane.

    Decomposed into two row-rolls + two lane-rolls + a lane-mask select so
    every constituent op is a register-level vector rotate (the in-VMEM
    analogue of the paper's intra-segment wrap, Alg. 5 line 10).  The
    rotates are ``pltpu.roll`` (``jnp.roll`` semantics, non-negative shift),
    which Mosaic lowers for a traced shift; jitted so the same function
    also runs outside a kernel.
    """
    rows, lanes = x.shape[-2:]
    row_ax, lane_ax = x.ndim - 2, x.ndim - 1
    shift = jnp.asarray(shift, jnp.int32) % (rows * lanes)
    a = shift // lanes
    b = shift % lanes
    # hi[r] = x[(r + a) % rows], lo[r] = x[(r + a + 1) % rows]
    hi = pltpu.roll(x, (rows - a) % rows, row_ax)
    lo = pltpu.roll(x, rows - 1 - a, row_ax)
    hi = pltpu.roll(hi, (lanes - b) % lanes, lane_ax)
    lo = pltpu.roll(lo, (lanes - b) % lanes, lane_ax)
    col = lax.broadcasted_iota(jnp.int32, x.shape, lane_ax)
    return jnp.where(col < lanes - b, hi, lo)


def key_to_seed(key) -> jnp.ndarray:
    """Derive a uint32 seed from a JAX PRNG key (stable, documented)."""
    data = jax.random.key_data(key).astype(jnp.uint32)
    return murmur3_fmix(data[..., 0] ^ (data[..., 1] * _GOLDEN))
