"""Megopolis resampling — Pallas TPU kernel (the paper's Alg. 5, TPU-native).

Memory-access contract (DESIGN.md §2):

  * particle weights live in HBM as ``f32[S, R, 128]`` (R = N/128 rows, S
    rows of a bank; a single call is a one-row bank);
  * the coalescing segment is one (8, 128) f32 VMEM tile (SEG = 1024
    particles, the TPU analogue of the paper's 32-thread warp segment);
  * every entry runs one grid, (S, num_tiles / G, B): rows outermost, the
    iteration axis innermost, G tiles swept per grid step.  Row s reads
    its own scalar-prefetched offset table ``o[s, b]`` and seed; block t's
    comparison window, tiles ``t*G + g + o[s, b] // SEG``, is fetched as
    two aligned G-tile blocks (``_window_index``) and tile g reads its
    comparison tile from one of them (``_window_tile``) — so every load the
    kernel ever issues is a whole, aligned, contiguous tile (the paper's
    Fig. 4b "wrapped sequential" pattern, 0 wasted words).  G is a function
    of the shapes alone (``tiles_per_step``: a power of two dividing
    num_tiles whose blocks fit the kernel's VMEM); an odd num_tiles gives
    G = 1, one tile per grid step;
  * the intra-segment wrap ``(i + o[s, b]) mod SEG`` is a register-level
    flat roll of the tile — no extra memory traffic;
  * per-(particle, iteration) uniforms come from a stateless counter hash
    (no CURAND state loads/stores — beyond-paper win, see EXPERIMENTS.md §Perf);
  * the current ancestor's weight ``w[k]`` is carried by VALUE in a VMEM
    scratch accumulator (never re-fetched), exactly like the register-carried
    ``w_k`` in the CUDA original; given state planes, the kernels carry the
    ancestor's state the same way (DESIGN.md §11).

Two kernel bodies serve the six entries: ``_kernel_fused`` (the ancestors
of ``__call__``, ``.batch`` and ``.batch_rows``, and with state planes
``apply`` and its banks) and ``_kernel_step`` (``step`` and ``step_rows``).
Each ``pallas_call`` carries a stable ``name=``, after the ``Resampler``
entry that launches it: ``megopolis_pallas`` (``single``), ``_batch``,
``_apply``, ``_apply_rows``, ``_step`` and ``_step_rows``.  The name is the
kernel's, independent of the Python wrapper's, so a profile's reduction can
find the kernel after a refactor (DESIGN.md §15).

Validated in ``interpret=True`` mode bit-exactly against ``ref.py``, and
compiled by Mosaic for the TPU (``tests/test_tpu_compile.py``,
``chip_smoke.py``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import (
    TILE,
    flat_roll,
    hash_uniform,
    step_select,
    step_stats,
    tile_lane_ids,
)

SUBLANES = 8
LANES = 128
SEG = TILE  # 1024 particles = one (8,128) f32 tile


def _sweep(t, b, o, seed, w_own, w_cmp, k_prev, wk_prev, n_total):
    """One accept/reject sweep of one (8,128) tile (Alg. 5 lines 5-14).

    Shared verbatim by every kernel body so they can never drift
    arithmetically; ``k_prev``/``wk_prev`` are the carried ancestor/weight
    values (ignored at b == 0, where k <- i and w[k] is seeded from the
    tile's own weights).  Returns ``(k, w[k], accept)``; the fused bodies
    select the carried state with the same ``accept``."""
    i_global = tile_lane_ids(t)  # particle index (Alg. 5 line 5)

    k = jnp.where(b == 0, i_global, k_prev)  # k <- i      (Alg. 5 line 6)
    wk = jnp.where(b == 0, w_own, wk_prev)  # w[k] by value (register carry)

    # j = i_aligned + o_aligned + (i + o) mod SEG   (Alg. 5 lines 7-11)
    # block fetch already applied i_aligned + o_aligned; flat-roll applies
    # the intra-segment wrap.
    w_j = flat_roll(w_cmp, o % SEG)
    o_aligned = o - (o % SEG)
    j_global = (t * SEG + o_aligned + (i_global + o) % SEG) % n_total

    u = hash_uniform(seed, i_global, b, dtype=w_j.dtype)
    accept = u * wk <= w_j  # u <= w[j]/w[k]  (line 13)
    return jnp.where(accept, j_global, k), jnp.where(accept, w_j, wk), accept


def _carry_dtype(dtype):
    """Dtype of the by-value state carry: f32 for float planes (compressed
    bf16/f16 words widen exactly and narrow back unchanged), the plane's
    own dtype for integer state."""
    return jnp.float32 if jnp.issubdtype(dtype, jnp.floating) else dtype


def _state_index(index):
    """Block index map of a ``[d_pad, G*8, 128]`` state block that follows
    the weights' map ``index``: the same row and tiles, the plane axis
    whole."""
    def state(*args):
        s, t, _ = index(*args)
        return s, 0, t, 0
    return state


#: Most tiles one grid step of the Megopolis kernels sweeps
#: (``tiles_per_step``).
MAX_TILES_PER_STEP = 128

#: VMEM the G-tile blocks of one launch may take (``tiles_per_step``):
#: inside the 16 MiB a v5e kernel may use without raising
#: ``vmem_limit_bytes``, with room for the compiler's own scratch.
BLOCK_VMEM_BYTES = 12 << 20

#: The scoped VMEM a v5e kernel gets unless it raises ``vmem_limit_bytes``.
DEFAULT_VMEM_LIMIT_BYTES = 16 << 20

#: Tiles per iteration of the in-kernel tile loop: a partial unroll, so the
#: scheduler may interleave the tiles' independent chains, while the code
#: the compiler sees stays one loop body whatever G is.  On a TPU v5e at
#: N = 2^20, B = 32 the kernel alone takes 0.744, 0.781 and 3.247 ms (apply
#: at 1 plane, step at 1 plane, apply at 8 planes) at 16, against 0.880,
#: 0.906, 3.260 at 8 and 1.264, 1.268, 3.281 at 4 (PERF.md §6).
_TILE_UNROLL = 16


def block_vmem_bytes(g_tiles, d_pad, itemsize):
    """VMEM of one launch's G-tile blocks: the own and the two comparison
    blocks of the weights and of the ``d_pad`` state planes (none for the
    ancestors-only forms), the ancestor and state outputs, each
    double-buffered, plus the ``w[k]`` and state carries (f32 words).
    ``itemsize`` is the widest plane word.  The v5e compiler plans the
    blocked ``apply`` within 0.05 MiB of this."""
    tile_bytes = TILE * itemsize
    blocks = 2 * (3 * tile_bytes + 4 * d_pad * tile_bytes + TILE * 4)
    carries = TILE * 4 + d_pad * TILE * 4
    return g_tiles * (blocks + carries)


def tiles_per_step(num_tiles, d_pad, itemsize):
    """G, the tiles one grid step of the Megopolis kernels sweeps: the largest
    power of two up to ``MAX_TILES_PER_STEP`` that divides ``num_tiles``
    and whose blocks fit ``BLOCK_VMEM_BYTES``.  An odd ``num_tiles`` gives
    1, one tile per grid step."""
    g = MAX_TILES_PER_STEP
    while g > 1 and (num_tiles % g
                     or block_vmem_bytes(g, d_pad, itemsize) > BLOCK_VMEM_BYTES):
        g //= 2
    return g


def step_vmem_limit_bytes(log_weights2d, block_bytes):
    """Scoped VMEM for one fused step launch: its G-tile blocks beside what
    it holds for a whole row, the resident log-weight block and the
    prelude's two whole-array f32 temporaries (11.9 MiB at N = 2^20 in
    f32 as the v5e compiler plans it), plus 1 MiB for the compiler's own
    scratch; never below the default."""
    resident = log_weights2d.size * (log_weights2d.dtype.itemsize + 2 * 4)
    return max(DEFAULT_VMEM_LIMIT_BYTES, resident + block_bytes + (1 << 20))


def _own_index(s, t, *_):
    """Block index map of grid step (s, t)'s own G-tile block."""
    return s, t, 0


def _window_index(num_blocks, g_tiles, second):
    """Block index map of the first (``second`` = 0) or second comparison
    block of row s of a G-tile block grid: tiles ``t*G + g`` compare
    against tiles ``(t*G + g + o // SEG) mod num_tiles`` with
    ``o = offs[s, b]``, a window that starts ``(o // SEG) mod G`` tiles into
    aligned block ``q = (t + o // SEG // G) mod num_blocks`` and ends in
    block ``(q + 1) mod num_blocks``.  At G = 1 the window is one whole
    block and the second block is never read: its map is constant in
    (t, b), so it is fetched once per row."""
    if g_tiles == 1 and second:
        return lambda s, t, b, offs, *_: (s, 0, 0)

    def index(s, t, b, offs, *_):
        return s, (t + offs[s, b] // SEG // g_tiles + second) % num_blocks, 0

    return index


def _tile_rows(g):
    """Rows of tile ``g`` of a G-tile block: an aligned 8-row slice."""
    return pl.ds(pl.multiple_of(g * SUBLANES, SUBLANES), SUBLANES)


def _window_tile(lo_ref, hi_ref, s, g_tiles):
    """Tile ``s`` (0 <= s < 2G) of the comparison window the two aligned
    blocks ``lo_ref`` and ``hi_ref`` hold: tile s of the first block while
    s < G, else tile s - G of the second.  Both loads are aligned tiles at a
    dynamic row offset; a select keeps one.  Weight blocks are
    ``(G*8, 128)``, state blocks ``(d_pad, G*8, 128)``."""
    lead = (slice(None),) * (len(lo_ref.shape) - 2)
    first = lo_ref[lead + (_tile_rows(jnp.minimum(s, g_tiles - 1)), slice(None))]
    second = hi_ref[lead + (_tile_rows(jnp.maximum(s - g_tiles, 0)), slice(None))]
    return jnp.where(s < g_tiles, first, second)


def _for_each_tile(g_tiles, o, tile):
    """Call ``tile(g, s)`` for g = 0 .. G-1 in order: tile g of the block
    compares against tile ``s = (o // SEG) mod G + g`` of its window.  One
    loop iteration runs ``_TILE_UNROLL`` tiles (all G when fewer).  ``tile``
    must not branch: a ``pl.when`` in it ends a basic block in every
    unrolled tile, and the tiles then run one after another (on a TPU v5e
    at 8 tiles per iteration and 1 state plane: 58 bundles per tile with
    that branch, 33 without; 3.07 against 0.77 ms per resample at N = 2^20;
    PERF.md §5-6)."""
    r = (o // SEG) % g_tiles
    unroll = min(_TILE_UNROLL, g_tiles)  # both powers of two

    def body(i, carry):
        for j in range(unroll):
            g = i * unroll + j
            tile(g, r + g)
        return carry

    lax.fori_loop(0, g_tiles // unroll, body, 0)


def _carry_state(b, o, accept, x_own, x_cmp, xk_prev):
    """The ancestor's state, carried by VALUE like ``w[k]`` (DESIGN.md §11).

    ``x_own``/``x_cmp``: this tile's and the comparison tile's
    ``[d_pad, 8, 128]`` state blocks, fetched with the same block index
    maps as the weights; each plane is flat-rolled by the same intra-
    segment shift as ``w_cmp``, so lane p of the rolled block is the state
    of ``j_global[p]`` and ``accept`` selects it exactly where ``k`` takes
    ``j``.  Every state load is one aligned tile: no gather."""
    xk = jnp.where(b == 0, x_own, xk_prev)
    return jnp.where(accept[None], flat_roll(x_cmp, o % SEG), xk)


def _prelude(lw, thr, n_total, st_ref, stats_ref, at):
    """Fused-step prelude: reduce the resident log-weight block to the step
    statistics, latch ``(m, do, deg)`` into SMEM and write the stats row
    to the flat ``stats_ref[at:at + 4]``."""
    m, ess_norm, incr, maxw, deg = step_stats(lw, n_total)
    do = ess_norm < thr
    one, zero = jnp.float32(1.0), jnp.float32(0.0)
    st_ref[0] = m
    st_ref[1] = jnp.where(do, one, zero)
    st_ref[2] = jnp.where(deg, one, zero)
    stats_ref[at + 0] = ess_norm
    stats_ref[at + 1] = jnp.where(do, incr, zero)
    stats_ref[at + 2] = jnp.where(do, one, zero)
    stats_ref[at + 3] = maxw


def _step_weights(lw_own, lw_cmp, m, deg, n_total, plane):
    """Normalised weights of the two tiles, as the composed path sees them:
    ``exp(lw - m)``, the §16 uniform bank where the degenerate latch is
    set, re-landed on the plane-dtype grid (a no-op at f32)."""
    def norm(lw):
        w = jnp.exp(lw.astype(jnp.float32) - m)
        w = jnp.where(deg, jnp.float32(1.0 / n_total), w)
        return w.astype(plane).astype(jnp.float32)

    return norm(lw_own), norm(lw_cmp)


def _kernel_fused(offsets_ref, seeds_ref, w_own_ref, w_lo_ref, w_hi_ref, *refs):
    """Resample grid step (s, t, b): the Alg. 5 sweep of the G tiles of
    block t of row s at iteration b, with row s's offset ``o[s, b]`` and
    seed.  Given state planes (``apply``), each ancestor's state is carried
    by value beside ``w[k]`` (DESIGN.md §11) and the LAST iteration writes
    the carried state tiles to the output, so the ancestor index never
    round-trips through HBM between selection and copy.  Without them
    (the ancestors-only entries) ``refs`` holds only the ancestor output
    and the ``w[k]`` carry, and the state lines are not traced."""
    state = len(refs) > 2
    if state:
        x_own_ref, x_lo_ref, x_hi_ref, k_ref, out_ref, wk_ref, xk_ref = refs
    else:
        k_ref, wk_ref = refs
    s = pl.program_id(0)
    t = pl.program_id(1)
    b = pl.program_id(2)
    o = offsets_ref[s, b]
    g_tiles = k_ref.shape[0] // SUBLANES
    n_total = pl.num_programs(1) * (g_tiles * SEG)

    def tile(g, r):
        own = _tile_rows(g)
        k_new, wk_new, accept = _sweep(
            t * g_tiles + g, b, o, seeds_ref[s],
            w_own_ref[own, :].astype(jnp.float32),
            _window_tile(w_lo_ref, w_hi_ref, r, g_tiles).astype(jnp.float32),
            k_ref[own, :], wk_ref[own, :], n_total,
        )
        k_ref[own, :] = k_new
        wk_ref[own, :] = wk_new
        if state:
            cd = xk_ref.dtype
            xk_ref[:, own, :] = _carry_state(
                b, o, accept, x_own_ref[:, own, :].astype(cd),
                _window_tile(x_lo_ref, x_hi_ref, r, g_tiles).astype(cd),
                xk_ref[:, own, :])

    _for_each_tile(g_tiles, o, tile)

    if state:
        @pl.when(b == pl.num_programs(2) - 1)
        def _copy_state():
            out_ref[...] = xk_ref[...].astype(out_ref.dtype)


def _kernel_step(offsets_ref, seeds_ref, thr_ref, lw_own_ref, lw_lo_ref,
                 lw_hi_ref, lw_full_ref, x_own_ref, x_lo_ref, x_hi_ref, k_ref,
                 out_ref, stats_ref, wk_ref, xk_ref, st_ref):
    """Fused STEP grid step (s, t, b): the whole SMC resample decision
    on-chip, row by row.

    At each row's (t, b) == (0, 0) a prelude reduces that row's resident
    log-weight array to the step statistics (normalisation shift m,
    normalised ESS, log-evidence increment), writes them to ``stats[s]``
    and latches the resample decision ``ess_norm < threshold`` into SMEM
    scratch.  Every sweep of the block's G tiles then runs on
    ``exp(lw - m)`` — the SAME normalised weights the composed path hands
    to ``apply`` — and the last iteration's epilogue either commits the
    selected ancestors and carried state or the identity permutation and
    the tile's own state."""
    s = pl.program_id(0)
    t = pl.program_id(1)
    b = pl.program_id(2)
    o = offsets_ref[s, b]
    g_tiles = k_ref.shape[0] // SUBLANES
    n_total = pl.num_programs(1) * (g_tiles * SEG)

    @pl.when((t == 0) & (b == 0))
    def _():
        _prelude(lw_full_ref[...].astype(jnp.float32), thr_ref[0], n_total,
                 st_ref, stats_ref, s * 4)

    do = st_ref[1] > 0.5
    cd = xk_ref.dtype

    def tile(g, r):
        own = _tile_rows(g)
        w_own, w_cmp = _step_weights(
            lw_own_ref[own, :], _window_tile(lw_lo_ref, lw_hi_ref, r, g_tiles),
            st_ref[0], st_ref[2] > 0.5, n_total, lw_own_ref.dtype)
        k_new, wk_new, accept = _sweep(
            t * g_tiles + g, b, o, seeds_ref[s], w_own, w_cmp, k_ref[own, :],
            wk_ref[own, :], n_total,
        )
        k_ref[own, :] = k_new
        wk_ref[own, :] = wk_new
        xk_ref[:, own, :] = _carry_state(
            b, o, accept, x_own_ref[:, own, :].astype(cd),
            _window_tile(x_lo_ref, x_hi_ref, r, g_tiles).astype(cd),
            xk_ref[:, own, :])

    _for_each_tile(g_tiles, o, tile)

    @pl.when(b == pl.num_programs(2) - 1)
    def _commit():
        k_ref[...] = step_select(do, k_ref[...], t)
        out_ref[...] = jnp.where(do, xk_ref[...],
                                 x_own_ref[...].astype(cd)).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("num_iters", "interpret", "name"))
def megopolis_pallas_fused(
    weights3d: jnp.ndarray,
    planes4d: jnp.ndarray | None,
    offsets2d: jnp.ndarray,
    seeds: jnp.ndarray,
    *,
    num_iters: int,
    interpret: bool,
    name: str = "megopolis_pallas_apply",
):
    """Resample (and, given planes, gather) pallas_call over a bank of S
    rows, grid (S, num_tiles / G, B) (DESIGN.md §2, §11).  ``weights3d``:
    f32[S, R, 128] with R % 8 == 0; ``planes4d``: particle state as an
    ``[S, d_pad, R, 128]`` plane stack, streamed a block of G tiles at a
    time beside the weights (G from ``tiles_per_step``), or None for the
    ancestors alone; ``offsets2d``: int32[S, B], row s's offset table;
    ``seeds``: uint32[S].  ``name`` is the launching entry's kernel name.
    Returns ``(ancestors int32[S, R, 128], state [S, d_pad, R, 128])``, or
    ``(ancestors,)`` without planes; row s is bit-identical to a one-row
    launch on row s's inputs, whatever the other rows hold."""
    bsz, rows, lanes = weights3d.shape
    assert lanes == LANES and rows % SUBLANES == 0
    assert offsets2d.shape == (bsz, num_iters) and seeds.shape == (bsz,)
    num_tiles = rows // SUBLANES
    d_pad, itemsize = 0, weights3d.dtype.itemsize
    if planes4d is not None:
        d_pad = planes4d.shape[1]
        assert planes4d.shape == (bsz, d_pad, rows, lanes)
        itemsize = max(itemsize, planes4d.dtype.itemsize)
    g_tiles = tiles_per_step(num_tiles, d_pad, itemsize)
    num_blocks = num_tiles // g_tiles

    block = (None, g_tiles * SUBLANES, LANES)
    maps = (_own_index,) + tuple(_window_index(num_blocks, g_tiles, second)
                                 for second in (0, 1))
    in_specs = [pl.BlockSpec(block, index) for index in maps]
    out_specs = [pl.BlockSpec(block, _own_index)]
    out_shape = [jax.ShapeDtypeStruct((bsz, rows, lanes), jnp.int32)]
    scratch_shapes = [pltpu.VMEM(block[1:], jnp.float32)]
    operands = [weights3d] * 3
    if planes4d is not None:
        state_block = (None, d_pad) + block[1:]
        in_specs += [pl.BlockSpec(state_block, _state_index(index)) for index in maps]
        out_specs.append(pl.BlockSpec(state_block, _state_index(_own_index)))
        out_shape.append(jax.ShapeDtypeStruct(planes4d.shape, planes4d.dtype))
        scratch_shapes.append(pltpu.VMEM(state_block[1:], _carry_dtype(planes4d.dtype)))
        operands += [planes4d] * 3
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,  # offset tables + seeds live in SMEM, prefetched
        grid=(bsz, num_blocks, num_iters),
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=scratch_shapes,
    )
    return tuple(pl.pallas_call(
        _kernel_fused,
        grid_spec=grid_spec,
        name=name,
        out_shape=out_shape,
        interpret=interpret,
    )(offsets2d, seeds, *operands))


@functools.partial(jax.jit, static_argnames=("num_iters", "interpret", "name"))
def megopolis_pallas_step(
    log_weights3d: jnp.ndarray,
    planes4d: jnp.ndarray,
    offsets2d: jnp.ndarray,
    seeds: jnp.ndarray,
    thr: jnp.ndarray,
    *,
    num_iters: int,
    interpret: bool,
    name: str = "megopolis_pallas_step",
):
    """Fused SMC-step pallas_call over a bank of S rows (DESIGN.md §12):
    normalise → ESS → conditional resample → state copy, ONE launch on the
    grid of ``megopolis_pallas_fused``, each row taking its OWN resample
    decision.  ``log_weights3d``: f32[S, R, 128] UNNORMALISED log-weights
    (streamed a block of G tiles at a time AND kept whole-row resident for
    the on-chip reduction — the step form inherits the whole-weights VMEM
    cap); ``thr``: f32[1] ESS/N trigger; other arguments as for
    ``megopolis_pallas_fused``.
    Returns ``(ancestors int32[S, R, 128], state [S, d_pad, R, 128], stats
    f32[S, 4])``, each stats row (ess_norm, log_evidence_incr, resampled,
    max_weight) — the in-kernel StepStats vector of DESIGN.md §15."""
    bsz, rows, lanes = log_weights3d.shape
    assert lanes == LANES and rows % SUBLANES == 0
    d_pad = planes4d.shape[1]
    assert planes4d.shape == (bsz, d_pad, rows, lanes)
    assert offsets2d.shape == (bsz, num_iters) and seeds.shape == (bsz,)
    num_tiles = rows // SUBLANES
    itemsize = max(log_weights3d.dtype.itemsize, planes4d.dtype.itemsize)
    g_tiles = tiles_per_step(num_tiles, d_pad, itemsize)
    num_blocks = num_tiles // g_tiles

    block = (None, g_tiles * SUBLANES, LANES)
    state_block = (None, d_pad) + block[1:]
    lo, hi = (_window_index(num_blocks, g_tiles, second) for second in (0, 1))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,  # offset tables + seeds + f32 ESS threshold
        grid=(bsz, num_blocks, num_iters),
        in_specs=[
            pl.BlockSpec(block, _own_index),
            pl.BlockSpec(block, lo),
            pl.BlockSpec(block, hi),
            # the row's whole log-weight array resident for its stats prelude
            pl.BlockSpec((None, rows, LANES), lambda s, *_: (s, 0, 0)),
            pl.BlockSpec(state_block, _state_index(_own_index)),
            pl.BlockSpec(state_block, _state_index(lo)),
            pl.BlockSpec(state_block, _state_index(hi)),
        ],
        out_specs=[
            pl.BlockSpec(block, _own_index),
            pl.BlockSpec(state_block, _state_index(_own_index)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        scratch_shapes=[
            pltpu.VMEM(block[1:], jnp.float32),
            pltpu.VMEM(state_block[1:], _carry_dtype(planes4d.dtype)),
            pltpu.SMEM((3,), jnp.float32),  # (m, do, deg) latch across grid steps
        ],
    )
    vmem_limit = step_vmem_limit_bytes(
        jax.ShapeDtypeStruct((rows, lanes), log_weights3d.dtype),
        block_vmem_bytes(g_tiles, d_pad, itemsize))
    k3, out, stats = pl.pallas_call(
        _kernel_step,
        grid_spec=grid_spec,
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=vmem_limit),
        name=name,
        out_shape=[
            jax.ShapeDtypeStruct((bsz, rows, lanes), jnp.int32),
            jax.ShapeDtypeStruct(planes4d.shape, planes4d.dtype),
            # flat, so a one-row launch's stats are the f32[4] its consumers read
            jax.ShapeDtypeStruct((bsz * 4,), jnp.float32),
        ],
        interpret=interpret,
    )(offsets2d, seeds, thr, log_weights3d, log_weights3d, log_weights3d,
      log_weights3d, planes4d, planes4d, planes4d)
    return k3, out, stats.reshape(bsz, 4)
