"""Megopolis resampling — Pallas TPU kernel (the paper's Alg. 5, TPU-native).

Memory-access contract (DESIGN.md §2):

  * particle weights live in HBM as ``f32[R, 128]`` (R = N/128 rows);
  * the coalescing segment is one (8, 128) f32 VMEM tile (SEG = 1024
    particles, the TPU analogue of the paper's 32-thread warp segment);
  * grid = (num_tiles, B), iteration axis innermost.  For grid step
    (t, b) the *comparison* block index is computed from a scalar-prefetched
    offset table: ``(t + o[b] // SEG) mod num_tiles`` — so every load the
    kernel ever issues is a whole, aligned, contiguous tile (the paper's
    Fig. 4b "wrapped sequential" pattern, 0 wasted words);
  * the fused ``apply`` and ``step`` kernels sweep G tiles per grid step,
    grid = (num_tiles / G, B): block t's comparison window, tiles
    ``t*G + g + o[b] // SEG``, is fetched as two aligned G-tile blocks
    (``_window_index``) and tile g reads its comparison tile from one of
    them (``_window_tile``).  G is a function of the shapes alone
    (``tiles_per_step``: a power of two dividing num_tiles whose blocks fit
    the kernel's VMEM); an odd num_tiles gives G = 1, the one-tile grid;
  * the intra-segment wrap ``(i + o[b]) mod SEG`` is a register-level flat
    roll of the tile — no extra memory traffic;
  * per-(particle, iteration) uniforms come from a stateless counter hash
    (no CURAND state loads/stores — beyond-paper win, see EXPERIMENTS.md §Perf);
  * the current ancestor's weight ``w[k]`` is carried by VALUE in a VMEM
    scratch accumulator (never re-fetched), exactly like the register-carried
    ``w_k`` in the CUDA original; the fused kernels carry the ancestor's
    state the same way (DESIGN.md §11).

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
    """Block index map of a ``[d_pad, 8, 128]`` state block that follows the
    weights' map ``index``: the same tile, the plane axis whole."""
    return lambda *args: (0,) + index(*args)


def _state_index_rows(index):
    """``_state_index`` for a bank: ``(s, t, 0)`` becomes ``(s, 0, t, 0)``."""
    def state(*args):
        s, t, _ = index(*args)
        return s, 0, t, 0
    return state


#: Most tiles one grid step of the fused ``apply`` and ``step`` kernels
#: sweeps (``tiles_per_step``).
MAX_TILES_PER_STEP = 128

#: VMEM the G-tile blocks of one fused launch may take (``tiles_per_step``):
#: inside the 16 MiB a v5e kernel may use without raising
#: ``vmem_limit_bytes``, with room for the compiler's own scratch.
BLOCK_VMEM_BYTES = 12 << 20

#: The scoped VMEM a v5e kernel gets unless it raises ``vmem_limit_bytes``.
DEFAULT_VMEM_LIMIT_BYTES = 16 << 20

#: Tiles per iteration of the in-kernel tile loop: a partial unroll, so the
#: scheduler may interleave the tiles' independent chains, while the code
#: the compiler sees stays one loop body whatever G is.  At N = 2^20 on a
#: TPU v5e, 8 takes 2%, 6% and 10-13% less kernel time than 4, 2 and 1
#: (PERF.md §6).
_TILE_UNROLL = 8


def block_vmem_bytes(g_tiles, d_pad, itemsize):
    """VMEM of one fused launch's G-tile blocks: the own and the two
    comparison blocks of the weights and of the ``d_pad`` state planes,
    the ancestor and state outputs, each double-buffered, plus the
    ``w[k]`` and state carries (f32 words).  ``itemsize`` is the widest
    plane word.  The v5e compiler plans the blocked ``apply`` within
    0.05 MiB of this."""
    tile_bytes = TILE * itemsize
    blocks = 2 * (3 * tile_bytes + 4 * d_pad * tile_bytes + TILE * 4)
    carries = TILE * 4 + d_pad * TILE * 4
    return g_tiles * (blocks + carries)


def tiles_per_step(num_tiles, d_pad, itemsize):
    """G, the tiles one grid step of the fused kernels sweeps: the largest
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
    it holds for the whole launch, the resident log-weight block and the
    prelude's two whole-array f32 temporaries (11.9 MiB at N = 2^20 in
    f32 as the v5e compiler plans it), plus 1 MiB for the compiler's own
    scratch; never below the default."""
    resident = log_weights2d.size * (log_weights2d.dtype.itemsize + 2 * 4)
    return max(DEFAULT_VMEM_LIMIT_BYTES, resident + block_bytes + (1 << 20))


def _window_index(num_blocks, g_tiles, second):
    """Block index map of the first (``second`` = 0) or second comparison
    block of a G-tile block grid: tiles ``t*G + g`` compare against tiles
    ``(t*G + g + o // SEG) mod num_tiles``, a window that starts
    ``(o // SEG) mod G`` tiles into aligned block
    ``q = (t + o // SEG // G) mod num_blocks`` and ends in block
    ``(q + 1) mod num_blocks``.  At G = 1 the window is one whole block and
    the second block is never read: its map is constant, so it is fetched
    once per launch."""
    if g_tiles == 1 and second:
        return lambda t, b, offs, *_: (0, 0)

    def index(t, b, offs, *_):
        return (t + offs[b] // SEG // g_tiles + second) % num_blocks, 0

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
    loop iteration runs ``_TILE_UNROLL`` tiles (all G when fewer)."""
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


def _prelude(lw, thr, n_total, st_ref, stats_ref, row):
    """Fused-step prelude: reduce the resident log-weight block to the step
    statistics, latch ``(m, do, deg)`` into SMEM and write the stats row."""
    m, ess_norm, incr, maxw, deg = step_stats(lw, n_total)
    do = ess_norm < thr
    one, zero = jnp.float32(1.0), jnp.float32(0.0)
    st_ref[0] = m
    st_ref[1] = jnp.where(do, one, zero)
    st_ref[2] = jnp.where(deg, one, zero)
    stats_ref[row + (0,)] = ess_norm
    stats_ref[row + (1,)] = jnp.where(do, incr, zero)
    stats_ref[row + (2,)] = jnp.where(do, one, zero)
    stats_ref[row + (3,)] = maxw


def _step_weights(lw_own, lw_cmp, m, deg, n_total, plane):
    """Normalised weights of the two tiles, as the composed path sees them:
    ``exp(lw - m)``, the §16 uniform bank where the degenerate latch is
    set, re-landed on the plane-dtype grid (a no-op at f32)."""
    def norm(lw):
        w = jnp.exp(lw.astype(jnp.float32) - m)
        w = jnp.where(deg, jnp.float32(1.0 / n_total), w)
        return w.astype(plane).astype(jnp.float32)

    return norm(lw_own), norm(lw_cmp)


def _kernel(offsets_ref, seed_ref, w_own_ref, w_cmp_ref, k_ref, wk_ref):
    """Grid step (t, b): one accept/reject sweep of tile t at iteration b."""
    t = pl.program_id(0)
    b = pl.program_id(1)
    n_total = pl.num_programs(0) * SEG
    k_new, wk_new, _ = _sweep(
        t, b, offsets_ref[b], seed_ref[0],
        w_own_ref[...].astype(jnp.float32), w_cmp_ref[...].astype(jnp.float32),
        k_ref[...], wk_ref[...], n_total,
    )
    k_ref[...] = k_new
    wk_ref[...] = wk_new


def _kernel_batch(offsets_ref, seeds_ref, w_own_ref, w_cmp_ref, k_ref, wk_ref):
    """Grid step (s, t, b): row s of the bank, tile t, iteration b.

    The offset table is scalar-prefetched ONCE for the whole bank (the
    batch-axis analogue of Alg. 5's globally shared offset); rows decorrelate
    through their per-row RNG seed ``seeds[s]`` only.  Block shapes carry a
    leading 1 for the batch axis."""
    s = pl.program_id(0)
    t = pl.program_id(1)
    b = pl.program_id(2)
    n_total = pl.num_programs(1) * SEG
    k_new, wk_new, _ = _sweep(
        t, b, offsets_ref[b], seeds_ref[s],
        w_own_ref[0].astype(jnp.float32), w_cmp_ref[0].astype(jnp.float32),
        k_ref[0], wk_ref[...], n_total,
    )
    k_ref[0] = k_new
    wk_ref[...] = wk_new


def _kernel_fused(offsets_ref, seed_ref, w_own_ref, w_lo_ref, w_hi_ref,
                  x_own_ref, x_lo_ref, x_hi_ref, k_ref, out_ref, wk_ref, xk_ref):
    """Fused resample+gather grid step (t, b): the Alg. 5 sweep of the G
    tiles of block t at iteration b, with each ancestor's state carried by
    value beside ``w[k]`` (DESIGN.md §11); the LAST iteration writes the
    carried state tiles to the output.  The ancestor index never
    round-trips through HBM between selection and copy."""
    t = pl.program_id(0)
    b = pl.program_id(1)
    o = offsets_ref[b]
    g_tiles = k_ref.shape[0] // SUBLANES
    n_total = pl.num_programs(0) * (g_tiles * SEG)
    cd = xk_ref.dtype

    def tile(g, s):
        own = _tile_rows(g)
        k_new, wk_new, accept = _sweep(
            t * g_tiles + g, b, o, seed_ref[0],
            w_own_ref[own, :].astype(jnp.float32),
            _window_tile(w_lo_ref, w_hi_ref, s, g_tiles).astype(jnp.float32),
            k_ref[own, :], wk_ref[own, :], n_total,
        )
        k_ref[own, :] = k_new
        wk_ref[own, :] = wk_new
        xk_new = _carry_state(b, o, accept, x_own_ref[:, own, :].astype(cd),
                              _window_tile(x_lo_ref, x_hi_ref, s, g_tiles).astype(cd),
                              xk_ref[:, own, :])
        xk_ref[:, own, :] = xk_new

        @pl.when(b == pl.num_programs(1) - 1)
        def _copy_state():
            out_ref[:, own, :] = xk_new.astype(out_ref.dtype)

    _for_each_tile(g_tiles, o, tile)


def _kernel_fused_rows(offsets_ref, seeds_ref, w_own_ref, w_cmp_ref,
                       x_own_ref, x_cmp_ref, k_ref, out_ref, wk_ref, xk_ref):
    """Fused grid step (s, t, b) over a bank: per-row offset TABLE rows
    ``offsets[s]`` + per-row seed, so row s is bit-identical to the fused
    single kernel with that row's table (passing identical rows recovers
    the shared-offset bank contract of ``_kernel_batch``)."""
    s = pl.program_id(0)
    t = pl.program_id(1)
    b = pl.program_id(2)
    o = offsets_ref[s, b]
    n_total = pl.num_programs(1) * SEG
    k_new, wk_new, accept = _sweep(
        t, b, o, seeds_ref[s],
        w_own_ref[0].astype(jnp.float32), w_cmp_ref[0].astype(jnp.float32),
        k_ref[0], wk_ref[...], n_total,
    )
    k_ref[0] = k_new
    wk_ref[...] = wk_new
    cd = xk_ref.dtype
    xk_new = _carry_state(b, o, accept, x_own_ref[0].astype(cd),
                          x_cmp_ref[0].astype(cd), xk_ref[...])
    xk_ref[...] = xk_new

    @pl.when(b == pl.num_programs(2) - 1)
    def _copy_state():
        out_ref[0] = xk_new.astype(out_ref.dtype)


def _kernel_step(offsets_ref, seed_ref, thr_ref, lw_own_ref, lw_lo_ref,
                 lw_hi_ref, lw_full_ref, x_own_ref, x_lo_ref, x_hi_ref, k_ref,
                 out_ref, stats_ref, wk_ref, xk_ref, st_ref):
    """Fused STEP grid step (t, b): the whole SMC resample decision on-chip.

    At (0, 0) a prelude reduces the resident log-weight array to the step
    statistics (normalisation shift m, normalised ESS, log-evidence
    increment) and latches the resample decision ``ess_norm < threshold``
    into SMEM scratch.  Every sweep of the block's G tiles then runs on
    ``exp(lw - m)`` — the SAME normalised weights the composed path hands
    to ``apply`` — and the last iteration's epilogue either commits the
    selected ancestors and carried state or the identity permutation and
    the tile's own state."""
    t = pl.program_id(0)
    b = pl.program_id(1)
    o = offsets_ref[b]
    g_tiles = k_ref.shape[0] // SUBLANES
    n_total = pl.num_programs(0) * (g_tiles * SEG)

    @pl.when((t == 0) & (b == 0))
    def _():
        _prelude(lw_full_ref[...].astype(jnp.float32), thr_ref[0], n_total,
                 st_ref, stats_ref, ())

    do = st_ref[1] > 0.5
    cd = xk_ref.dtype

    def tile(g, s):
        own = _tile_rows(g)
        t_global = t * g_tiles + g
        w_own, w_cmp = _step_weights(
            lw_own_ref[own, :], _window_tile(lw_lo_ref, lw_hi_ref, s, g_tiles),
            st_ref[0], st_ref[2] > 0.5, n_total, lw_own_ref.dtype)
        k_new, wk_new, accept = _sweep(
            t_global, b, o, seed_ref[0], w_own, w_cmp, k_ref[own, :],
            wk_ref[own, :], n_total,
        )
        k_ref[own, :] = k_new
        wk_ref[own, :] = wk_new
        x_own = x_own_ref[:, own, :].astype(cd)
        xk_new = _carry_state(b, o, accept, x_own,
                              _window_tile(x_lo_ref, x_hi_ref, s, g_tiles).astype(cd),
                              xk_ref[:, own, :])
        xk_ref[:, own, :] = xk_new

        @pl.when(b == pl.num_programs(1) - 1)
        def _commit():
            k_ref[own, :] = step_select(do, k_new, t_global)
            out_ref[:, own, :] = jnp.where(do, xk_new, x_own).astype(out_ref.dtype)

    _for_each_tile(g_tiles, o, tile)


def _kernel_step_rows(offsets_ref, seeds_ref, thr_ref, lw_own_ref, lw_cmp_ref,
                      lw_full_ref, x_own_ref, x_cmp_ref, k_ref, out_ref,
                      stats_ref, wk_ref, xk_ref, st_ref):
    """Fused STEP over a bank, grid (s, t, b): per-row offset tables and
    seeds as in ``_kernel_fused_rows``; the prelude re-runs at each row's
    (t, b) == (0, 0) so the SMEM (m, do) latch and the per-row stats row
    ``stats[s]`` are that row's own decision."""
    s = pl.program_id(0)
    t = pl.program_id(1)
    b = pl.program_id(2)
    o = offsets_ref[s, b]
    n_total = pl.num_programs(1) * SEG

    @pl.when((t == 0) & (b == 0))
    def _():
        _prelude(lw_full_ref[0].astype(jnp.float32), thr_ref[0], n_total,
                 st_ref, stats_ref, (s,))

    do = st_ref[1] > 0.5
    w_own, w_cmp = _step_weights(lw_own_ref[0], lw_cmp_ref[0], st_ref[0],
                                 st_ref[2] > 0.5, n_total, lw_own_ref.dtype)
    k_new, wk_new, accept = _sweep(
        t, b, o, seeds_ref[s], w_own, w_cmp, k_ref[0], wk_ref[...], n_total,
    )
    k_ref[0] = k_new
    wk_ref[...] = wk_new
    cd = xk_ref.dtype
    x_own = x_own_ref[0].astype(cd)
    xk_new = _carry_state(b, o, accept, x_own, x_cmp_ref[0].astype(cd),
                          xk_ref[...])
    xk_ref[...] = xk_new

    @pl.when(b == pl.num_programs(2) - 1)
    def _commit():
        k_ref[0] = step_select(do, k_new, t)
        out_ref[0] = jnp.where(do, xk_new, x_own).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("num_iters", "interpret"))
def megopolis_pallas(
    weights2d: jnp.ndarray,
    offsets: jnp.ndarray,
    seed: jnp.ndarray,
    *,
    num_iters: int,
    interpret: bool,
) -> jnp.ndarray:
    """Raw pallas_call. ``weights2d``: f32[R, 128] with R % 8 == 0;
    ``offsets``: int32[B]; ``seed``: uint32[1].  Returns int32[R, 128]."""
    rows, lanes = weights2d.shape
    assert lanes == LANES and rows % SUBLANES == 0
    num_tiles = rows // SUBLANES

    def _cmp_index(t, b, offs, seed):
        # aligned block chosen by the shared offset (wraps mod num_tiles)
        return (t + offs[b] // SEG) % num_tiles, 0

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,  # offsets + seed live in SMEM, prefetched
        grid=(num_tiles, num_iters),
        in_specs=[
            # own tile: block index constant in b -> fetched once per t
            pl.BlockSpec((SUBLANES, LANES), lambda t, b, offs, seed: (t, 0)),
            pl.BlockSpec((SUBLANES, LANES), _cmp_index),
        ],
        out_specs=pl.BlockSpec((SUBLANES, LANES), lambda t, b, offs, seed: (t, 0)),
        scratch_shapes=[pltpu.VMEM((SUBLANES, LANES), jnp.float32)],
    )
    return pl.pallas_call(
        _kernel,
        grid_spec=grid_spec,
        name="megopolis_pallas",
        out_shape=jax.ShapeDtypeStruct((rows, lanes), jnp.int32),
        interpret=interpret,
    )(offsets, seed, weights2d, weights2d)


@functools.partial(jax.jit, static_argnames=("num_iters", "interpret"))
def megopolis_pallas_batch(
    weights3d: jnp.ndarray,
    offsets: jnp.ndarray,
    seeds: jnp.ndarray,
    *,
    num_iters: int,
    interpret: bool,
) -> jnp.ndarray:
    """Batched pallas_call: a whole ``[Bz, R, 128]`` weight bank in ONE launch.

    Grid grows a LEADING batch dimension (Bz, num_tiles, num_iters) — the
    iteration axis stays innermost so the VMEM ``w[k]`` carry still runs the
    full accept/reject chain per (row, tile) before moving on.  ``offsets``:
    int32[num_iters], ONE table shared by every row (Alg. 5's global offset,
    lifted to the bank — the comparison block index is then identical across
    rows, so the scalar-prefetched schedule is row-invariant); ``seeds``:
    uint32[Bz], one stateless-RNG stream per row.  Returns int32[Bz, R, 128];
    row s is bit-identical to ``megopolis_pallas(weights3d[s], offsets,
    seeds[s:s+1], ...)`` (asserted in tests/test_batched.py).
    """
    bsz, rows, lanes = weights3d.shape
    assert lanes == LANES and rows % SUBLANES == 0
    num_tiles = rows // SUBLANES

    def _own_index(s, t, b, offs, seeds):
        return s, t, 0

    def _cmp_index(s, t, b, offs, seeds):
        # aligned block chosen by the bank-shared offset (wraps mod num_tiles)
        return s, (t + offs[b] // SEG) % num_tiles, 0

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,  # shared offsets + per-row seeds in SMEM
        grid=(bsz, num_tiles, num_iters),
        in_specs=[
            pl.BlockSpec((1, SUBLANES, LANES), _own_index),
            pl.BlockSpec((1, SUBLANES, LANES), _cmp_index),
        ],
        out_specs=pl.BlockSpec((1, SUBLANES, LANES), _own_index),
        scratch_shapes=[pltpu.VMEM((SUBLANES, LANES), jnp.float32)],
    )
    return pl.pallas_call(
        _kernel_batch,
        grid_spec=grid_spec,
        name="megopolis_pallas_batch",
        out_shape=jax.ShapeDtypeStruct((bsz, rows, lanes), jnp.int32),
        interpret=interpret,
    )(offsets, seeds, weights3d, weights3d)


@functools.partial(jax.jit, static_argnames=("num_iters", "interpret"))
def megopolis_pallas_fused(
    weights2d: jnp.ndarray,
    planes: jnp.ndarray,
    offsets: jnp.ndarray,
    seed: jnp.ndarray,
    *,
    num_iters: int,
    interpret: bool,
):
    """Fused resample+gather pallas_call (DESIGN.md §11).  ``planes``:
    particle state as a ``[d_pad, R, 128]`` plane stack, streamed a block
    of G tiles at a time beside the weights (own and comparison blocks,
    G from ``tiles_per_step``); other arguments as for
    ``megopolis_pallas``.  Returns ``(ancestors int32[R, 128], state
    [d_pad, R, 128])`` — the ancestor stream is identical to the unfused
    kernel's (same sweep arithmetic, same RNG)."""
    rows, lanes = weights2d.shape
    assert lanes == LANES and rows % SUBLANES == 0
    d_pad = planes.shape[0]
    assert planes.shape[1:] == (rows, lanes)
    num_tiles = rows // SUBLANES
    itemsize = max(weights2d.dtype.itemsize, planes.dtype.itemsize)
    g_tiles = tiles_per_step(num_tiles, d_pad, itemsize)
    num_blocks = num_tiles // g_tiles

    def _own_index(t, b, offs, seed):
        return t, 0

    block = (g_tiles * SUBLANES, LANES)
    state_block = (d_pad,) + block
    lo, hi = (_window_index(num_blocks, g_tiles, second) for second in (0, 1))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(num_blocks, num_iters),
        in_specs=[
            pl.BlockSpec(block, _own_index),
            pl.BlockSpec(block, lo),
            pl.BlockSpec(block, hi),
            pl.BlockSpec(state_block, _state_index(_own_index)),
            pl.BlockSpec(state_block, _state_index(lo)),
            pl.BlockSpec(state_block, _state_index(hi)),
        ],
        out_specs=[
            pl.BlockSpec(block, _own_index),
            pl.BlockSpec(state_block, _state_index(_own_index)),
        ],
        scratch_shapes=[
            pltpu.VMEM(block, jnp.float32),
            pltpu.VMEM(state_block, _carry_dtype(planes.dtype)),
        ],
    )
    return pl.pallas_call(
        _kernel_fused,
        grid_spec=grid_spec,
        name="megopolis_pallas_apply",
        out_shape=[
            jax.ShapeDtypeStruct((rows, lanes), jnp.int32),
            jax.ShapeDtypeStruct((d_pad, rows, lanes), planes.dtype),
        ],
        interpret=interpret,
    )(offsets, seed, weights2d, weights2d, weights2d, planes, planes, planes)


@functools.partial(jax.jit, static_argnames=("num_iters", "interpret"))
def megopolis_pallas_fused_rows(
    weights3d: jnp.ndarray,
    planes4d: jnp.ndarray,
    offsets2d: jnp.ndarray,
    seeds: jnp.ndarray,
    *,
    num_iters: int,
    interpret: bool,
):
    """Fused bank launch: grid (Bz, num_tiles, num_iters) with PER-ROW
    offset tables ``offsets2d`` int32[Bz, num_iters] and per-row seeds.

    Row s is bit-identical to ``megopolis_pallas_fused(weights3d[s],
    planes4d[s], offsets2d[s], seeds[s:s+1], ...)`` — the explicit-key bank
    path (``apply_rows``).  Passing identical table rows recovers the
    shared-offset ``apply``-bank contract (one scalar-prefetch schedule,
    row-invariant comparison blocks).  Returns ``(int32[Bz, R, 128],
    [Bz, d_pad, R, 128])``."""
    bsz, rows, lanes = weights3d.shape
    assert lanes == LANES and rows % SUBLANES == 0
    d_pad = planes4d.shape[1]
    assert planes4d.shape == (bsz, d_pad, rows, lanes)
    num_tiles = rows // SUBLANES

    def _own_index(s, t, b, offs, seeds):
        return s, t, 0

    def _cmp_index(s, t, b, offs, seeds):
        return s, (t + offs[s, b] // SEG) % num_tiles, 0

    state_block = (1, d_pad, SUBLANES, LANES)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(bsz, num_tiles, num_iters),
        in_specs=[
            pl.BlockSpec((1, SUBLANES, LANES), _own_index),
            pl.BlockSpec((1, SUBLANES, LANES), _cmp_index),
            pl.BlockSpec(state_block, _state_index_rows(_own_index)),
            pl.BlockSpec(state_block, _state_index_rows(_cmp_index)),
        ],
        out_specs=[
            pl.BlockSpec((1, SUBLANES, LANES), _own_index),
            pl.BlockSpec(state_block, _state_index_rows(_own_index)),
        ],
        scratch_shapes=[
            pltpu.VMEM((SUBLANES, LANES), jnp.float32),
            pltpu.VMEM(state_block[1:], _carry_dtype(planes4d.dtype)),
        ],
    )
    return pl.pallas_call(
        _kernel_fused_rows,
        grid_spec=grid_spec,
        name="megopolis_pallas_apply_rows",
        out_shape=[
            jax.ShapeDtypeStruct((bsz, rows, lanes), jnp.int32),
            jax.ShapeDtypeStruct((bsz, d_pad, rows, lanes), planes4d.dtype),
        ],
        interpret=interpret,
    )(offsets2d, seeds, weights3d, weights3d, planes4d, planes4d)


@functools.partial(jax.jit, static_argnames=("num_iters", "interpret"))
def megopolis_pallas_step(
    log_weights2d: jnp.ndarray,
    planes: jnp.ndarray,
    offsets: jnp.ndarray,
    seed: jnp.ndarray,
    thr: jnp.ndarray,
    *,
    num_iters: int,
    interpret: bool,
):
    """Fused SMC-step pallas_call (DESIGN.md §12): normalise → ESS →
    conditional resample → state copy, ONE launch.  ``log_weights2d``:
    f32[R, 128] UNNORMALISED log-weights (streamed a block of G tiles at
    a time AND kept whole-array resident for the on-chip reduction — the
    step form inherits the whole-weights VMEM cap); ``thr``: f32[1] ESS/N
    trigger.
    Returns ``(ancestors int32[R, 128], state [d_pad, R, 128], stats f32[4]
    = (ess_norm, log_evidence_incr, resampled, max_weight) — the in-kernel
    StepStats vector of DESIGN.md §15)``."""
    rows, lanes = log_weights2d.shape
    assert lanes == LANES and rows % SUBLANES == 0
    d_pad = planes.shape[0]
    assert planes.shape[1:] == (rows, lanes)
    num_tiles = rows // SUBLANES
    itemsize = max(log_weights2d.dtype.itemsize, planes.dtype.itemsize)
    g_tiles = tiles_per_step(num_tiles, d_pad, itemsize)
    num_blocks = num_tiles // g_tiles

    def _own_index(t, b, offs, seed, thr):
        return t, 0

    block = (g_tiles * SUBLANES, LANES)
    state_block = (d_pad,) + block
    lo, hi = (_window_index(num_blocks, g_tiles, second) for second in (0, 1))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,  # offsets + seed + f32 ESS threshold
        grid=(num_blocks, num_iters),
        in_specs=[
            pl.BlockSpec(block, _own_index),
            pl.BlockSpec(block, lo),
            pl.BlockSpec(block, hi),
            # whole log-weight array resident for the (0,0) stats prelude
            pl.BlockSpec((rows, LANES), lambda t, b, o, s, r: (0, 0)),
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
            pltpu.VMEM(block, jnp.float32),
            pltpu.VMEM(state_block, _carry_dtype(planes.dtype)),
            pltpu.SMEM((3,), jnp.float32),  # (m, do, deg) latch across grid steps
        ],
    )
    vmem_limit = step_vmem_limit_bytes(
        log_weights2d, block_vmem_bytes(g_tiles, d_pad, itemsize))
    return pl.pallas_call(
        _kernel_step,
        grid_spec=grid_spec,
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=vmem_limit),
        name="megopolis_pallas_step",
        out_shape=[
            jax.ShapeDtypeStruct((rows, lanes), jnp.int32),
            jax.ShapeDtypeStruct((d_pad, rows, lanes), planes.dtype),
            jax.ShapeDtypeStruct((4,), jnp.float32),
        ],
        interpret=interpret,
    )(offsets, seed, thr, log_weights2d, log_weights2d, log_weights2d,
      log_weights2d, planes, planes, planes)


@functools.partial(jax.jit, static_argnames=("num_iters", "interpret"))
def megopolis_pallas_step_rows(
    log_weights3d: jnp.ndarray,
    planes4d: jnp.ndarray,
    offsets2d: jnp.ndarray,
    seeds: jnp.ndarray,
    thr: jnp.ndarray,
    *,
    num_iters: int,
    interpret: bool,
):
    """Fused SMC-step bank launch: row s is bit-identical to
    ``megopolis_pallas_step(log_weights3d[s], planes4d[s], offsets2d[s],
    seeds[s:s+1], thr, ...)`` — each row takes its OWN resample decision.
    Returns ``(int32[Bz, R, 128], [Bz, d_pad, R, 128], f32[Bz, 4])``."""
    bsz, rows, lanes = log_weights3d.shape
    assert lanes == LANES and rows % SUBLANES == 0
    d_pad = planes4d.shape[1]
    assert planes4d.shape == (bsz, d_pad, rows, lanes)
    num_tiles = rows // SUBLANES

    def _own_index(s, t, b, offs, seeds, thr):
        return s, t, 0

    def _cmp_index(s, t, b, offs, seeds, thr):
        return s, (t + offs[s, b] // SEG) % num_tiles, 0

    state_block = (1, d_pad, SUBLANES, LANES)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(bsz, num_tiles, num_iters),
        in_specs=[
            pl.BlockSpec((1, SUBLANES, LANES), _own_index),
            pl.BlockSpec((1, SUBLANES, LANES), _cmp_index),
            pl.BlockSpec((1, rows, LANES), lambda s, t, b, o, se, r: (s, 0, 0)),
            pl.BlockSpec(state_block, _state_index_rows(_own_index)),
            pl.BlockSpec(state_block, _state_index_rows(_cmp_index)),
        ],
        out_specs=[
            pl.BlockSpec((1, SUBLANES, LANES), _own_index),
            pl.BlockSpec(state_block, _state_index_rows(_own_index)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        scratch_shapes=[
            pltpu.VMEM((SUBLANES, LANES), jnp.float32),
            pltpu.VMEM(state_block[1:], _carry_dtype(planes4d.dtype)),
            pltpu.SMEM((3,), jnp.float32),
        ],
    )
    return pl.pallas_call(
        _kernel_step_rows,
        grid_spec=grid_spec,
        name="megopolis_pallas_step_rows",
        out_shape=[
            jax.ShapeDtypeStruct((bsz, rows, lanes), jnp.int32),
            jax.ShapeDtypeStruct((bsz, d_pad, rows, lanes), planes4d.dtype),
            jax.ShapeDtypeStruct((bsz, 4), jnp.float32),
        ],
        interpret=interpret,
    )(offsets2d, seeds, thr, log_weights3d, log_weights3d, log_weights3d,
      planes4d, planes4d)
