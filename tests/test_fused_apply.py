"""Fused resample+gather (``Resampler.apply``) quality gate (DESIGN.md §11).

Contract under test, over the FULL family × backend matrix:

  1. **composition parity** — ``apply(key, w, p)`` is bit-identical to
     ``(take(p, r(key, w)), r(key, w))`` on the SAME backend, for single,
     bank (``apply_batch`` vs ``batch``) and explicit-key rows
     (``apply_rows`` vs ``batch_rows``) forms;
  2. **state layout** — scalar ``[N]`` states, trailing multi-dim states,
     a ``state_dim`` NOT divisible by the plane tile (padding path), and
     4-byte integer states all gather exactly;
  3. **state-column equivariance** (hypothesis) — permuting state columns
     commutes with ``apply`` (pins that plane packing/padding never mixes
     components);
  4. **residency** — the fused kernels enforce the VMEM state budget with
     a clear error;
  5. **consumers** — the resample paths of ``ParticleFilter.step``,
     ``run_filter_bank`` and the AIS sampler contain no ``jnp.take`` (the
     HBM index round-trip the fused path exists to remove), and the
     analytic memory model says fused < unfused.
"""


import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.resamplers.batched import split_batch_keys
from repro.core.spec import spec_for_backend
from repro.kernels.common import (
    MAX_VMEM_STATE,
    STATE_PLANE_TILE,
    TILE,
    pack_state_planes,
    pad_state_dim,
    unpack_state_planes,
)

N = 2 * TILE
BATCH = 3
ITERS = 8
MAX_ITERS = 24

FAMILIES = (
    "megopolis",
    "metropolis",
    "metropolis_c1",
    "metropolis_c2",
    "rejection",
    "multinomial",
    "systematic",
    "improved_systematic",
    "stratified",
    "residual",
)
BACKENDS = ("reference", "xla", "pallas_interpret")
#: The DESIGN.md §14 compression axis the parity tests sweep.
PLANE_DTYPES_TESTED = ("float32", "bfloat16")


def _build(name, backend, plane_dtype="float32"):
    return spec_for_backend(name, backend, num_iters=ITERS, max_iters=MAX_ITERS,
                            plane_dtype=plane_dtype).build()


@pytest.fixture(scope="module")
def w_single():
    return jax.random.uniform(jax.random.PRNGKey(11), (N,)) + 1e-3


@pytest.fixture(scope="module")
def w_bank():
    return jax.random.uniform(jax.random.PRNGKey(12), (BATCH, N)) + 1e-3


@pytest.fixture(scope="module")
def p_single():
    return jax.random.normal(jax.random.PRNGKey(13), (N, 4))


@pytest.fixture(scope="module")
def p_bank():
    return jax.random.normal(jax.random.PRNGKey(14), (BATCH, N, 4))


def _assert_equal(a, b):
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ------------------------------------------------- 1. composition parity
@pytest.mark.parametrize("plane_dtype", PLANE_DTYPES_TESTED)
@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("name", FAMILIES)
def test_apply_single_matches_take(name, backend, plane_dtype, w_single,
                                   p_single, base_key):
    r = _build(name, backend, plane_dtype)
    ancestors = r(base_key, w_single)
    got_p, got_a = r.apply(base_key, w_single, p_single)
    _assert_equal(got_a, ancestors)
    # Compressed cells gather the QUANTISED plane (DESIGN.md §14); at f32
    # ``quantise`` is the identity and this is the original oracle.
    _assert_equal(got_p, jnp.take(r.quantise(p_single), ancestors, axis=0))


@pytest.mark.parametrize("plane_dtype", PLANE_DTYPES_TESTED)
@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("name", FAMILIES)
def test_apply_batch_matches_take(name, backend, plane_dtype, w_bank, p_bank,
                                  base_key):
    r = _build(name, backend, plane_dtype)
    ancestors = r.batch(base_key, w_bank)
    got_p, got_a = r.apply_batch(base_key, w_bank, p_bank)
    _assert_equal(got_a, ancestors)
    _assert_equal(
        got_p,
        jax.vmap(lambda p, a: jnp.take(p, a, axis=0))(
            r.quantise(p_bank), ancestors
        ),
    )


@pytest.mark.parametrize("plane_dtype", PLANE_DTYPES_TESTED)
@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("name", FAMILIES)
def test_apply_rows_matches_rows(name, backend, plane_dtype, w_bank, p_bank,
                                 base_key):
    """apply_rows row b == apply(keys[b], w[b], p[b]) — the filter-bank
    contract — and its ancestors == batch_rows."""
    r = _build(name, backend, plane_dtype)
    keys = split_batch_keys(base_key, BATCH)
    got_p, got_a = r.apply_rows(keys, w_bank, p_bank)
    _assert_equal(got_a, r.batch_rows(keys, w_bank))
    for b in range(BATCH):
        pb, ab = r.apply(keys[b], w_bank[b], p_bank[b])
        _assert_equal(got_a[b], ab)
        _assert_equal(got_p[b], pb)


# ------------------------------------------ 1b. degenerate-weight parity
# The §12 entry-consistency cells extended to collapsed WEIGHT banks
# (DESIGN.md §16, satellite S3): under guard='recover', every degenerate
# signature resamples exactly like the uniform bank, and the fused
# entries stay mutually consistent (__call__ == apply ancestors,
# apply_rows row b == apply row b) — family × backend × plane dtype.
def _degenerate_weight_cases(n):
    uni = jnp.full((n,), 1.0 / n, jnp.float32)
    return {
        "all_nan": jnp.full((n,), jnp.nan, jnp.float32),
        "all_zero": jnp.zeros((n,), jnp.float32),
        "pos_inf_entry": uni.at[5].set(jnp.inf),
        "subnormal": jnp.full((n,), 1e-40, jnp.float32),
        "one_hot": jnp.zeros((n,), jnp.float32).at[n // 3].set(1.0),
    }


@pytest.mark.parametrize("plane_dtype", PLANE_DTYPES_TESTED)
@pytest.mark.parametrize("case", sorted(_degenerate_weight_cases(4)))
@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("name", ("megopolis", "rejection", "systematic"))
def test_degenerate_weights_entry_consistency(name, backend, case,
                                              plane_dtype, p_single, p_bank,
                                              base_key):
    w = _degenerate_weight_cases(N)[case]
    r = spec_for_backend(name, backend, num_iters=ITERS,
                         max_iters=MAX_ITERS, plane_dtype=plane_dtype,
                         guard="recover").build()
    ancestors = r(base_key, w)
    assert bool(jnp.all((ancestors >= 0) & (ancestors < N)))
    got_p, got_a = r.apply(base_key, w, p_single)
    _assert_equal(got_a, ancestors)
    _assert_equal(got_p, jnp.take(r.quantise(p_single), ancestors, axis=0))
    keys = split_batch_keys(base_key, BATCH)
    w_bank = jnp.stack([w] * BATCH)
    rows_p, rows_a = r.apply_rows(keys, w_bank, p_bank)
    for b in range(BATCH):
        pb, ab = r.apply(keys[b], w_bank[b], p_bank[b])
        _assert_equal(rows_a[b], ab)
        _assert_equal(rows_p[b], pb)


@pytest.mark.parametrize("backend", BACKENDS)
def test_degenerate_weights_recover_equals_uniform(backend, base_key):
    """The recover contract on the weights entries: collapsed banks draw
    the SAME ancestors as the uniform bank with the same key."""
    r = spec_for_backend("systematic", backend, guard="recover").build()
    uni = jnp.full((N,), 1.0 / N, jnp.float32)
    exp = r(base_key, uni)
    for case in ("all_nan", "all_zero", "pos_inf_entry"):
        _assert_equal(r(base_key, _degenerate_weight_cases(N)[case]), exp)


# ------------------------------------------------------- 2. state layouts
@pytest.mark.parametrize("backend", ("reference", "pallas_interpret"))
@pytest.mark.parametrize("name", ("megopolis", "rejection", "systematic"))
def test_apply_scalar_state(name, backend, w_single, base_key):
    p = jax.random.normal(jax.random.PRNGKey(21), (N,))
    r = _build(name, backend)
    got_p, got_a = r.apply(base_key, w_single, p)
    assert got_p.shape == (N,)
    _assert_equal(got_p, jnp.take(p, got_a, axis=0))


@pytest.mark.parametrize("name", FAMILIES)
def test_apply_padded_state_dim(name, w_single, base_key):
    """state_dim = 5 is not divisible by the plane tile (8): the kernel
    lane must pad, gather and unpad without touching real components."""
    assert 5 % STATE_PLANE_TILE != 0
    p = jax.random.normal(jax.random.PRNGKey(22), (N, 5))
    r = _build(name, "pallas_interpret")
    got_p, got_a = r.apply(base_key, w_single, p)
    _assert_equal(got_p, jnp.take(p, got_a, axis=0))


@pytest.mark.parametrize("name", ("megopolis", "metropolis"))
def test_apply_multidim_and_int_state(name, w_single, base_key):
    r = _build(name, "pallas_interpret")
    p3 = jax.random.normal(jax.random.PRNGKey(23), (N, 2, 3))
    got_p, got_a = r.apply(base_key, w_single, p3)
    _assert_equal(got_p, jnp.take(p3, got_a, axis=0))
    pi = jax.random.randint(jax.random.PRNGKey(24), (N, 3), 0, 1 << 20)
    got_pi, got_ai = r.apply(base_key, w_single, pi)
    assert got_pi.dtype == pi.dtype
    _assert_equal(got_pi, jnp.take(pi, got_ai, axis=0))


def test_pack_unpack_roundtrip():
    for shape in [(N,), (N, 1), (N, 4), (N, 5), (N, 2, 3)]:
        p = jax.random.normal(jax.random.PRNGKey(25), shape)
        planes, state_shape = pack_state_planes(p)
        d = int(np.prod(shape[1:])) if len(shape) > 1 else 1
        assert planes.shape[0] == pad_state_dim(d)
        _assert_equal(unpack_state_planes(planes, state_shape), p)


# --------------------------------------- 3. state-column equivariance
def _check_column_permutation(seed: int):
    """apply(key, w, p[:, perm]) == apply(key, w, p)[:, perm]: the fused
    plane packing must never mix state components, padded or not."""
    k = jax.random.PRNGKey(seed)
    d = 1 + seed % 11  # covers padded (d % 8 != 0) and unpadded dims
    w = jax.random.uniform(jax.random.fold_in(k, 0), (N,)) + 1e-3
    p = jax.random.normal(jax.random.fold_in(k, 1), (N, d))
    perm = jax.random.permutation(jax.random.fold_in(k, 2), d)
    r = _build("megopolis", "pallas_interpret")
    key = jax.random.fold_in(k, 3)
    out, _ = r.apply(key, w, p)
    out_perm, _ = r.apply(key, w, p[:, perm])
    _assert_equal(out_perm, out[:, perm])


try:
    from hypothesis import given, settings, strategies as st

    @given(seed=st.integers(0, 2**30))
    @settings(max_examples=10, deadline=None)
    def test_apply_state_column_permutation_equivariance(seed):
        _check_column_permutation(seed)

except ImportError:
    # hypothesis absent (CI installs it): pinned seed grid instead.
    @pytest.mark.parametrize("seed", [0, 3, 7, 12, 31])
    def test_apply_state_column_permutation_equivariance(seed):
        _check_column_permutation(seed)


@pytest.mark.parametrize("backend", ("reference", "pallas_interpret"))
@pytest.mark.parametrize("name", ("megopolis", "metropolis"))
def test_apply_rows_rejects_short_key_array(name, backend, w_bank, p_bank, base_key):
    """A keys array shorter than the bank must raise — the fused bank
    kernels size their grid from weights and would otherwise read
    out-of-bounds seeds."""
    r = _build(name, backend)
    keys = split_batch_keys(base_key, BATCH - 1)
    with pytest.raises(ValueError, match="one key per row"):
        r.apply_rows(keys, w_bank, p_bank)


# ----------------------------------- 1b. cross-dtype ancestor bit-parity
@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("name", FAMILIES)
def test_compressed_ancestors_bit_identical_to_f32(name, backend, w_single,
                                                   base_key):
    """The DESIGN.md §14 headline claim: compressing the planes never
    perturbs the ancestor stream.  ``r_bf16(key, w)`` equals
    ``r_f32(key, r_bf16.quantise(w))`` ancestor-for-ancestor, because
    selection arithmetic, RNG and bisection all stay f32 on-chip — only
    the stored operand values move to the bf16 grid."""
    rb = _build(name, backend, "bfloat16")
    rf = _build(name, backend, "float32")
    _assert_equal(rb(base_key, w_single), rf(base_key, rb.quantise(w_single)))


# ------------------------------------------------------- 4. residency cap
def _over_state_cap():
    """A state just past the resident-plane-stack budget."""
    return MAX_VMEM_STATE // N // STATE_PLANE_TILE * STATE_PLANE_TILE + STATE_PLANE_TILE


def test_apply_state_residency_cap(base_key):
    """The families that keep the whole plane stack VMEM-resident
    (Metropolis, rejection) refuse a state past the budget."""
    p = jnp.zeros((N, _over_state_cap()), jnp.float32)
    w = jnp.ones((N,), jnp.float32)
    r = _build("metropolis", "pallas_interpret")
    with pytest.raises(ValueError, match="VMEM"):
        r.apply(base_key, w, p)


@pytest.mark.parametrize("entry", ["apply", "apply_rows"])
def test_megopolis_apply_has_no_state_cap(entry, base_key):
    """Megopolis carries the ancestor's state by value through the sweep
    (one aligned tile per load), so no residency cap binds it: a state past
    the resident budget still copies bit-exactly."""
    d = _over_state_cap()
    w = jax.random.uniform(jax.random.PRNGKey(5), (N,)) + 1e-3
    p = jax.random.normal(jax.random.PRNGKey(6), (N, d))
    r = _build("megopolis", "pallas_interpret")
    if entry == "apply":
        got_p, got_a = r.apply(base_key, w, p)
        _assert_equal(got_a, r(base_key, w))
        _assert_equal(got_p, jnp.take(p, got_a, axis=0))
    else:
        keys = jax.random.split(base_key, 2)
        got_p, got_a = r.apply_rows(keys, jnp.stack([w, w[::-1]]), jnp.stack([p, -p]))
        _assert_equal(got_p[1], jnp.take(-p, got_a[1], axis=0))


def test_f16_residency_edge_admits_wider_state(base_key):
    """The eq.(3) residency edge re-derives from the plane itemsize
    (DESIGN.md §14): at N=1024 a padded state of 2064 components overflows
    the 4-byte f32 byte budget but fits in half-width f16 planes."""
    n, d = 1024, 2056  # pad_state_dim(2056) == 2064
    assert n * pad_state_dim(d) > MAX_VMEM_STATE          # f32: over budget
    assert n * pad_state_dim(d) * 2 <= MAX_VMEM_STATE * 4  # f16: within bytes
    w = jnp.ones((n,), jnp.float32)
    p = jnp.zeros((n, d), jnp.float32)
    with pytest.raises(ValueError, match="VMEM"):
        _build("metropolis", "pallas_interpret").apply(base_key, w, p)
    r16 = _build("metropolis", "pallas_interpret", "float16")
    got_p, got_a = r16.apply(base_key, w, p)
    assert got_p.shape == (n, d)
    _assert_equal(got_p, jnp.take(r16.quantise(p), got_a, axis=0))


# ----------------------------------------------------------- 5. consumers
@pytest.mark.parametrize(
    "consumer",
    (
        "pf.step",
        "pf.run_filter_bank",
        "ais.run_smc_sampler",
        "ais.run_smc_sampler_bank",
    ),
)
def test_resample_paths_contain_no_take(consumer):
    """The acceptance gate of the fused data path: ancestors never leave a
    kernel to index an HBM gather — asserted on the consumers' traced
    jaxprs by the DESIGN.md §13 taint pass, not by grepping their source."""
    from repro.analysis import audit_consumers

    (rep,) = audit_consumers(names=[consumer])
    assert rep.ok, rep.violations
    assert rep.tainted_gathers == 0


def test_memmodel_fused_beats_unfused():
    from repro.launch.memmodel import resample_step_bytes

    for n in (1 << 10, 1 << 16, 1 << 20):
        for d in (1, 4, 32):
            fused = resample_step_bytes(n, d, fused=True)
            unfused = resample_step_bytes(n, d, fused=False)
            assert fused["total"] < unfused["total"]
            assert unfused["total"] - fused["total"] == n * 4  # the index vector


def test_filter_step_is_fused_and_matches_reference(base_key):
    """End-to-end: a ParticleFilter on the pallas_interpret backend steps
    through apply and equals the manual index+take composition."""
    from repro.core.spec import MegopolisSpec
    from repro.pf import ParticleFilter, ungm

    pf = ParticleFilter(
        model=ungm(),
        num_particles=TILE,
        resampler=MegopolisSpec(num_iters=ITERS, segment=1024,
                                backend="pallas_interpret"),
    )
    particles = pf.model.init(jax.random.PRNGKey(30), TILE)
    z = jnp.float32(0.3)
    x_bar, est, w, _ = pf.step(base_key, particles, z, jnp.float32(1.0))
    # replay the step manually through the index path
    k_pred, k_res = jax.random.split(base_key)
    x = pf.model.transition(k_pred, particles, jnp.float32(1.0))
    w_ref = pf.model.likelihood(z, x, jnp.float32(1.0))
    anc = pf._built(k_res, w_ref)
    _assert_equal(x_bar, jnp.take(x, anc, axis=0))
    _assert_equal(w, w_ref)


# ------------------------------- 6. blocked grid: G tiles per grid step
#: Tile counts of the blocked-geometry parity cases, with the G that
#: ``tiles_per_step`` takes for each at these small shapes.
BLOCKED_G = {1: 1, 3: 1, 12: 4, 16: 16}
BLOCKED_ITERS = 3


def blocked_offsets(table, n, g):
    """Hand-made offset tables (one offset per iteration) that put every
    block's comparison window where the blocked grid splits it: ``r0``
    starts it on a block boundary (``(o // 1024) mod G == 0``), ``rlast``
    one tile short of the next block (``G - 1``), and ``wrap`` at tile
    ``num_tiles - 1``, so the last block's window runs past the end of the
    array into block 0."""
    num_tiles = n // TILE
    starts = {
        "r0": ((0, 5), (g, 1000), (0, 1023)),
        "rlast": ((g - 1, 77), (2 * g - 1, 1), (g - 1, 0)),
        "wrap": ((num_tiles - 1, 1023), (num_tiles - 1, 3), (num_tiles - 1, 512)),
    }[table]
    return jnp.array([(c * TILE + s) % n for c, s in starts], jnp.int32)


def blocked_inputs(num_tiles, plane_dtype, state_dim, key):
    """Weights, particles and the plane stack the fused kernels take, all
    on the ``plane_dtype`` grid."""
    from repro.kernels.common import compress_plane, quantise_plane

    n = num_tiles * TILE
    kw, kp = jax.random.split(key)
    w = quantise_plane(jax.random.uniform(kw, (n,)) + 1e-3, plane_dtype)
    shape = (n,) if state_dim == 1 else (n, state_dim)
    p = quantise_plane(jax.random.normal(kp, shape), plane_dtype)
    planes, state_shape = pack_state_planes(p)
    return w, p, compress_plane(planes, plane_dtype), state_shape


#: The two rows' tables of the ``bank`` cases: a two-row launch whose rows
#: split their comparison windows at different tiles.
BANK_TABLES = ("rlast", "wrap")

#: ``(num_tiles, table, plane_dtype)`` of the blocked parity cases: every
#: single-row table at every G, and a two-row ``bank`` at G = 4 and 16.
BLOCKED_CASES = [(num_tiles, table, plane_dtype)
                 for num_tiles in sorted(BLOCKED_G)
                 for table in ("r0", "rlast", "wrap")
                 for plane_dtype in PLANE_DTYPES_TESTED]
BLOCKED_CASES += [(12, "bank", "float32"), (16, "bank", "float32")]


def blocked_tables(table):
    """The table name of each row of a blocked parity case."""
    return BANK_TABLES if table == "bank" else (table,)


@pytest.mark.parametrize("state_dim", (1, 4))
@pytest.mark.parametrize("num_tiles,table,plane_dtype", BLOCKED_CASES)
def test_blocked_apply_matches_ref(num_tiles, table, plane_dtype, state_dim):
    """The fused apply kernel, called directly at tile counts that give
    G = 1, 1, 4 and 16, equals the ``megopolis_ref`` oracle and the state
    gather of its ancestors bit for bit, wherever the window starts.  Each
    row of a two-row bank, with its own weights, state, table and seed,
    equals the one-row launch on that row's inputs."""
    from repro.kernels.common import compress_plane
    from repro.kernels.megopolis.megopolis import (
        megopolis_pallas_fused,
        tiles_per_step,
    )
    from repro.kernels.megopolis.ref import megopolis_ref

    n = num_tiles * TILE
    tables = blocked_tables(table)
    rows = [blocked_inputs(num_tiles, plane_dtype, state_dim,
                           jax.random.PRNGKey(num_tiles + 100 * s))
            for s in range(len(tables))]
    w3 = compress_plane(jnp.stack([w.reshape(n // 128, 128) for w, *_ in rows]),
                        plane_dtype)
    planes = jnp.stack([planes for _, _, planes, _ in rows])
    g = tiles_per_step(num_tiles, planes.shape[1], w3.dtype.itemsize)
    assert g == BLOCKED_G[num_tiles]
    offsets = jnp.stack([blocked_offsets(t, n, g) for t in tables])
    seeds = jnp.arange(len(tables), dtype=jnp.uint32) + 2021 + num_tiles
    k3, out = megopolis_pallas_fused(w3, planes, offsets, seeds,
                                     num_iters=BLOCKED_ITERS, interpret=True)
    for s, (w, p, _, state_shape) in enumerate(rows):
        ancestors = megopolis_ref(w, offsets[s], seeds[s:s + 1], num_iters=BLOCKED_ITERS)
        _assert_equal(k3[s].reshape(n), ancestors)
        _assert_equal(unpack_state_planes(out[s].astype(p.dtype), state_shape),
                      jnp.take(p, ancestors, axis=0))
        if len(rows) > 1:
            one = megopolis_pallas_fused(w3[s:s + 1], planes[s:s + 1], offsets[s:s + 1],
                                         seeds[s:s + 1], num_iters=BLOCKED_ITERS,
                                         interpret=True)
            _assert_equal(k3[s], one[0][0])
            _assert_equal(out[s], one[1][0])


@pytest.mark.parametrize("state_dim", (1, 4))
@pytest.mark.parametrize("entry", ("apply", "step", "apply_rows", "step_rows"))
def test_blocked_tile_loop_has_no_branch(entry, state_dim):
    """The G-tile loop of both blocked fused kernels, single and bank,
    holds no branch, so its unrolled tiles are one block the scheduler may
    interleave; the last iteration's write is the one ``cond`` after the
    loop, once per grid step.  The step's prelude is the only ``cond``
    before it."""
    from repro.analysis.walker import count_primitive, pallas_call_eqns, subjaxprs
    from repro.kernels.megopolis.megopolis import (
        megopolis_pallas_fused,
        megopolis_pallas_step,
        tiles_per_step,
    )

    num_tiles = 16
    bsz = 2 if entry.endswith("_rows") else 1
    rows = num_tiles * TILE // 128
    d_pad = pad_state_dim(state_dim)
    assert tiles_per_step(num_tiles, d_pad, 4) == BLOCKED_G[num_tiles] > 1
    w = jax.ShapeDtypeStruct((bsz, rows, 128), jnp.float32)
    planes = jax.ShapeDtypeStruct((bsz, d_pad, rows, 128), jnp.float32)
    offsets = jax.ShapeDtypeStruct((bsz, BLOCKED_ITERS), jnp.int32)
    seeds = jax.ShapeDtypeStruct((bsz,), jnp.uint32)
    name = f"megopolis_pallas_{entry}"
    if entry.startswith("apply"):
        jaxpr = jax.make_jaxpr(lambda *a: megopolis_pallas_fused(
            *a, num_iters=BLOCKED_ITERS, interpret=True, name=name))(
                w, planes, offsets, seeds)
    else:
        thr = jax.ShapeDtypeStruct((1,), jnp.float32)
        jaxpr = jax.make_jaxpr(lambda *a: megopolis_pallas_step(
            *a, num_iters=BLOCKED_ITERS, interpret=True, name=name))(
                w, planes, offsets, seeds, thr)
    ((call, _),) = pallas_call_eqns(jaxpr)
    assert call.params["name"] == name
    kernel = call.params["jaxpr"]
    (loop,) = [i for i, e in enumerate(kernel.eqns) if e.primitive.name in ("scan", "while")]
    assert sum(count_primitive(sub, "cond") for _, sub in subjaxprs(kernel.eqns[loop])) == 0
    conds = [i for i, e in enumerate(kernel.eqns) if e.primitive.name == "cond"]
    assert [i > loop for i in conds] == ([True] if entry.startswith("apply") else [False, True])
    assert count_primitive(kernel, "cond") == len(conds)


@pytest.mark.parametrize("entry", ("apply", "step"))
def test_tiles_per_step_at_cell_shapes(entry):
    """G at the benchmark cells' shape (N = 2^20, one f32 state plane) is
    the cap for both kernels; an odd tile count keeps one tile per grid
    step; a wide state (8 planes: ``apply`` at N = 2^22, ``step`` at
    N = 2^20) takes a smaller G whose blocks fit the budget.  The step
    raises its scoped VMEM to hold its resident log-weights and prelude
    beside the blocks."""
    from repro.kernels.megopolis.megopolis import (
        BLOCK_VMEM_BYTES,
        DEFAULT_VMEM_LIMIT_BYTES,
        MAX_TILES_PER_STEP,
        block_vmem_bytes,
        step_vmem_limit_bytes,
        tiles_per_step,
    )

    n = 1 << 20
    assert tiles_per_step(n // TILE, 1, 4) == MAX_TILES_PER_STEP
    for odd in (1, 3, 1023):
        assert tiles_per_step(odd, 1, 4) == 1
    n_wide = 1 << 22 if entry == "apply" else n
    wide = tiles_per_step(n_wide // TILE, 8, 4)
    assert 1 < wide < MAX_TILES_PER_STEP
    assert block_vmem_bytes(wide, 8, 4) <= BLOCK_VMEM_BYTES < block_vmem_bytes(2 * wide, 8, 4)
    if entry == "step":
        lw = jax.ShapeDtypeStruct((n // 128, 128), jnp.float32)
        limit = step_vmem_limit_bytes(lw, block_vmem_bytes(MAX_TILES_PER_STEP, 1, 4))
        assert DEFAULT_VMEM_LIMIT_BYTES < limit <= 2 * DEFAULT_VMEM_LIMIT_BYTES
        small = jax.ShapeDtypeStruct((8, 128), jnp.float32)
        assert step_vmem_limit_bytes(small, block_vmem_bytes(1, 1, 4)) == DEFAULT_VMEM_LIMIT_BYTES


@pytest.mark.parametrize("entry", ("apply", "step"))
def test_blocked_kernel_footprint_within_budget(entry):
    """The analysis pass prices both blocked launches at N = 2^20 from
    their traced blocks, under the static VMEM budget."""
    from repro.analysis import kernel_footprints
    from repro.kernels.common import vmem_budget_bytes

    r = _build("megopolis", "pallas_interpret")
    n = 1 << 20
    key = jax.ShapeDtypeStruct((2,), jnp.uint32)
    w = jax.ShapeDtypeStruct((n,), jnp.float32)
    if entry == "apply":
        jaxpr = jax.make_jaxpr(r.apply)(key, w, w)
    else:
        jaxpr = jax.make_jaxpr(lambda k, lw, p: r.step(k, lw, p, 0.5))(key, w, w)
    (fp,) = kernel_footprints(jaxpr)
    assert fp.grid[0] == 1 and fp.grid[1] < n // TILE  # blocked: fewer grid steps than tiles
    assert fp.vmem_bytes <= vmem_budget_bytes()
