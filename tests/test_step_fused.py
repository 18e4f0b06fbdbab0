"""Fused SMC step (``Resampler.step``) quality gate (DESIGN.md §12).

Contract under test, over the FULL family × backend matrix:

  1. **composition parity** — ``step(key, log_w, p, thr)`` is bit-identical
     to the normalise → ESS → branch → ``apply`` composition on the SAME
     backend, for single and explicit-key rows forms, at thresholds that
     take both branches;
  2. **no-op branch** — when ``ess_norm >= thr`` the particles come back
     bit-identical, ancestors are the identity permutation, the logZ
     increment is zero, and the output does not depend on the key (the key
     is consumed, but only the taken branch's draws are selected);
  3. **threshold edges** — ``thr=0.0`` never fires (strict ``<``),
     ``thr=1.0`` does not fire on uniform weights (ess_norm == 1 exactly),
     and a population EXACTLY at threshold does not fire;
  4. **degenerate weights** (hypothesis, pinned-grid fallback) — all mass
     on one particle, all-equal, -inf-except-one and subnormal log-weights
     produce finite normalised weights / ESS / increment on every backend,
     with step ≡ composition throughout;
  5. **single launch** — on the pallas backend the WHOLE step traces to
     exactly ONE ``pallas_call`` for every family (the tentpole claim);
  6. **consumers** — the filter/AIS/decode resample paths contain no
     ``lax.cond`` around the resampler and ride ``step``/``step_rows``;
     the analytic memory model says fused < composed.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis import count_pallas_calls
from repro.core.metrics import (
    degenerate_log_weights,
    effective_sample_size,
    log_mean_weight,
    log_weights_from_linear,
    max_normalised_weight,
    normalise_log_weights,
    unique_ancestor_count,
)
from repro.obs.stats import stats_from_vector
from repro.core.resamplers.batched import split_batch_keys
from repro.core.spec import spec_for_backend
from repro.kernels.common import (
    MAX_VMEM_STATE,
    STATE_PLANE_TILE,
    TILE,
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


def _build(name, backend, num_iters=ITERS, plane_dtype="float32"):
    return spec_for_backend(name, backend, num_iters=num_iters,
                            max_iters=MAX_ITERS, plane_dtype=plane_dtype).build()


@pytest.fixture(scope="module")
def lw_spread():
    """Concentrated log-weights: ess_norm ≈ 0.07, so mid thresholds fire."""
    return jax.random.normal(jax.random.PRNGKey(11), (N,)) * 2.0


@pytest.fixture(scope="module")
def lw_flat():
    """Near-uniform log-weights: ess_norm ≈ 1, so mid thresholds do NOT fire."""
    return jax.random.normal(jax.random.PRNGKey(12), (N,)) * 0.01


@pytest.fixture(scope="module")
def lw_bank():
    return jax.random.normal(jax.random.PRNGKey(13), (BATCH, N)) * 2.0


@pytest.fixture(scope="module")
def p_single():
    return jax.random.normal(jax.random.PRNGKey(14), (N, 4))


@pytest.fixture(scope="module")
def p_bank():
    return jax.random.normal(jax.random.PRNGKey(15), (BATCH, N, 4))


def _assert_equal(a, b):
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def _assert_tree_equal(got, exp):
    """Bit-exact over every leaf (particles, ancestors, StepStats)."""
    for g, e in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(exp)):
        _assert_equal(g, e)


def _composed_step(r, key, log_w, particles, thr):
    """The oracle: normalise → ESS → branch → apply, from shared metrics
    helpers and the SAME backend's fused apply — what ``step`` must equal
    bit for bit, including the §15 ``StepStats`` record.  Inputs land on
    the plane-dtype grid first (DESIGN.md §14, identity at f32);
    ``r.apply`` re-lands the normalised weights on the same grid,
    matching the fused step's in-kernel requantise."""
    log_w = r.quantise(log_w)
    particles = r.quantise(particles)
    n = log_w.shape[-1]
    ess_n = effective_sample_size(log_w) / jnp.float32(n)
    do = ess_n < thr
    w = normalise_log_weights(log_w)
    p_res, a_res = r.apply(key, w, particles)
    ancestors = jnp.where(do, a_res, jnp.arange(n, dtype=jnp.int32))
    p_out = jnp.where(do, p_res, particles)
    incr = jnp.where(do, log_mean_weight(log_w), jnp.float32(0.0))
    stats4 = jnp.stack([
        ess_n,
        incr,
        jnp.where(do, jnp.float32(1.0), jnp.float32(0.0)),
        max_normalised_weight(log_w),
    ])
    return p_out, ancestors, stats_from_vector(
        stats4, unique_ancestor_count(ancestors), degenerate_log_weights(log_w)
    )


# ------------------------------------------------- 1. composition parity
@pytest.mark.parametrize("plane_dtype", PLANE_DTYPES_TESTED)
@pytest.mark.parametrize("thr", (0.0, 0.7, 2.0))
@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("name", FAMILIES)
def test_step_single_matches_composition(name, backend, thr, plane_dtype,
                                         lw_spread, p_single, base_key):
    r = _build(name, backend, plane_dtype=plane_dtype)
    exp = _composed_step(r, base_key, lw_spread, p_single, thr)
    got = r.step(base_key, lw_spread, p_single, thr)
    _assert_tree_equal(got, exp)


@pytest.mark.parametrize("plane_dtype", PLANE_DTYPES_TESTED)
@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("name", FAMILIES)
def test_step_rows_matches_single(name, backend, plane_dtype, lw_bank, p_bank,
                                  base_key):
    """step_rows row b == step(keys[b], ...) — the filter-bank contract;
    each row takes its OWN branch."""
    r = _build(name, backend, plane_dtype=plane_dtype)
    keys = split_batch_keys(base_key, BATCH)
    got = r.step_rows(keys, lw_bank, p_bank, 0.7)
    for b in range(BATCH):
        exp = r.step(keys[b], lw_bank[b], p_bank[b], 0.7)
        for g, e in zip(jax.tree_util.tree_leaves(got),
                        jax.tree_util.tree_leaves(exp)):
            _assert_equal(g[b], e)


@pytest.mark.parametrize("name", ("megopolis", "metropolis", "residual"))
def test_step_rows_mixed_branches(name, p_bank, base_key):
    """A bank whose rows straddle the threshold: concentrated rows resample,
    the flat row comes back identity — in the SAME launch."""
    lw = jnp.stack([
        jax.random.normal(jax.random.PRNGKey(31), (N,)) * 2.0,
        jax.random.normal(jax.random.PRNGKey(32), (N,)) * 0.01,
        jax.random.normal(jax.random.PRNGKey(33), (N,)) * 2.0,
    ])
    r = _build(name, "pallas_interpret")
    keys = split_batch_keys(base_key, BATCH)
    p_out, anc, stats = r.step_rows(keys, lw, p_bank, 0.7)
    fired = np.asarray(stats.ess_norm) < 0.7
    assert list(fired) == [True, False, True]
    assert list(np.asarray(stats.resampled)) == [1.0, 0.0, 1.0]
    _assert_equal(anc[1], jnp.arange(N, dtype=jnp.int32))
    _assert_equal(p_out[1], p_bank[1])
    assert float(stats.log_evidence_incr[1]) == 0.0
    assert int(stats.survivors[1]) == N  # identity ancestors: all survive
    assert not np.array_equal(np.asarray(anc[0]), np.arange(N))
    assert int(stats.survivors[0]) < N  # a real resample drops particles


# ------------------------------------------------------- 2. no-op branch
@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("name", FAMILIES)
def test_step_noop_branch(name, backend, lw_flat, p_single, base_key):
    """ess_norm >= thr: particles bit-identical, identity ancestors,
    incr == 0, and the result is key-independent (the key is consumed but
    the untaken branch's draws are discarded)."""
    r = _build(name, backend)
    p_out, anc, stats = r.step(base_key, lw_flat, p_single, 0.5)
    assert float(stats.ess_norm) >= 0.5
    _assert_equal(p_out, p_single)
    _assert_equal(anc, jnp.arange(N, dtype=jnp.int32))
    assert float(stats.log_evidence_incr) == 0.0
    assert float(stats.resampled) == 0.0
    assert int(stats.survivors) == N
    other = r.step(jax.random.PRNGKey(999), lw_flat, p_single, 0.5)
    _assert_tree_equal(other, (p_out, anc, stats))


# ---------------------------------------------------- 3. threshold edges
@pytest.mark.parametrize("backend", ("reference", "pallas_interpret"))
@pytest.mark.parametrize("name", ("megopolis", "rejection", "systematic"))
def test_step_threshold_edges(name, backend, lw_spread, p_single, base_key):
    r = _build(name, backend)
    # thr = 0.0 never fires: ess_norm > 0 and the trigger is strict <
    p_out, anc, stats = r.step(base_key, lw_spread, p_single, 0.0)
    _assert_equal(p_out, p_single)
    _assert_equal(anc, jnp.arange(N, dtype=jnp.int32))
    assert float(stats.log_evidence_incr) == 0.0
    # thr = 1.0 on exactly-uniform weights: ess_norm == 1.0 exactly (f32
    # integer sums are exact at this N), strict < does not fire
    lw_uniform = jnp.zeros((N,), jnp.float32)
    p_out, anc, stats = r.step(base_key, lw_uniform, p_single, 1.0)
    assert float(stats.ess_norm) == 1.0
    _assert_equal(p_out, p_single)
    _assert_equal(anc, jnp.arange(N, dtype=jnp.int32))
    # exactly AT threshold: strict < does not fire
    ess_thr = effective_sample_size(lw_spread) / jnp.float32(N)
    p_out, anc, _ = r.step(base_key, lw_spread, p_single, ess_thr)
    _assert_equal(p_out, p_single)
    # nudge one ulp above: fires
    above = jnp.nextafter(ess_thr, jnp.float32(2.0))
    _, anc_fire, stats_fire = r.step(base_key, lw_spread, p_single, above)
    assert not np.array_equal(np.asarray(anc_fire), np.arange(N))
    assert float(stats_fire.log_evidence_incr) != 0.0
    assert float(stats_fire.resampled) == 1.0


# ------------------------------------------------- 'auto' num_iters rows
@pytest.mark.parametrize("name", ("megopolis", "metropolis", "metropolis_c1"))
def test_step_auto_iters_rows(name, lw_bank, p_bank, base_key):
    """num_iters='auto' resolves eq. (3) PER ROW from each row's normalised
    weights; rows stay bit-identical to the single 'auto' step."""
    r = _build(name, "pallas_interpret", num_iters="auto")
    keys = split_batch_keys(base_key, BATCH)
    got = r.step_rows(keys, lw_bank, p_bank, 0.7)
    for b in range(BATCH):
        exp = r.step(keys[b], lw_bank[b], p_bank[b], 0.7)
        for g, e in zip(jax.tree_util.tree_leaves(got),
                        jax.tree_util.tree_leaves(exp)):
            _assert_equal(g[b], e)


# ------------------------------------------- 4. degenerate-weight safety
def _degenerate_cases(n):
    one_hot = jnp.full((n,), -jnp.inf).at[n // 3].set(0.0)
    return {
        "all_mass_on_one": jnp.full((n,), -100.0).at[7].set(0.0),
        "all_equal": jnp.full((n,), -3.5),
        "inf_except_one": one_hot,
        "subnormal": jnp.full((n,), -1e-40),
    }


@pytest.mark.parametrize("case", sorted(_degenerate_cases(4)))
def test_metrics_degenerate_weights_finite(case):
    """The shared normalise/ESS helpers directly: every degenerate pattern
    yields finite normalised weights, ESS in [1, N], finite log-mean."""
    lw = _degenerate_cases(N)[case]
    w = normalise_log_weights(lw)
    assert bool(jnp.all(jnp.isfinite(w)))
    assert float(jnp.max(w)) == 1.0  # the argmax weight survives exactly
    ess = effective_sample_size(lw)
    assert bool(jnp.isfinite(ess))
    assert 1.0 - 1e-4 <= float(ess) <= N * (1 + 1e-6)
    assert bool(jnp.isfinite(log_mean_weight(lw)))


def test_log_weights_from_linear_guards_zero():
    """The centralised linear→log guard: zero and subnormal weights floor
    at 1e-30 (f32 normal range) instead of producing -inf / flushed logs."""
    w = jnp.array([0.0, 1e-38, 1.0], jnp.float32)
    lw = log_weights_from_linear(w)
    assert bool(jnp.all(jnp.isfinite(lw)))
    assert float(lw[2]) == 0.0
    ess = effective_sample_size(lw)
    assert bool(jnp.isfinite(ess))


def _check_degenerate_step(name, backend, case, thr):
    lw = _degenerate_cases(N)[case]
    p = jax.random.normal(jax.random.PRNGKey(41), (N, 2))
    r = _build(name, backend)
    key = jax.random.PRNGKey(42)
    p_out, anc, stats = r.step(key, lw, p, thr)
    assert bool(jnp.isfinite(stats.ess_norm))
    assert bool(jnp.isfinite(stats.log_evidence_incr))
    assert bool(jnp.isfinite(stats.max_weight))
    assert bool(jnp.all(jnp.isfinite(p_out)))
    exp = _composed_step(r, key, lw, p, thr)
    _assert_tree_equal((p_out, anc, stats), exp)


_DEGEN_FAMILIES = ("megopolis", "metropolis", "rejection", "systematic", "residual")


# The §16 COLLAPSED signatures: non-finite max, so the uniform fallback
# engages (kernel-side deg latch ≡ host normalise_log_weights fallback);
# the fused step must STILL match the composed oracle bit for bit,
# including a truthful non-finite evidence increment when the resample
# fires, and must set StepStats.degenerate.
def _collapsed_cases(n):
    return {
        "all_nan": jnp.full((n,), jnp.nan),
        "all_neg_inf": jnp.full((n,), -jnp.inf),
        "pos_inf_entry": jnp.zeros((n,)).at[11].set(jnp.inf),
    }


@pytest.mark.parametrize("case", sorted(_collapsed_cases(4)))
@pytest.mark.parametrize("thr", (0.5, 2.0))
@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("name", _DEGEN_FAMILIES)
def test_step_collapsed_banks_match_composition(name, backend, thr, case,
                                                base_key):
    lw = _collapsed_cases(N)[case]
    p = jax.random.normal(jax.random.PRNGKey(43), (N, 2))
    r = _build(name, backend)
    got = r.step(base_key, lw, p, thr)
    exp = _composed_step(r, base_key, lw, p, thr)
    _assert_tree_equal(got, exp)
    _, anc, stats = got
    assert bool(jnp.asarray(stats.degenerate))
    # the fallback bank is uniform: ESS pegs at 1, max weight at 1/N
    assert float(stats.ess_norm) == 1.0
    assert float(stats.max_weight) == np.float32(1.0 / N)
    assert bool(jnp.all((anc >= 0) & (anc < N)))


@pytest.mark.parametrize("case", sorted(_collapsed_cases(4)))
@pytest.mark.parametrize("name", ("megopolis", "systematic"))
def test_step_collapsed_banks_bf16_plane(name, case, base_key):
    """The §14 compressed plane composes with the §16 fallback: the
    substitution precedes the requantise in kernel and host alike."""
    lw = _collapsed_cases(N)[case]
    p = jax.random.normal(jax.random.PRNGKey(44), (N, 2))
    r = _build(name, "pallas_interpret", plane_dtype="bfloat16")
    got = r.step(base_key, lw, p, 2.0)
    exp = _composed_step(r, base_key, lw, p, 2.0)
    _assert_tree_equal(got, exp)
    assert bool(jnp.asarray(got[2].degenerate))

try:
    from hypothesis import given, settings, strategies as st

    @given(
        name=st.sampled_from(_DEGEN_FAMILIES),
        backend=st.sampled_from(BACKENDS),
        case=st.sampled_from(sorted(_degenerate_cases(4))),
        thr=st.sampled_from([0.0, 0.5, 1.0]),
    )
    @settings(max_examples=25, deadline=None)
    def test_step_degenerate_weights(name, backend, case, thr):
        _check_degenerate_step(name, backend, case, thr)

except ImportError:
    # hypothesis absent (CI installs it): pinned grid instead.
    @pytest.mark.parametrize("case", sorted(_degenerate_cases(4)))
    @pytest.mark.parametrize("backend", BACKENDS)
    @pytest.mark.parametrize("name", _DEGEN_FAMILIES)
    def test_step_degenerate_weights(name, backend, case):
        _check_degenerate_step(name, backend, case, 0.5)


# ------------------------------------------------------ 5. single launch
@pytest.mark.parametrize("plane_dtype", PLANE_DTYPES_TESTED)
@pytest.mark.parametrize("name", FAMILIES)
def test_step_is_single_launch(name, plane_dtype, lw_spread, p_single, base_key):
    """THE tentpole gate: on the pallas backend the whole reweight → ESS →
    conditional resample → state copy step traces to exactly ONE
    pallas_call — including the prefix-sum family, whose composed apply
    alone is 2 launches (4 for residual) plus host glue.  Compression
    narrows the tiles, never adds a launch (DESIGN.md §14)."""
    r = _build(name, "pallas_interpret", plane_dtype=plane_dtype)
    jaxpr = jax.make_jaxpr(lambda k, lw, p: r.step(k, lw, p, 0.5))(
        base_key, lw_spread, p_single
    )
    assert count_pallas_calls(jaxpr) == 1


@pytest.mark.parametrize("name", ("megopolis", "metropolis", "rejection"))
def test_step_rows_is_single_launch(name, lw_bank, p_bank, base_key):
    """The bank form on the leading-batch-grid families is ONE launch too."""
    r = _build(name, "pallas_interpret")
    keys = split_batch_keys(base_key, BATCH)
    jaxpr = jax.make_jaxpr(lambda k, lw, p: r.step_rows(k, lw, p, 0.5))(
        keys, lw_bank, p_bank
    )
    assert count_pallas_calls(jaxpr) == 1


# ------------------------------------------------- validation + residency
@pytest.mark.parametrize("backend", ("reference", "pallas_interpret"))
def test_step_rows_rejects_short_key_array(backend, lw_bank, p_bank, base_key):
    r = _build("megopolis", backend)
    keys = split_batch_keys(base_key, BATCH - 1)
    with pytest.raises(ValueError, match="one key per row"):
        r.step_rows(keys, lw_bank, p_bank, 0.5)


def test_step_state_residency_cap(base_key):
    """Families with a resident plane stack refuse a state past the budget;
    Megopolis carries state by value and steps it bit-exactly."""
    d = MAX_VMEM_STATE // N // STATE_PLANE_TILE * STATE_PLANE_TILE + STATE_PLANE_TILE
    p = jax.random.normal(jax.random.PRNGKey(3), (N, d))
    lw = jax.random.normal(jax.random.PRNGKey(4), (N,)) * 2.0
    with pytest.raises(ValueError, match="VMEM"):
        _build("metropolis", "pallas_interpret").step(base_key, lw, p, 0.5)
    r = _build("megopolis", "pallas_interpret")
    p_out, anc, stats = r.step(base_key, lw, p, 0.5)
    assert float(stats.resampled) == 1.0
    _assert_equal(p_out, jnp.take(p, anc, axis=0))


# ----------------------------------------------------------- 6. consumers
@pytest.mark.parametrize(
    "consumer",
    (
        "ais.run_smc_sampler",
        "ais.run_smc_sampler_bank",
        "pf.step_conditional",
        "pf.run_filter_bank",
    ),
)
def test_consumer_resample_paths_use_fused_step(consumer):
    """No host-side cond around the resampler, no ancestor round-trip, and
    exactly ONE launch (which only the fused step/step_rows path can
    achieve): checked on the consumers' traced jaxprs by the DESIGN.md §13
    analyzer, not by grepping their source."""
    from repro.analysis import audit_consumers

    (rep,) = audit_consumers(names=[consumer])
    assert rep.ok, rep.violations
    assert rep.launches == 1
    assert rep.cond_count == 0
    assert rep.tainted_gathers == 0


def test_decode_resample_path_is_fused():
    """smc_decode: one launch, no host cond; its cache gathers ARE
    ancestor-indexed (mixed-dtype KV pytree) — allowed by its contract and
    priced, not forbidden."""
    from repro.analysis import audit_consumers

    (rep,) = audit_consumers(names=["smc.decode"])
    assert rep.ok, rep.violations
    assert rep.launches == 1 and rep.cond_count == 0
    assert rep.tainted_gathers > 0


def test_memmodel_fused_step_beats_composed():
    from repro.launch.memmodel import smc_step_bytes

    for n in (1 << 10, 1 << 16, 1 << 20):
        for d in (1, 4, 32):
            fused = smc_step_bytes(n, d, fused=True)
            composed = smc_step_bytes(n, d, fused=False)
            assert fused["total"] < composed["total"]
            # the normalised-weight buffer + the ancestor vector
            assert composed["total"] - fused["total"] == n * 8


def test_conditional_filter_step_matches_manual_replay(base_key):
    """End-to-end: a conditional-SIR ParticleFilter on the pallas backend
    steps through the fused path and equals a manual replay through the
    composed normalise → ESS → branch → apply arithmetic."""
    from repro.core.spec import MegopolisSpec
    from repro.pf import ParticleFilter, ungm

    pf = ParticleFilter(
        model=ungm(),
        num_particles=TILE,
        resampler=MegopolisSpec(num_iters=ITERS, segment=1024,
                                backend="pallas_interpret"),
        ess_threshold=0.5,
    )
    particles = pf.model.init(jax.random.PRNGKey(51), TILE)
    log_w0 = jnp.zeros((TILE,), jnp.float32)
    z, t = jnp.float32(0.3), jnp.float32(1.0)
    x_bar, log_w1, est, stats = pf.step_conditional(base_key, particles, log_w0, z, t)
    # manual replay
    k_pred, k_res = jax.random.split(base_key)
    x = pf.model.transition(k_pred, particles, t)
    lw = log_w0 + log_weights_from_linear(pf.model.likelihood(z, x, t))
    exp = _composed_step(pf._built, k_res, lw, x, 0.5)
    _assert_equal(x_bar, exp[0])
    _assert_tree_equal(stats, exp[2])
    wn = normalise_log_weights(lw)
    _assert_equal(est, jnp.sum(wn * x) / jnp.sum(wn))
    fired = bool(stats.ess_norm < 0.5)
    _assert_equal(log_w1, jnp.zeros_like(lw) if fired else lw)


# ------------------------------- 7. blocked grid: G tiles per grid step
@pytest.mark.parametrize("state_dim", (1, 4))
@pytest.mark.parametrize("plane_dtype", PLANE_DTYPES_TESTED)
@pytest.mark.parametrize("table", ("r0", "rlast", "wrap"))
@pytest.mark.parametrize("num_tiles", (1, 3, 12, 16))
def test_blocked_step_matches_composition(num_tiles, table, plane_dtype,
                                          state_dim):
    """The fused step kernel, called directly at tile counts that give
    G = 1, 1, 4 and 16 (tables and inputs of the apply parity cases),
    equals normalise → ESS → branch → ``megopolis_ref`` bit for bit, stats
    row included, on the branch that resamples and on the one that holds."""
    from test_fused_apply import BLOCKED_ITERS, blocked_inputs, blocked_offsets

    from repro.kernels.common import compress_plane, quantise_plane
    from repro.kernels.megopolis.megopolis import megopolis_pallas_step, tiles_per_step
    from repro.kernels.megopolis.ref import megopolis_ref

    n = num_tiles * TILE
    _, p, planes, state_shape = blocked_inputs(num_tiles, plane_dtype, state_dim,
                                               jax.random.PRNGKey(num_tiles))
    lw = quantise_plane(jax.random.normal(jax.random.PRNGKey(50 + num_tiles), (n,))
                        * 2.0, plane_dtype)
    lw2 = compress_plane(lw.reshape(n // 128, 128), plane_dtype)
    g = tiles_per_step(num_tiles, planes.shape[0], lw2.dtype.itemsize)
    offsets = blocked_offsets(table, n, g)
    seed = jnp.array([77 + num_tiles], jnp.uint32)

    @jax.jit  # as every consumer runs the composition: XLA folds the
    def composed(lw):  # division by the constant N as it folds the kernel's
        w = quantise_plane(normalise_log_weights(lw), plane_dtype)
        return (effective_sample_size(lw) / jnp.float32(n), log_mean_weight(lw),
                max_normalised_weight(lw),
                megopolis_ref(w, offsets, seed, num_iters=BLOCKED_ITERS))

    ess_n, incr, maxw, selected = composed(lw)
    for thr, fires in ((0.7, True), (0.0, False)):
        k2, out, stats = megopolis_pallas_step(
            lw2, planes, offsets, seed, jnp.array([thr], jnp.float32),
            num_iters=BLOCKED_ITERS, interpret=True)
        assert bool(ess_n < thr) == fires
        ancestors = selected if fires else jnp.arange(n, dtype=jnp.int32)
        _assert_equal(k2.reshape(n), ancestors)
        _assert_equal(unpack_state_planes(out.astype(p.dtype), state_shape),
                      jnp.take(p, ancestors, axis=0))
        _assert_equal(stats, jnp.stack([
            ess_n, incr if fires else jnp.float32(0.0), jnp.float32(fires), maxw,
        ]))
