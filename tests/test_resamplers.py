"""Functional correctness of every resampler: valid outputs, determinism,
degenerate-weight behaviour, and the Alg.8 == searchsorted equivalence."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import get_resampler, list_resamplers, select_iterations
from repro.core.metrics import offspring_counts
from repro.core.resamplers.megopolis import megopolis, megopolis_indices

ALL = list_resamplers()
N = 512
B = 24


def _weights(key, n=N):
    return jax.random.uniform(key, (n,)) + 1e-3


@pytest.mark.parametrize("name", ALL)
def test_ancestors_valid_and_deterministic(name, base_key):
    w = _weights(jax.random.fold_in(base_key, 1))
    fn = get_resampler(name)
    a1 = fn(jax.random.fold_in(base_key, 2), w, B)
    a2 = fn(jax.random.fold_in(base_key, 2), w, B)
    assert a1.shape == (N,)
    assert a1.dtype == jnp.int32
    assert bool(jnp.all((a1 >= 0) & (a1 < N)))
    np.testing.assert_array_equal(np.asarray(a1), np.asarray(a2))


@pytest.mark.parametrize("name", ALL)
def test_total_offspring_is_n(name, base_key):
    w = _weights(jax.random.fold_in(base_key, 3))
    a = get_resampler(name)(jax.random.fold_in(base_key, 4), w, B)
    assert int(offspring_counts(a, N).sum()) == N


@pytest.mark.parametrize("name", [n for n in ALL if n not in ("metropolis_c1", "rejection")])
def test_degenerate_single_heavy_particle(name, base_key):
    """One particle holds ~all weight -> nearly all ancestors point at it."""
    w = jnp.full((N,), 1e-7).at[137].set(1.0)
    num_iters = int(select_iterations(w, 0.01))
    a = get_resampler(name)(jax.random.fold_in(base_key, 5), w, num_iters)
    frac = float(jnp.mean(a == 137))
    assert frac > 0.95, f"{name}: only {frac:.2%} selected the heavy particle"


def test_rejection_degenerate_needs_geometric_tail(base_key):
    """Rejection's per-particle iteration count is geometric with mean
    max(w)/E(w) ~ N here — the variable-execution-time weakness the paper
    cites (§1).  With a cap ~8x the mean it must still converge."""
    from repro.core import rejection

    w = jnp.full((N,), 1e-7).at[137].set(1.0)
    a = rejection(jax.random.fold_in(base_key, 5), w, 0, max_iters=8 * N)
    assert float(jnp.mean(a == 137)) > 0.95


def test_c1_partition_bias_vs_megopolis(base_key):
    """Paper Fig. 6: C1 (PS128) is badly biased under degeneracy — warps whose
    fixed partition misses the heavy particle can never select it, unlike
    Megopolis which exposes every particle each iteration."""
    from repro.core import megopolis, metropolis_c1

    w = jnp.full((N,), 1e-7).at[137].set(1.0)
    num_iters = int(select_iterations(w, 0.01))
    frac_c1, frac_mego = 0.0, 0.0
    trials = 8
    for t in range(trials):
        k = jax.random.fold_in(base_key, 300 + t)
        frac_c1 += float(jnp.mean(metropolis_c1(k, w, num_iters) == 137)) / trials
        frac_mego += float(jnp.mean(megopolis(k, w, num_iters) == 137)) / trials
    assert frac_mego > 0.95
    assert frac_c1 < 0.5 * frac_mego, (frac_c1, frac_mego)


@pytest.mark.parametrize("name", ALL)
def test_uniform_weights_low_selfmove(name, base_key):
    """With uniform weights every ancestor choice is accepted; output must
    still be a valid resample (jit-compatible too)."""
    w = jnp.ones((N,))
    fn = jax.jit(get_resampler(name), static_argnums=2)
    a = fn(jax.random.fold_in(base_key, 6), w, B)
    assert bool(jnp.all((a >= 0) & (a < N)))


def test_improved_systematic_equals_searchsorted(base_key):
    from repro.core import improved_systematic, systematic

    for trial in range(5):
        k = jax.random.fold_in(base_key, 100 + trial)
        w = _weights(k, 257)  # non-power-of-2 on purpose
        a_ref = systematic(k, w)
        a_alg8 = improved_systematic(k, w)
        np.testing.assert_array_equal(np.asarray(a_ref), np.asarray(a_alg8))


def test_megopolis_index_map_is_bijection():
    """Per-iteration i->j must be a bijection for ANY offset/segment (the
    heart of Proposition 1's variance argument)."""
    for n, seg in [(256, 32), (256, 64), (1024, 128), (96, 32)]:
        i = jnp.arange(n)
        for o in [0, 1, 31, 32, 33, n - 1, n // 2]:
            j = np.asarray(megopolis_indices(i, o, seg, n))
            if n % seg == 0:
                assert len(set(j.tolist())) == n, (n, seg, o)
            assert ((j >= 0) & (j < n)).all()


def test_megopolis_uniform_exposure():
    """Over many offsets, each particle i must see ~uniform j (bias arg)."""
    n, seg = 128, 32
    i = jnp.arange(n)
    counts = np.zeros((n,), np.int64)
    for o in range(n):  # exhaustive offsets
        j = np.asarray(megopolis_indices(i, o, seg, n))
        counts += np.bincount(j, minlength=n)
    # exhaustive o in [0,n) must expose every j exactly n times
    assert (counts == n).all()


def test_select_iterations_matches_closed_form():
    from repro.core.iterations import gaussian_weight_iterations

    # eq. 3 with the eq. 12 family: E(w)/max(w) = exp(-y^2/4)/sqrt(2)
    for y, eps in [(0.0, 0.01), (2.0, 0.01), (4.0, 0.1)]:
        b = gaussian_weight_iterations(y, eps)
        assert b >= 1
    assert gaussian_weight_iterations(0.0, 0.01) <= 10
    assert gaussian_weight_iterations(4.0, 0.01) > gaussian_weight_iterations(1.0, 0.01)


def test_rejection_unbiased_mean(base_key):
    from repro.core import rejection

    w = _weights(base_key, 256)
    counts = np.zeros(256)
    for t in range(64):
        a = rejection(jax.random.fold_in(base_key, 200 + t), w, 0)
        counts += np.bincount(np.asarray(a), minlength=256)
    emp = counts / counts.sum()
    tgt = np.asarray(w / w.sum())
    assert np.abs(emp - tgt).max() < 0.02


def _megopolis_by_gather(key, weights, num_iters, segment):
    """Alg. 5 as first written: both weights gathered by index each
    iteration — the reference the rotating, value-carrying form must equal."""
    n = weights.shape[0]
    key_off, key_u = jax.random.split(key)
    offsets = jax.random.randint(key_off, (num_iters,), 0, n)
    i = jnp.arange(n, dtype=jnp.int32)

    def body(b, k):
        j = megopolis_indices(i, offsets[b], segment, n).astype(jnp.int32)
        u = jax.random.uniform(jax.random.fold_in(key_u, b), (n,), weights.dtype)
        return jnp.where(u * weights[k] <= weights[j], j, k)

    return jax.lax.fori_loop(0, num_iters, body, i)


@pytest.mark.parametrize(
    "n,segment",
    [(512, 32), (2048, 1024), (512, 1), (500, 32), (1000, 1024)],
    ids=["rotate-32", "rotate-1024", "rotate-1", "gather-32", "gather-1024"],
)
def test_megopolis_matches_gather_form(n, segment, base_key):
    """Where N is whole segments the comparison weights come from two rolls
    (else a gather) and w[k] rides a carry; the ancestors are bit-identical
    to the gather-by-index form either way."""
    w = _weights(jax.random.fold_in(base_key, 40), n) ** 4
    key = jax.random.fold_in(base_key, 41)
    got = jax.jit(lambda k, w: megopolis(k, w, B, segment=segment))(key, w)
    want = jax.jit(lambda k, w: _megopolis_by_gather(k, w, B, segment))(key, w)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
