"""Pallas kernel validation (interpret mode): bit-exact vs ref.py oracles,
shape/dtype sweeps, and statistical quality parity with repro.core."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import megopolis as core_megopolis
from repro.core import select_iterations
from repro.core.metrics import mse, offspring_counts
from repro.core.resamplers.batched import split_batch_keys
from repro.core.weightgen import gaussian_weights
from repro.kernels import megopolis_tpu, metropolis_tpu, prefix_sum_tpu
from repro.kernels.common import TILE, flat_roll, hash_uniform, key_to_seed
from repro.kernels.megopolis.megopolis import megopolis_pallas_fused
from repro.kernels.megopolis.ref import megopolis_ref
from repro.kernels.metropolis.c1c2 import metropolis_c1_pallas, metropolis_c2_pallas
from repro.kernels.metropolis.metropolis import metropolis_pallas
from repro.kernels.metropolis.ops import metropolis_tpu_batch
from repro.kernels.metropolis.ref import metropolis_c1_ref, metropolis_c2_ref, metropolis_ref
from repro.kernels.prefix_sum.ops import prefix_resample_tpu, searchsorted_tpu
from repro.kernels.prefix_sum.ref import prefix_resample_ref, prefix_sum_ref, prefix_sum_tiled_ref
from repro.kernels.rejection.ops import rejection_tpu, rejection_tpu_batch
from repro.kernels.rejection.ref import rejection_ref


# ---------------------------------------------------------------- flat_roll
@pytest.mark.parametrize("rows", [8, 16])
@pytest.mark.parametrize("shift", [0, 1, 127, 128, 129, 1000, 1023, 1024])
def test_flat_roll_matches_numpy(rows, shift):
    x = jnp.arange(rows * 128, dtype=jnp.float32).reshape(rows, 128)
    got = np.asarray(flat_roll(x, shift)).reshape(-1)
    want = np.roll(np.asarray(x).reshape(-1), -shift)
    np.testing.assert_array_equal(got, want)


def test_hash_uniform_statistics():
    """The stateless RNG must be uniform enough for accept/reject tests."""
    i = jnp.arange(1 << 16)
    u = np.asarray(hash_uniform(jnp.uint32(123), i, 7))
    assert 0.0 <= u.min() and u.max() < 1.0
    assert abs(u.mean() - 0.5) < 0.01
    assert abs(np.quantile(u, 0.25) - 0.25) < 0.01
    # iteration decorrelation
    u2 = np.asarray(hash_uniform(jnp.uint32(123), i, 8))
    assert abs(np.corrcoef(u, u2)[0, 1]) < 0.02


# ---------------------------------------------------------- megopolis kernel
@pytest.mark.parametrize("n_tiles", [1, 2, 5])
@pytest.mark.parametrize("num_iters", [1, 7, 24])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_megopolis_kernel_matches_ref(n_tiles, num_iters, dtype, base_key):
    n = n_tiles * TILE
    w = (jax.random.uniform(jax.random.fold_in(base_key, n_tiles), (n,)) + 1e-3).astype(dtype)
    offsets = jax.random.randint(jax.random.fold_in(base_key, 77), (num_iters,), 0, n, jnp.int32)
    seed = key_to_seed(jax.random.fold_in(base_key, 99)).reshape(1)
    (got,) = megopolis_pallas_fused(
        w.reshape(1, -1, 128), None, offsets[None], seed, num_iters=num_iters,
        interpret=True, name="megopolis_pallas",
    )
    got = got.reshape(n)
    want = megopolis_ref(w, offsets, seed, num_iters=num_iters)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_megopolis_tpu_public_api(base_key):
    n = 4 * TILE
    w = jax.random.uniform(base_key, (n,)) + 1e-3
    a = megopolis_tpu(base_key, w, 16, interpret=True)
    assert a.shape == (n,) and a.dtype == jnp.int32
    assert bool(jnp.all((a >= 0) & (a < n)))
    with pytest.raises(ValueError):
        megopolis_tpu(base_key, w[: n - 3], 16, interpret=True)


def test_megopolis_kernel_quality_parity(base_key):
    """Kernel (SEG=1024, hash RNG) must match core megopolis (SEG=32,
    jax.random) in MSE on the paper's weight family — DESIGN.md §2."""
    n = 4 * TILE
    w = gaussian_weights(jax.random.PRNGKey(3), n, y=2.0)
    num_iters = int(select_iterations(w, 0.01))
    k_runs = 24
    o_kern, o_core = [], []
    for t in range(k_runs):
        kk = jax.random.fold_in(base_key, 500 + t)
        o_kern.append(np.asarray(offspring_counts(megopolis_tpu(kk, w, num_iters, interpret=True), n)))
        o_core.append(np.asarray(offspring_counts(core_megopolis(kk, w, num_iters), n)))
    m_kern = float(mse(jnp.asarray(np.stack(o_kern)), w)) / n
    m_core = float(mse(jnp.asarray(np.stack(o_core)), w)) / n
    assert abs(m_kern - m_core) < 0.4 * m_core, (m_kern, m_core)


# --------------------------------------------------------- metropolis kernel
@pytest.mark.parametrize("n_tiles", [1, 3])
@pytest.mark.parametrize("num_iters", [1, 16])
def test_metropolis_kernel_matches_ref(n_tiles, num_iters, base_key):
    n = n_tiles * TILE
    w = jax.random.uniform(jax.random.fold_in(base_key, 5), (n,)) + 1e-3
    seed = key_to_seed(jax.random.fold_in(base_key, 6)).reshape(1)
    got = metropolis_pallas(w.reshape(-1, 128), seed, num_iters=num_iters, interpret=True)
    want = metropolis_ref(w, seed, num_iters=num_iters)
    np.testing.assert_array_equal(np.asarray(got).reshape(-1), np.asarray(want))


def test_metropolis_tpu_vmem_cap(base_key):
    from repro.kernels.metropolis.ops import MAX_VMEM_PARTICLES

    w = jnp.ones((MAX_VMEM_PARTICLES + TILE,))
    with pytest.raises(ValueError, match="VMEM"):
        metropolis_tpu(base_key, w, 4, interpret=True)


@pytest.mark.parametrize("bsz", [1, 3])
def test_metropolis_batch_kernel_rows_match_single(bsz, base_key):
    """Row b of the [B, R, 128] launch == single kernel with split key b."""
    n = 2 * TILE
    w = jax.random.uniform(jax.random.fold_in(base_key, 21), (bsz, n)) + 1e-3
    got = metropolis_tpu_batch(base_key, w, 6, interpret=True)
    keys = split_batch_keys(base_key, bsz)
    want = jnp.stack([metropolis_tpu(keys[b], w[b], 6, interpret=True) for b in range(bsz)])
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# ------------------------------------------------------------ C1/C2 kernels
@pytest.mark.parametrize("n_tiles", [2, 4])
@pytest.mark.parametrize("num_iters", [1, 9])
def test_c1_kernel_matches_ref(n_tiles, num_iters, base_key):
    n = n_tiles * TILE
    w = jax.random.uniform(jax.random.fold_in(base_key, 31), (n,)) + 1e-3
    p = jax.random.randint(jax.random.fold_in(base_key, 32), (n_tiles,), 0, n_tiles, jnp.int32)
    seed = key_to_seed(jax.random.fold_in(base_key, 33)).reshape(1)
    got = metropolis_c1_pallas(
        w.reshape(-1, 128), p, seed, num_iters=num_iters, interpret=True
    ).reshape(n)
    want = metropolis_c1_ref(w, p, seed, num_iters=num_iters)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("n_tiles", [2, 4])
@pytest.mark.parametrize("num_iters", [1, 9])
def test_c2_kernel_matches_ref(n_tiles, num_iters, base_key):
    n = n_tiles * TILE
    w = jax.random.uniform(jax.random.fold_in(base_key, 41), (n,)) + 1e-3
    p = jax.random.randint(
        jax.random.fold_in(base_key, 42), (n_tiles * num_iters,), 0, n_tiles, jnp.int32
    )
    seed = key_to_seed(jax.random.fold_in(base_key, 43)).reshape(1)
    got = metropolis_c2_pallas(
        w.reshape(-1, 128), p, seed, num_iters=num_iters, interpret=True
    ).reshape(n)
    want = metropolis_c2_ref(w, p, seed, num_iters=num_iters)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_c1_proposals_stay_in_partition(base_key):
    """C1's defining constraint: every ancestor that moved lies in its
    tile's single partition tile (Alg. 3's locality, tile-granular)."""
    n = 4 * TILE
    n_tiles = 4
    w = jax.random.uniform(jax.random.fold_in(base_key, 51), (n,)) + 1e-3
    p = jax.random.randint(jax.random.fold_in(base_key, 52), (n_tiles,), 0, n_tiles, jnp.int32)
    seed = key_to_seed(jax.random.fold_in(base_key, 53)).reshape(1)
    a = np.asarray(
        metropolis_c1_pallas(w.reshape(-1, 128), p, seed, num_iters=16, interpret=True)
    ).reshape(n)
    i = np.arange(n)
    moved = a != i
    a_tile = a // TILE
    want_tile = np.asarray(p)[i // TILE]
    assert np.all(a_tile[moved] == want_tile[moved])


# ---------------------------------------------------------- rejection kernel
@pytest.mark.parametrize("n_tiles", [1, 3])
@pytest.mark.parametrize("max_iters", [1, 24])
def test_rejection_kernel_matches_ref(n_tiles, max_iters, base_key):
    n = n_tiles * TILE
    w = jax.random.uniform(jax.random.fold_in(base_key, 61), (n,)) + 1e-3
    got = rejection_tpu(base_key, w, max_iters=max_iters, interpret=True)
    want = rejection_ref(w, key_to_seed(base_key).reshape(1), max_iters=max_iters)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_rejection_batch_kernel_rows_match_single(base_key):
    n = 2 * TILE
    w = jax.random.uniform(jax.random.fold_in(base_key, 62), (3, n)) + 1e-3
    got = rejection_tpu_batch(base_key, w, max_iters=16, interpret=True)
    keys = split_batch_keys(base_key, 3)
    want = jnp.stack([rejection_tpu(keys[b], w[b], max_iters=16, interpret=True) for b in range(3)])
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_rejection_kernel_unbiased_in_expectation(base_key):
    """Offspring mean tracks N*w/sum(w) (rejection is unbiased; cap rarely
    binds at these weights)."""
    n = 2 * TILE
    w = gaussian_weights(jax.random.PRNGKey(5), n, y=1.0)
    runs = 24
    offs = []
    for t in range(runs):
        a = rejection_tpu(jax.random.fold_in(base_key, 600 + t), w, max_iters=64, interpret=True)
        offs.append(np.asarray(offspring_counts(a, n)))
    mean_off = np.stack(offs).mean(axis=0)
    want = n * np.asarray(w / jnp.sum(w))
    # noisy at K=24: check correlation + overall scale rather than per-particle
    assert np.corrcoef(mean_off, want)[0, 1] > 0.95
    np.testing.assert_allclose(mean_off.sum(), n, rtol=1e-6)


# --------------------------------------------------------- prefix sum kernel
@pytest.mark.parametrize("n_tiles", [1, 2, 7])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.float64])
def test_prefix_sum_matches_ref(n_tiles, dtype, base_key):
    n = n_tiles * TILE
    x = jax.random.uniform(base_key, (n,), jnp.float32).astype(dtype)
    got = prefix_sum_tpu(x, interpret=True)
    want = prefix_sum_ref(x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6)


def test_prefix_sum_tiled_ref_bit_exact(base_key):
    """The tiled oracle replays the kernel's carry arithmetic bit-for-bit
    (the plain-cumsum oracle is only close)."""
    n = 5 * TILE
    x = jax.random.uniform(base_key, (n,), jnp.float32)
    got = np.asarray(prefix_sum_tpu(x, interpret=True))
    want = np.asarray(prefix_sum_tiled_ref(x))
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("side", ["left", "right"])
def test_searchsorted_kernel_matches_jnp(side, base_key):
    n = 2 * TILE
    c = jnp.sort(jax.random.uniform(jax.random.fold_in(base_key, 71), (n,))) * 100.0
    u = jax.random.uniform(jax.random.fold_in(base_key, 72), (n,)) * 110.0 - 5.0
    got = searchsorted_tpu(c, u, side=side, interpret=True)
    want = jnp.minimum(jnp.searchsorted(c, u, side=side), n - 1).astype(jnp.int32)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize(
    "kind", ["multinomial", "systematic", "improved_systematic", "stratified", "residual"]
)
def test_prefix_resample_kernel_matches_ref(kind, base_key):
    n = 3 * TILE
    w = jax.random.uniform(jax.random.fold_in(base_key, 73), (n,)) + 1e-3
    got = prefix_resample_tpu(base_key, w, kind, interpret=True)
    want = prefix_resample_ref(base_key, w, kind=kind)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_prefix_resample_improved_systematic_equals_systematic(base_key):
    """Alg. 8's walk == searchsorted-left systematic; on the kernel lane the
    two kinds share the search kernel by construction — pin it."""
    n = 2 * TILE
    w = jax.random.uniform(jax.random.fold_in(base_key, 74), (n,)) + 1e-3
    a = prefix_resample_tpu(base_key, w, "systematic", interpret=True)
    b = prefix_resample_tpu(base_key, w, "improved_systematic", interpret=True)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_prefix_sum_f32_instability_story(base_key):
    """Reproduce the paper's §1 motivation: f32 prefix sums over many
    similar weights drift from the f64 truth as N grows."""
    n = 64 * TILE
    x = jax.random.uniform(base_key, (n,), jnp.float32) + 0.5
    f32 = np.asarray(prefix_sum_tpu(x, interpret=True))[-1]
    f64 = np.cumsum(np.asarray(x, np.float64))[-1]
    rel = abs(f32 - f64) / f64
    assert rel > 0  # measurable drift exists
    assert rel < 1e-4  # but bounded at this N (grows with N)
