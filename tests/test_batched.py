"""The batched engine's contract (DESIGN.md §4), enforced end to end:

  1. registry: row ``b`` of every ``resample_batch`` is bit-identical to
     the single-population call with the matching split key;
  2. hand-batched Megopolis: the shared-offset mode equals singles with
     the shared table injected;
  3. kernel: the batched Pallas launch equals the vmapped ``ref.py``
     oracle (interpret mode) AND per-row single-bank launches;
  4. filter bank: each bank row reproduces ``run_filter`` exactly.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    get_resampler,
    get_resampler_batch,
    list_resamplers,
    megopolis,
    megopolis_batch,
)
from repro.core.resamplers.batched import split_batch_keys
from repro.kernels.common import TILE, key_to_seed
from repro.kernels.megopolis.megopolis import megopolis_pallas_fused
from repro.kernels.megopolis.ops import megopolis_tpu_batch
from repro.kernels.megopolis.ref import megopolis_ref
from repro.pf import ParticleFilter, run_filter, run_filter_bank, ungm, ungm_family, ungm_theta
from repro.pf.filter import simulate

ALL = list_resamplers()
BATCH = 3
N = 512
ITERS = 12


def _bank(key, batch=BATCH, n=N):
    return jax.random.uniform(key, (batch, n)) + 1e-3


# ------------------------------------------------------------- registry
@pytest.mark.parametrize("name", ALL)
def test_batch_rows_bit_identical_to_singles(name, base_key):
    w = _bank(jax.random.fold_in(base_key, 11))
    key = jax.random.fold_in(base_key, 12)
    got = get_resampler_batch(name)(key, w, ITERS)
    assert got.shape == (BATCH, N) and got.dtype == jnp.int32
    keys = split_batch_keys(key, BATCH)
    single = get_resampler(name)
    for b in range(BATCH):
        np.testing.assert_array_equal(
            np.asarray(got[b]), np.asarray(single(keys[b], w[b], ITERS)),
            err_msg=f"{name} row {b}",
        )


@pytest.mark.parametrize("name", ALL)
def test_batch_is_jittable_and_valid(name, base_key):
    w = _bank(jax.random.fold_in(base_key, 13))
    fn = jax.jit(get_resampler_batch(name), static_argnums=2)
    a = fn(jax.random.fold_in(base_key, 14), w, 8)
    assert bool(jnp.all((a >= 0) & (a < N)))


def test_batch_rejects_single_population_shape(base_key):
    w = jnp.ones((N,))
    with pytest.raises(ValueError, match=r"\[B, N\]"):
        get_resampler_batch("systematic")(base_key, w, 0)


# ------------------------------------------- hand-batched megopolis mode
def test_megopolis_shared_offsets_rows_equal_singles(base_key):
    w = _bank(jax.random.fold_in(base_key, 15))
    key = jax.random.fold_in(base_key, 16)
    got = megopolis_batch(key, w, ITERS, shared_offsets=True)
    # the bank-shared table megopolis_batch draws internally:
    offsets = jax.random.randint(jax.random.fold_in(key, ITERS), (ITERS,), 0, N)
    keys = split_batch_keys(key, BATCH)
    for b in range(BATCH):
        want = megopolis(keys[b], w[b], ITERS, offsets=offsets)
        np.testing.assert_array_equal(np.asarray(got[b]), np.asarray(want))


def test_megopolis_shared_offsets_still_resamples_degenerate(base_key):
    from repro.core import select_iterations

    w = jnp.full((BATCH, N), 1e-7).at[:, 137].set(1.0)
    num_iters = int(select_iterations(w[0], 0.01))  # eq. 3's B for this bank
    a = megopolis_batch(jax.random.fold_in(base_key, 17), w, num_iters, shared_offsets=True)
    assert float(jnp.mean(a == 137)) > 0.95


# ------------------------------------------------------- batched kernel
@pytest.mark.parametrize("n_tiles", [1, 2])
@pytest.mark.parametrize("num_iters", [1, 7])
def test_megopolis_kernel_batch_matches_vmapped_ref(n_tiles, num_iters, base_key):
    n = n_tiles * TILE
    bsz = 3
    w = jax.random.uniform(jax.random.fold_in(base_key, 21), (bsz, n)) + 1e-3
    offsets = jax.random.randint(jax.random.fold_in(base_key, 22), (num_iters,), 0, n, jnp.int32)
    seeds = key_to_seed(jax.random.split(jax.random.fold_in(base_key, 23), bsz))
    (got,) = megopolis_pallas_fused(
        w.reshape(bsz, -1, 128), None, jnp.broadcast_to(offsets, (bsz, num_iters)), seeds,
        num_iters=num_iters, interpret=True, name="megopolis_pallas_batch",
    )
    got = got.reshape(bsz, n)
    want = jax.vmap(
        lambda wr, s: megopolis_ref(wr, offsets, s.reshape(1), num_iters=num_iters)
    )(w, seeds)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_megopolis_kernel_batch_rows_match_single_bank_kernel(base_key):
    n, bsz, num_iters = 2 * TILE, 2, 9
    w = jax.random.uniform(jax.random.fold_in(base_key, 24), (bsz, n)) + 1e-3
    offsets = jax.random.randint(jax.random.fold_in(base_key, 25), (num_iters,), 0, n, jnp.int32)
    seeds = key_to_seed(jax.random.split(jax.random.fold_in(base_key, 26), bsz))
    (got,) = megopolis_pallas_fused(
        w.reshape(bsz, -1, 128), None, jnp.broadcast_to(offsets, (bsz, num_iters)), seeds,
        num_iters=num_iters, interpret=True, name="megopolis_pallas_batch",
    )
    for s in range(bsz):
        (single,) = megopolis_pallas_fused(
            w[s].reshape(1, -1, 128), None, offsets[None], seeds[s].reshape(1),
            num_iters=num_iters, interpret=True, name="megopolis_pallas",
        )
        np.testing.assert_array_equal(np.asarray(got[s]), np.asarray(single[0]))


def test_megopolis_tpu_batch_public_api(base_key):
    n, bsz = 2 * TILE, 3
    w = jax.random.uniform(jax.random.fold_in(base_key, 27), (bsz, n)) + 1e-3
    a = megopolis_tpu_batch(jax.random.fold_in(base_key, 28), w, 16, interpret=True)
    assert a.shape == (bsz, n) and a.dtype == jnp.int32
    assert bool(jnp.all((a >= 0) & (a < n)))
    with pytest.raises(ValueError, match="VMEM tile"):
        megopolis_tpu_batch(base_key, w[:, : n - 3], 16, interpret=True)
    with pytest.raises(ValueError, match=r"\[B, N\]"):
        megopolis_tpu_batch(base_key, w[0], 16, interpret=True)


# ---------------------------------------------------------- filter bank
@pytest.mark.parametrize("resampler", ["megopolis", "systematic"])
def test_filter_bank_rows_match_single_filters(resampler, base_key):
    num_s, steps, particles = 3, 6, 256
    model = ungm_family()
    scenarios = [ungm_theta(amp=6.0 + 2.0 * s, obs_var=0.5 + 0.5 * s) for s in range(num_s)]
    thetas = jax.tree.map(lambda *xs: jnp.stack(xs), *scenarios)
    obs = jnp.stack([
        simulate(jax.random.fold_in(base_key, 30 + s), model, steps, theta=th)[1]
        for s, th in enumerate(scenarios)
    ])
    pf = ParticleFilter(model, particles, resampler=resampler, num_iters=8)
    key = jax.random.fold_in(base_key, 40)
    bank = run_filter_bank(key, pf, obs, thetas=thetas)
    assert bank.shape == (num_s, steps)
    keys = split_batch_keys(key, num_s)
    for s in range(num_s):
        single = run_filter(keys[s], pf, obs[s], theta=scenarios[s])
        np.testing.assert_array_equal(
            np.asarray(bank[s]), np.asarray(single), err_msg=f"scenario {s}"
        )


def test_filter_bank_theta_less_model(base_key):
    """Plain (key, x, t) models join a bank unchanged — theta is optional."""
    steps, num_s = 5, 2
    _, zs = simulate(jax.random.fold_in(base_key, 50), ungm(), steps)
    obs = jnp.stack([zs] * num_s)
    pf = ParticleFilter(ungm(), 256, resampler="megopolis", num_iters=8)
    key = jax.random.fold_in(base_key, 51)
    bank = run_filter_bank(key, pf, obs)
    keys = split_batch_keys(key, num_s)
    for s in range(num_s):
        np.testing.assert_array_equal(
            np.asarray(bank[s]), np.asarray(run_filter(keys[s], pf, obs[s]))
        )
    # identical observations but distinct split keys -> rows must differ
    assert not np.array_equal(np.asarray(bank[0]), np.asarray(bank[1]))
