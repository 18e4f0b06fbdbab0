"""The typed spec API's contract (DESIGN.md §9), enforced end to end:

  1. static safety: specs are hashable, usable as jit static args, and
     ``jax.tree`` round-trips return the same object;
  2. eager validation: bad segment / backend / kind / num_iters raise at
     construction, not at trace time;
  3. ``num_iters='auto'`` routes through eq. (3) at call time (jit-safe);
  4. name parity: every registry name builds via ``spec_from_name`` and its
     single/batch paths are bit-identical to the legacy string lookups;
  5. backend dispatch: 'xla' is bit-identical to 'reference'; the pallas
     pair reproduces the kernel wrappers;
  6. the legacy surfaces (``get_resampler`` KeyError hints,
     ``ParticleFilter.resampler_kwargs``) degrade gracefully.
"""


import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    MegopolisSpec,
    MetropolisC1Spec,
    MetropolisC2Spec,
    MetropolisSpec,
    PrefixSumSpec,
    RejectionSpec,
    coerce_spec,
    get_resampler,
    get_resampler_batch,
    list_resamplers,
    metropolis,
    select_iterations,
    spec_from_name,
)
from repro.core.spec import AUTO_MAX_ITERS, Resampler

ALL = list_resamplers()
N = 512
BATCH = 3
ITERS = 12


def _weights(key, n=N):
    return jax.random.uniform(key, (n,)) + 1e-3


def _bank(key, batch=BATCH, n=N):
    return jax.random.uniform(key, (batch, n)) + 1e-3


# ------------------------------------------------------------ static safety
def test_specs_are_hashable_and_comparable():
    assert hash(MegopolisSpec(num_iters=8)) == hash(MegopolisSpec(num_iters=8))
    assert MegopolisSpec(num_iters=8) == MegopolisSpec(num_iters=8)
    assert MegopolisSpec(num_iters=8) != MegopolisSpec(num_iters=9)
    # usable as dict keys (e.g. a sweep-result table keyed by spec)
    table = {MetropolisC1Spec(partition_size_bytes=ps): ps for ps in (128, 2048)}
    assert table[MetropolisC1Spec(partition_size_bytes=128)] == 128


def test_spec_as_jit_static_argument(base_key):
    w = _weights(jax.random.fold_in(base_key, 1))

    @jax.jit
    def run(spec, key, weights):
        return spec.build()(key, weights)

    # registered static: the spec rides in the treedef, no static_argnums needed
    a = run(MegopolisSpec(num_iters=ITERS), base_key, w)
    assert a.shape == (N,) and a.dtype == jnp.int32

    from functools import partial

    @partial(jax.jit, static_argnums=0)
    def run2(spec, key, weights):
        return spec.build()(key, weights)

    np.testing.assert_array_equal(
        np.asarray(run2(MegopolisSpec(num_iters=ITERS), base_key, w)), np.asarray(a)
    )


@pytest.mark.parametrize("name", ALL)
def test_tree_util_round_trip(name):
    spec = spec_from_name(name)
    leaves, treedef = jax.tree.flatten(spec)
    assert leaves == []  # fully static: no traced content
    assert jax.tree.unflatten(treedef, leaves) == spec


def test_replace_sweeps_revalidate():
    base = MetropolisC2Spec(num_iters=4)
    sweep = [base.replace(partition_size_bytes=ps) for ps in (128, 256, 512)]
    assert [s.partition_size_bytes for s in sweep] == [128, 256, 512]
    assert all(s.num_iters == 4 for s in sweep)
    with pytest.raises(ValueError, match="partition_size_bytes"):
        base.replace(partition_size_bytes=0)


# ---------------------------------------------------------- eager validation
@pytest.mark.parametrize(
    "ctor, match",
    [
        (lambda: MegopolisSpec(num_iters=0), "num_iters"),
        (lambda: MegopolisSpec(num_iters=2.5), "num_iters"),
        (lambda: MegopolisSpec(segment=0), "segment"),
        (lambda: MegopolisSpec(backend="cuda"), "backend"),
        (lambda: MegopolisSpec(num_iters=4, backend="pallas_interpret"), "segment=1024"),
        (lambda: MetropolisC1Spec(partition_size_bytes=-1), "partition_size_bytes"),
        # C1/C2 pallas kernels partition at one VMEM tile: the spec must say so
        (lambda: MetropolisC1Spec(backend="pallas"), "4096"),
        (lambda: MetropolisC2Spec(backend="pallas_interpret", partition_size_bytes=2048), "4096"),
        (lambda: RejectionSpec(max_iters=0), "max_iters"),
        (lambda: PrefixSumSpec(kind="sistematic"), "systematic"),
        (lambda: PrefixSumSpec(backend="cuda"), "backend"),
    ],
)
def test_validation_is_eager(ctor, match):
    with pytest.raises(ValueError, match=match):
        ctor()


def test_spec_from_name_rejects_unknown_kwargs():
    with pytest.raises(TypeError, match="partition_size_bytes"):
        spec_from_name("megopolis", partition_size_bytes=128)
    # legacy API uniformity: iteration-free families tolerate num_iters
    assert spec_from_name("systematic", num_iters=30) == PrefixSumSpec(kind="systematic")
    assert spec_from_name("rejection", num_iters=30) == RejectionSpec()


def test_get_resampler_keyerror_suggests_nearest_name():
    with pytest.raises(KeyError, match="did you mean 'megopolis'"):
        get_resampler("megapolis")
    with pytest.raises(KeyError, match="did you mean 'systematic'"):
        get_resampler_batch("systemattic")
    with pytest.raises(KeyError, match="choices"):
        spec_from_name("not_even_close_xyz")


# ------------------------------------------------------------- 'auto' iters
def test_auto_iterations_match_eq3_for_metropolis(base_key):
    """num_iters only feeds the loop bound + fold_in counter, so the 'auto'
    (traced) count is bit-identical to the same static count."""
    w = jnp.full((N,), 1e-7).at[137].set(1.0)
    b = int(select_iterations(w, 0.01))
    assert b < AUTO_MAX_ITERS  # the clamp is not binding here
    got = MetropolisSpec().build()(base_key, w)
    want = metropolis(base_key, w, b)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_auto_megopolis_resolves_degeneracy_and_jits(base_key):
    w = jnp.full((N,), 1e-7).at[137].set(1.0)
    r = MegopolisSpec().build()  # the headline no-tuning call
    a = r(base_key, w)
    assert float(jnp.mean(a == 137)) > 0.95
    a_jit = jax.jit(r)(base_key, w)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(a_jit))
    bank = r.batch(base_key, jnp.stack([w, w]))
    assert bank.shape == (2, N)
    assert float(jnp.mean(bank == 137)) > 0.95


def test_auto_with_pallas_backend_needs_concrete_weights(base_key):
    spec = MegopolisSpec(segment=1024, backend="pallas_interpret")
    w = jax.random.uniform(base_key, (1024,)) + 1e-3
    with pytest.raises(TypeError, match="concrete"):
        jax.jit(spec.build())(base_key, w)


# ------------------------------------------------- name parity vs the legacy
@pytest.mark.parametrize("name", ALL)
def test_spec_single_matches_legacy_registry(name, base_key):
    w = _weights(jax.random.fold_in(base_key, 61))
    key = jax.random.fold_in(base_key, 62)
    r = coerce_spec(name, num_iters=ITERS).build()
    assert isinstance(r, Resampler) and r.name == name
    np.testing.assert_array_equal(
        np.asarray(r(key, w)), np.asarray(get_resampler(name)(key, w, ITERS))
    )


@pytest.mark.parametrize("name", ALL)
def test_spec_batch_matches_legacy_batch_registry(name, base_key):
    w = _bank(jax.random.fold_in(base_key, 63))
    key = jax.random.fold_in(base_key, 64)
    got = coerce_spec(name, num_iters=ITERS).build().batch(key, w)
    want = get_resampler_batch(name)(key, w, ITERS)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_resampler_rejects_wrong_rank(base_key):
    r = MegopolisSpec(num_iters=4).build()
    with pytest.raises(ValueError, match=r"\.batch"):
        r(base_key, jnp.ones((2, N)))
    with pytest.raises(ValueError, match=r"\[B, N\]"):
        r.batch(base_key, jnp.ones((N,)))


# ----------------------------------------------------------- backend dispatch
@pytest.mark.parametrize("name", ["megopolis", "metropolis", "systematic", "rejection"])
def test_xla_backend_bit_identical_to_reference(name, base_key):
    w = _weights(jax.random.fold_in(base_key, 65))
    ref = coerce_spec(name, num_iters=ITERS).build()
    xla = coerce_spec(name, num_iters=ITERS).replace(backend="xla").build()
    np.testing.assert_array_equal(np.asarray(ref(base_key, w)), np.asarray(xla(base_key, w)))
    wb = _bank(jax.random.fold_in(base_key, 66))
    np.testing.assert_array_equal(
        np.asarray(ref.batch(base_key, wb)), np.asarray(xla.batch(base_key, wb))
    )


def test_pallas_interpret_backend_matches_kernel_wrappers(base_key):
    from repro.kernels.megopolis.ops import megopolis_tpu, megopolis_tpu_batch

    n = 1024
    w = jax.random.uniform(jax.random.fold_in(base_key, 67), (n,)) + 1e-3
    r = MegopolisSpec(num_iters=4, segment=1024, backend="pallas_interpret").build()
    np.testing.assert_array_equal(
        np.asarray(r(base_key, w)), np.asarray(megopolis_tpu(base_key, w, 4, interpret=True))
    )
    wb = jax.random.uniform(jax.random.fold_in(base_key, 68), (2, n)) + 1e-3
    np.testing.assert_array_equal(
        np.asarray(r.batch(base_key, wb)), np.asarray(megopolis_tpu_batch(base_key, wb, 4, interpret=True))
    )


# --------------------------------------------------- ParticleFilter frontier
def test_particle_filter_accepts_spec_and_string(base_key):
    from repro.pf import ParticleFilter, run_filter, ungm
    from repro.pf.filter import simulate

    _, zs = simulate(jax.random.fold_in(base_key, 70), ungm(), 5)
    by_name = ParticleFilter(ungm(), 256, resampler="megopolis", num_iters=8)
    by_spec = ParticleFilter(ungm(), 256, resampler=MegopolisSpec(num_iters=8))
    assert by_name.spec == by_spec.spec == MegopolisSpec(num_iters=8)
    k = jax.random.fold_in(base_key, 71)
    np.testing.assert_array_equal(
        np.asarray(run_filter(k, by_name, zs)), np.asarray(run_filter(k, by_spec, zs))
    )


def test_particle_filter_resampler_kwargs_compat_shim(base_key):
    from repro.pf import ParticleFilter, ungm

    with pytest.warns(DeprecationWarning, match="resampler_kwargs"):
        pf = ParticleFilter(ungm(), 256, resampler="metropolis_c1", num_iters=8,
                            resampler_kwargs=(("partition_size_bytes", 2048),))
    assert pf.spec == MetropolisC1Spec(num_iters=8, partition_size_bytes=2048)
    with pytest.raises(ValueError, match="inside the ResamplerSpec"):
        ParticleFilter(ungm(), 256, resampler=MegopolisSpec(num_iters=8),
                       resampler_kwargs=(("segment", 64),))
    # a half-migrated call must fail loudly, not silently drop num_iters
    with pytest.raises(ValueError, match="inside the spec"):
        ParticleFilter(ungm(), 256, resampler=MegopolisSpec(), num_iters=8)
    # string names keep the paper §7 default prior when num_iters is unset
    assert ParticleFilter(ungm(), 256).spec == MegopolisSpec(num_iters=30)


def test_particle_filter_validates_eagerly():
    from repro.pf import ParticleFilter, ungm

    with pytest.raises(KeyError, match="did you mean"):
        ParticleFilter(ungm(), 256, resampler="megapolis")
    with pytest.raises(ValueError, match="num_iters"):
        ParticleFilter(ungm(), 256, resampler="megopolis", num_iters=0)


def test_smc_config_resolves_spec():
    from repro.smc import SMCDecodeConfig

    cfg = SMCDecodeConfig(num_particles=8, max_new_tokens=4, resampler="megopolis",
                          num_iters=7, segment=16)
    assert cfg.resampler_spec() == MegopolisSpec(num_iters=7, segment=16)
    # segment/num_iters don't leak into families that lack them
    cfg2 = SMCDecodeConfig(num_particles=8, max_new_tokens=4, resampler="systematic")
    assert cfg2.resampler_spec() == PrefixSumSpec(kind="systematic")
    spec = MetropolisSpec(num_iters=3)
    cfg3 = SMCDecodeConfig(num_particles=8, max_new_tokens=4, resampler=spec)
    assert cfg3.resampler_spec() is spec


def test_distributed_resampler_spec_validation():
    from repro.core.distributed import make_distributed_resampler

    with pytest.raises(TypeError, match="MegopolisSpec"):
        make_distributed_resampler(None, spec=MetropolisSpec(num_iters=4))
    with pytest.raises(ValueError, match="concrete num_iters"):
        make_distributed_resampler(None, spec=MegopolisSpec())  # num_iters='auto'
    with pytest.raises(ValueError, match="backend"):
        make_distributed_resampler(
            None, spec=MegopolisSpec(num_iters=4, segment=1024, backend="pallas"))
    with pytest.raises(ValueError, match="schedule"):
        make_distributed_resampler(None, spec=MegopolisSpec(num_iters=4, segment=1024),
                                   schedule="bogus")
