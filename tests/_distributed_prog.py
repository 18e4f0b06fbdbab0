"""Subprocess program for distributed tests — run with 8 virtual devices.

Invoked by tests/test_distributed.py via subprocess so the main pytest
process keeps its single real CPU device (jax locks device count at init).
Prints 'OK <name>' per passing check; any exception fails the subprocess.
"""

import os

os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=8 " + os.environ.get("XLA_FLAGS", "")
)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

from repro.core.distributed import (  # noqa: E402
    _static_shard_schedule,
    effective_sample_size,
    gather_ancestors,
    island_exchange,
    make_distributed_resampler,
    megopolis_hier_ref,
)
from repro.core.metrics import mse, offspring_counts  # noqa: E402
from repro.core.weightgen import gaussian_weights  # noqa: E402
from repro.core import megopolis as core_megopolis, select_iterations  # noqa: E402
from repro.kernels.common import key_to_seed  # noqa: E402
from repro.compat import make_mesh  # noqa: E402


def main():
    assert jax.device_count() == 8, jax.device_count()
    mesh = make_mesh((8,), ("data",), devices=jax.devices())
    n = 8 * 2048
    num_iters = 24
    key = jax.random.PRNGKey(0)
    w = gaussian_weights(key, n, y=2.0)

    # ---- exactness: shard_map static schedule == single-device hier oracle
    res = make_distributed_resampler(mesh, axis_name="data", num_iters=num_iters, schedule="static")
    k_call = jax.random.PRNGKey(42)
    a_dist = np.asarray(res(k_call, w))
    k_seed, k_loc, k_shard = jax.random.split(k_call, 3)
    seed = key_to_seed(k_seed)
    offs_local = jax.random.randint(k_loc, (num_iters,), 0, n // 8, jnp.int32)
    sched = _static_shard_schedule(0xA5A5, num_iters, 8)
    a_ref = np.asarray(
        megopolis_hier_ref(seed, offs_local, sched, w, n_shards=8, num_iters=num_iters)
    )
    np.testing.assert_array_equal(a_dist, a_ref)
    print("OK static_exactness")

    # ---- exactness: dynamic (hypercube) schedule == oracle w/ same offsets
    res_d = make_distributed_resampler(
        mesh, axis_name="data", num_iters=num_iters, schedule="dynamic"
    )
    a_dyn = np.asarray(res_d(k_call, w))
    offs_shard = jax.random.randint(jax.random.split(k_call, 3)[2], (num_iters,), 0, 8, jnp.int32)
    a_ref_d = np.asarray(
        megopolis_hier_ref(seed, offs_local, offs_shard, w, n_shards=8, num_iters=num_iters)
    )
    np.testing.assert_array_equal(a_dyn, a_ref_d)
    print("OK dynamic_exactness")

    # ---- quality parity vs single-device megopolis (MSE within 40%)
    b_needed = int(select_iterations(w, 0.01))
    res_q = make_distributed_resampler(mesh, axis_name="data", num_iters=b_needed)
    runs_d, runs_s = [], []
    for t in range(16):
        kk = jax.random.fold_in(key, 100 + t)
        runs_d.append(np.asarray(offspring_counts(res_q(kk, w), n)))
        runs_s.append(np.asarray(offspring_counts(core_megopolis(kk, w, b_needed), n)))
    m_d = float(mse(jnp.asarray(np.stack(runs_d)), w)) / n
    m_s = float(mse(jnp.asarray(np.stack(runs_s)), w)) / n
    assert abs(m_d - m_s) < 0.4 * m_s, (m_d, m_s)
    print("OK quality_parity", round(m_d, 4), round(m_s, 4))

    # ---- payload gather: distributed gather == take on global arrays
    x = jax.random.normal(jax.random.PRNGKey(7), (n, 3))
    anc = res(k_call, w)
    gathered = jax.jit(
        jax.shard_map(
            lambda xl, al: gather_ancestors(xl, al, axis_name="data"),
            mesh=mesh,
            in_specs=(P("data"), P("data")),
            out_specs=P("data"),
            check_vma=False,
        )
    )(x, anc)
    np.testing.assert_allclose(np.asarray(gathered), np.asarray(jnp.take(x, anc, axis=0)), rtol=0)
    print("OK gather")

    # ---- island exchange: preserves multiset of particles
    mixed = jax.jit(
        jax.shard_map(
            lambda xl: island_exchange(xl, axis_name="data", fraction=0.25),
            mesh=mesh,
            in_specs=(P("data"),),
            out_specs=P("data"),
            check_vma=False,
        )
    )(x)
    np.testing.assert_allclose(
        np.sort(np.asarray(mixed).ravel()), np.sort(np.asarray(x).ravel()), rtol=0
    )
    print("OK island")

    # ---- ESS psum
    ess = jax.jit(
        jax.shard_map(
            lambda wl: effective_sample_size(wl, axis_name="data"),
            mesh=mesh,
            in_specs=(P("data"),),
            out_specs=P(),
            check_vma=False,
        )
    )(w)
    ess_ref = float(jnp.sum(w) ** 2 / jnp.sum(w**2))
    assert abs(float(ess) - ess_ref) / ess_ref < 1e-5
    print("OK ess")

    # ---- collective accounting: static mode must lower to exactly B
    # collective-permutes; dynamic mode to B * log2(8).
    import re

    def n_permutes(fn):
        txt = jax.jit(fn).lower(k_call, w).compile().as_text()
        # Count instruction call sites only ("collective-permute(" — the
        # async start/done forms spell "collective-permute-start(").  A bare
        # name match over-counts: HLO text repeats each instruction name at
        # every operand reference, which varies by XLA version.
        return len(re.findall(r"\bcollective-permute\(", txt))

    cp_static = n_permutes(res)
    cp_dynamic = n_permutes(res_d)
    assert cp_static <= num_iters + 2, cp_static
    # hypercube = 3 hops/iter, but hop 1 rotates the loop-invariant weight
    # block so XLA CSE dedupes it across iterations: 2B + 1 expected.
    assert 2 * num_iters <= cp_dynamic <= 3 * num_iters + 2, (cp_dynamic, num_iters)
    assert cp_dynamic > cp_static
    print("OK collective_counts", cp_static, cp_dynamic)

    print("ALL_OK")


if __name__ == "__main__":
    main()
