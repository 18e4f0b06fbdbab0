"""End-to-end SIR particle filter on the univariate nonlinear growth model
(paper §7, eqs. 22-23): tracks a simulated trajectory and reports the RMSE
of Megopolis vs alternatives.  Where the time of each stage goes is read
from a profile on the chip (the benchmark under ``bench/``), not here.

    PYTHONPATH=src python examples/particle_filter.py [--particles 16384]

``--bank S`` instead runs a SCENARIO BANK (DESIGN.md §4): S differently
parameterised UNGM instances filtered side by side in one jitted scan —
one batched resampling launch per step instead of S — and prints the
per-scenario RMSE plus the bank-vs-naive-loop speedup.

    PYTHONPATH=src python examples/particle_filter.py --bank 8
"""

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import (
    MegopolisSpec,
    MetropolisC1Spec,
    MetropolisSpec,
    PrefixSumSpec,
)
from repro.pf.filter import (
    ParticleFilter,
    run_filter,
    run_filter_bank,
    simulate,
)
from repro.pf.metrics import rmse
from repro.pf.models import ungm, ungm_family, ungm_theta


def run_bank_demo(args):
    model = ungm_family()
    scenarios = [
        ungm_theta(amp=4.0 + 8.0 * s / max(args.bank - 1, 1), obs_var=0.5 + 0.25 * s)
        for s in range(args.bank)
    ]
    thetas = jax.tree.map(lambda *xs: jnp.stack(xs), *scenarios)
    truths, obs = [], []
    for s, th in enumerate(scenarios):
        xs, zs = simulate(jax.random.PRNGKey(100 + s), model, args.steps, theta=th)
        truths.append(np.asarray(xs))
        obs.append(zs)
    obs = jnp.stack(obs)

    pf = ParticleFilter(model, args.particles, resampler=MegopolisSpec(num_iters=args.iters))
    key = jax.random.PRNGKey(42)

    bank = jax.jit(lambda k: run_filter_bank(k, pf, obs, thetas=thetas))
    jax.block_until_ready(bank(key))  # compile
    t0 = time.perf_counter()
    ests = jax.block_until_ready(bank(key))
    t_bank = time.perf_counter() - t0

    keys = jax.random.split(key, args.bank)
    loop = jax.jit(lambda k, z, th: run_filter(k, pf, z, theta=th))
    jax.block_until_ready(loop(keys[0], obs[0], scenarios[0]))  # compile
    t0 = time.perf_counter()
    for s in range(args.bank):
        jax.block_until_ready(loop(keys[s], obs[s], scenarios[s]))
    t_loop = time.perf_counter() - t0

    print(f"UNGM scenario bank: S={args.bank}, {args.particles} particles, "
          f"{args.steps} steps, B={args.iters} (megopolis)\n")
    print(f"{'scenario':>8s} {'amp':>6s} {'obs_var':>8s} {'RMSE':>8s}")
    for s, th in enumerate(scenarios):
        err = rmse(np.asarray(ests[s])[None], truths[s])
        print(f"{s:8d} {float(th['amp']):6.2f} {float(th['obs_var']):8.2f} {err:8.3f}")
    print(f"\nbank: {t_bank*1e3:8.1f} ms   naive loop: {t_loop*1e3:8.1f} ms   "
          f"speedup: {t_loop / t_bank:5.2f}x")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--particles", type=int, default=1 << 14)
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--iters", type=int, default=30, help="B (paper §7 baseline)")
    ap.add_argument("--bank", type=int, default=0,
                    help="run S scenarios as one batched filter bank instead")
    args = ap.parse_args()
    if args.bank:
        return run_bank_demo(args)

    model = ungm()
    key = jax.random.PRNGKey(42)
    k_sim, k_flt = jax.random.split(key)
    truth, obs = simulate(k_sim, model, args.steps)

    print(f"UNGM, {args.particles} particles, {args.steps} steps, B={args.iters}\n")
    print(f"{'resampler':22s} {'RMSE':>8s}")
    # Each competitor is one typed spec — hyperparameters travel inside it
    # (DESIGN.md §9), so there is no per-algorithm kwargs plumbing here.
    for spec in (MegopolisSpec(num_iters=args.iters),
                 MetropolisSpec(num_iters=args.iters),
                 MetropolisC1Spec(num_iters=args.iters, partition_size_bytes=128),
                 PrefixSumSpec(kind="improved_systematic")):
        pf = ParticleFilter(model, args.particles, resampler=spec)
        ests = run_filter(k_flt, pf, obs)
        err = rmse(np.asarray(ests)[None], np.asarray(truth))
        print(f"{spec.name:22s} {err:8.3f}")


if __name__ == "__main__":
    main()
