"""Paper §7 (Fig. 9 + Table 2): end-to-end SIR particle filter on the UNGM
nonlinear system (eqs. 22-23) — mean RMSE and the host wall time of one
whole jitted ``run_filter`` across B (a CPU wall here: the per-stage split
comes from a profile on the chip, ``bench/``).

Fig. 9: B sweep for {Megopolis, Metropolis, C1-PS128, C2-PS128}.
Table 2: B in {16, 32, 64} + the unbiased multinomial/systematic baselines.
"""

from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from benchmarks.common import print_table, write_csv
from repro.core import (
    MegopolisSpec,
    MetropolisC1Spec,
    MetropolisC2Spec,
    MetropolisSpec,
    PrefixSumSpec,
)
from repro.pf.filter import ParticleFilter, run_filter, simulate
from repro.pf.metrics import rmse
from repro.pf.models import ungm

# Typed spec templates (DESIGN.md §9): the B sweep is spec.replace, and the
# per-algorithm hyperparameters live inside the spec — no kwargs tuples.
FIG9_ALGOS = {
    "megopolis": MegopolisSpec(),
    "metropolis": MetropolisSpec(),
    "c1_ps128": MetropolisC1Spec(partition_size_bytes=128),
    "c2_ps128": MetropolisC2Spec(partition_size_bytes=128),
}


def evaluate(algo: str, spec, b: int, *, particles: int, steps: int,
             mc_runs: int) -> dict:
    model = ungm()
    pf = ParticleFilter(model, particles, resampler=spec)
    filt = jax.jit(lambda k, zs: run_filter(k, pf, zs))
    errs, walls = [], []
    for run_i in range(mc_runs):
        key = jax.random.PRNGKey(run_i)
        k_sim, k_flt = jax.random.split(key)
        xs, zs = simulate(k_sim, model, steps)
        jax.block_until_ready(filt(k_flt, zs))  # compiled on the first run
        t0 = time.perf_counter()
        ests = jax.block_until_ready(filt(k_flt, zs))
        walls.append(time.perf_counter() - t0)
        errs.append(rmse(np.asarray(ests)[None], np.asarray(xs)))
    return {"algo": algo, "B": b, "rmse": float(np.mean(errs)),
            "run_filter_ms": float(np.median(walls)) * 1e3}


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    args = ap.parse_args(argv)
    particles = 1 << (20 if args.full else 13)
    steps = 100 if args.full else 25
    mc = 4 if not args.full else 16

    # Fig. 9: B sweep
    b_values = (5, 10, 20, 30) if not args.full else (5, 7, 10, 15, 20, 25, 30, 40)
    fig9 = []
    for iters in b_values:
        for algo, template in FIG9_ALGOS.items():
            fig9.append(evaluate(algo, template.replace(num_iters=iters), iters,
                                 particles=particles, steps=steps, mc_runs=mc))
    write_csv("fig9.csv", fig9)
    print("== Fig. 9 (B sweep) ==")
    print_table(fig9)

    # Table 2: fixed B + unbiased baselines
    table2 = []
    for algo in ("multinomial", "improved_systematic"):
        table2.append(evaluate(algo, PrefixSumSpec(kind=algo), 0,
                               particles=particles, steps=steps, mc_runs=mc))
    for iters in (16, 32, 64):
        for algo, template in FIG9_ALGOS.items():
            table2.append(evaluate(algo, template.replace(num_iters=iters), iters,
                                   particles=particles, steps=steps, mc_runs=mc))
    write_csv("table2.csv", table2)
    print("\n== Table 2 ==")
    print_table(table2)


if __name__ == "__main__":
    main()
