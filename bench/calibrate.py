#!/usr/bin/env python3
"""Readings that a cell's correctness limits are set from, on the chip.

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,3 --seconds 3 \
        [--faults state_unchanged,half_batch,answer_altered --fault-seeds 3]

In one process, at the cell's own size and load: for each seed a short
window of the cell's traffic, then

* ``program``: the check's ``est_gap`` of the program against the float32
  reference (the lower reading is the largest over the seeds);
* ``control``: the same gap of the reference computed in bfloat16, the
  precision below the configuration's float32, on the same tracks (the
  upper reading is the smallest over the seeds);

and, with ``--faults``, the program's gap with each planted fault
(``faults.py``) on the first ``--fault-seeds`` seeds. Prints one JSON line
per reading; the benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]

import faults  # noqa: E402
import registry  # noqa: E402
import run  # noqa: E402


def readings(workload, seeds, seconds, fault=None, overrides=None):
    import jax
    import jax.numpy as jnp
    import numpy as np

    import check
    import loadgen
    import system as system_mod

    cell = registry.find_cell(workload)
    config = {**cell.config, **(overrides or {}).get("config", {})}
    traffic = {**cell.traffic, **(overrides or {}).get("traffic", {})}
    reference = registry.load_module("reference", config["reference"])
    with faults.planted(fault) if fault else contextlib.nullcontext():
        sut = system_mod.build(config)
        for seed in seeds:
            pool_key, filter_key = run.base_keys(seed)
            _, zs = reference.simulate(config, jax.random.split(pool_key, traffic["pool"]),
                                       traffic["steps_per_track"])
            zs = np.asarray(zs)
            zs_dev = jax.device_put(zs)
            loadgen.warm_up(traffic, sut, filter_key, zs_dev, zs)
            window = loadgen.run_window(traffic, sut, filter_key, zs_dev, zs, seconds)
            tracks = check.sample_tracks(window, traffic, seed)
            got = np.stack([window.tracks[r] for r in tracks]).astype(np.float64)
            want = check.reference_estimates(config, reference, filter_key, zs, tracks)
            row = {"workload": workload, "seed": seed, "fault": fault, "tracks": tracks,
                   "steps": window.steps, "program": float(np.max(np.abs(got - want)))}
            if fault is None:
                low = check.reference_estimates(config, reference, filter_key, zs, tracks,
                                                jnp.bfloat16)
                row["control"] = float(np.max(np.abs(low - want)))
            print(json.dumps(row), flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--faults", default="", help="comma-separated, from faults.FAULTS")
    ap.add_argument("--fault-seeds", type=int, default=3)
    args = ap.parse_args(argv)
    import jax

    if jax.devices()[0].platform != "tpu":
        print("calibrate: needs a TPU", file=sys.stderr)
        return 2
    jax.config.update("jax_compilation_cache_dir", str(run.CACHE_DIR))
    seeds = [int(s) for s in args.seeds.split(",")]
    readings(args.workload, seeds, args.seconds)
    for fault in filter(None, args.faults.split(",")):
        readings(args.workload, seeds[: args.fault_seeds], args.seconds, fault)
    return 0


if __name__ == "__main__":
    sys.exit(main())
