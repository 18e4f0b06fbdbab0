"""Benchmark orchestrator: one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--full] [--skip NAME ...] [--json PATH]

CI scale by default (~minutes on CPU); ``--full`` restores paper sizes.
``--json PATH`` writes the per-suite wall-times (plus the ais suite's logZ
quality stats) to a machine-readable trajectory file — accrete one
``BENCH_<date>.json`` per run into the perf history (EXPERIMENTS.md §Perf;
a second run the same day gets ``-2``, ``-3``, … rather than clobbering
the first).  Every snapshot is stamped with provenance (git SHA, jax/
jaxlib versions, device kind/platform) so ``benchmarks/trajectory.py``
can attribute a delta to a code or toolchain change, and the run streams
``suite_start``/``suite_end``/``run_end`` events to the JSONL flight
recorder at ``out/events.jsonl`` (DESIGN.md §15).  The dry-run / roofline
pipeline is separate (launch/dryrun.py) because it re-initialises jax
with 512 virtual devices.
"""

from __future__ import annotations

import argparse
import difflib
import json
import os
import subprocess
import sys
import time
from datetime import date

SUITES = [
    ("transactions", "benchmarks.transactions_bench", []),
    ("kernel", "benchmarks.kernel_bench", []),
    ("fig6", "benchmarks.fig6_quality_speed", []),
    ("fig7", "benchmarks.fig7_partition_sweep", []),
    ("fig8", "benchmarks.fig8_prefix_sum", []),
    ("fig10", "benchmarks.fig10_gamma", []),
    ("table2", "benchmarks.table2_e2e_pf", []),
    ("filter_bank", "benchmarks.filter_bank_bench", ["--quick"]),
    ("ais", "benchmarks.ais_bench", ["--quick"]),
    ("smc", "benchmarks.smc_decode_bench", ["--particles", "32", "--new-tokens", "8",
                                            "--archs", "qwen3-0.6b"]),
    ("fused_gather", "benchmarks.fused_gather_bench", ["--quick"]),
    ("step", "benchmarks.step_bench", ["--quick"]),
    ("analysis", "benchmarks.analysis_bench", []),
    ("resilience", "benchmarks.resilience_bench", ["--quick"]),
]
# Suites whose CLI has no --full flag (or whose scale is pinned above).
_NO_FULL = ("transactions", "kernel", "smc", "filter_bank", "ais",
            "fused_gather", "step", "analysis", "resilience")


def _check_suite_names(names, flag: str):
    """Unknown suite names error with a difflib nearest-match hint (the
    same UX as the spec registry's KeyErrors) instead of being silently
    ignored — a typo in --skip used to run the suite anyway."""
    known = [name for name, _, _ in SUITES]
    for name in names:
        if name not in known:
            hint = difflib.get_close_matches(name, known, n=1)
            did_you_mean = f" — did you mean {hint[0]!r}?" if hint else ""
            raise SystemExit(
                f"benchmarks.run: unknown suite {name!r} in {flag}"
                f"{did_you_mean}; choices: {known}"
            )


def _ais_stats():
    """Fold the ais suite's logZ quality rows into the trajectory JSON
    (written by benchmarks.ais_bench as BENCH_ais.json)."""
    from benchmarks.common import OUT_DIR

    path = os.path.join(OUT_DIR, "BENCH_ais.json")
    if not os.path.exists(path):
        return None
    with open(path) as f:
        payload = json.load(f)
    return {
        "config": payload.get("config"),
        "logz": [
            {k: r[k] for k in ("resampler", "backend", "target", "logz_bias",
                               "logz_std", "logz_rmse", "wall_per_run_s")}
            for r in payload.get("rows", [])
        ],
    }


def _fused_gather_stats():
    """Fold the fused-vs-unfused suite's rows into the trajectory JSON
    (written by benchmarks.fused_gather_bench as BENCH_fused_gather.json)."""
    from benchmarks.common import OUT_DIR

    path = os.path.join(OUT_DIR, "BENCH_fused_gather.json")
    if not os.path.exists(path):
        return None
    with open(path) as f:
        payload = json.load(f)
    return {
        "config": payload.get("config"),
        "cells": [
            {k: r.get(k, "float32" if k == "plane_dtype" else None)
             for k in ("family", "backend", "state_dim", "plane_dtype",
                       "fused_ms", "unfused_ms", "speedup", "model_speedup",
                       "parity", "perf_gated", "identical_program")}
            for r in payload.get("rows", [])
        ],
    }


def _step_stats():
    """Fold the fused-step suite's rows into the trajectory JSON (written
    by benchmarks.step_bench as BENCH_step.json)."""
    from benchmarks.common import OUT_DIR

    path = os.path.join(OUT_DIR, "BENCH_step.json")
    if not os.path.exists(path):
        return None
    with open(path) as f:
        payload = json.load(f)
    return {
        "config": payload.get("config"),
        "cells": [
            {k: r.get(k, "float32" if k == "plane_dtype" else None)
             for k in ("family", "backend", "plane_dtype", "step_ms",
                       "composed_ms", "speedup", "launches_step",
                       "launches_composed", "parity", "perf_gated",
                       "identical_program")}
            for r in payload.get("rows", [])
        ],
    }


def _analysis_stats():
    """Fold the static contract audit — launch counts per matrix cell and
    the modelled §2.4 transaction table — into the trajectory JSON
    (written by benchmarks.analysis_bench as BENCH_analysis.json)."""
    from benchmarks.common import OUT_DIR

    path = os.path.join(OUT_DIR, "BENCH_analysis.json")
    if not os.path.exists(path):
        return None
    with open(path) as f:
        payload = json.load(f)
    return {
        "ok": payload.get("ok"),
        "cells": payload.get("cells"),
        "transactions": payload.get("transactions"),
    }


def _unique_snapshot_path(directory: str) -> str:
    """``BENCH_<date>.json`` inside ``directory``, suffixed ``-2``, ``-3``,
    … when today's snapshot already exists — a same-day re-run must accrete
    a new trajectory point, not overwrite the morning's."""
    stem = f"BENCH_{date.today().isoformat()}"
    path = os.path.join(directory, f"{stem}.json")
    k = 2
    while os.path.exists(path):
        path = os.path.join(directory, f"{stem}-{k}.json")
        k += 1
    return path


def provenance() -> dict:
    """Who/what produced this snapshot: git SHA (``unknown`` outside a
    checkout), jax/jaxlib versions, and the device the suites ran on —
    enough for trajectory.py to attribute a delta."""
    try:
        sha = subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"],
            capture_output=True, text=True, timeout=10,
            cwd=os.path.dirname(os.path.abspath(__file__)),
        ).stdout.strip() or "unknown"
    except (OSError, subprocess.SubprocessError):
        sha = "unknown"
    import jax
    import jaxlib

    dev = jax.devices()[0]
    return {
        "git_sha": sha,
        "jax_version": jax.__version__,
        "jaxlib_version": jaxlib.__version__,
        "device_kind": dev.device_kind,
        "platform": dev.platform,
    }


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--skip", nargs="*", default=[])
    ap.add_argument("--only", nargs="*", default=[])
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="write per-suite wall-times (+ ais logZ stats) to PATH; "
                         "pass a directory to get BENCH_<date>.json inside it")
    args = ap.parse_args(argv)
    _check_suite_names(args.skip, "--skip")
    _check_suite_names(args.only, "--only")

    from benchmarks.common import ensure_out
    from repro.compile_cache import enable_compile_cache
    from repro.obs.sink import JsonlSink

    enable_compile_cache()

    sink = JsonlSink(os.path.join(ensure_out(), "events.jsonl"))
    prov = provenance()
    sink.emit("run_start", full=args.full, **prov)

    failures = []
    suite_times = {}
    for name, module, extra in SUITES:
        if name in args.skip or (args.only and name not in args.only):
            continue
        print(f"\n======== {name} ({module}) ========")
        sink.emit("suite_start", suite=name)
        t0 = time.time()
        argv_m = list(extra) + (["--full"] if args.full and name not in _NO_FULL else [])
        try:
            mod = __import__(module, fromlist=["main"])
            mod.main(argv_m)
            suite_times[name] = time.time() - t0
            print(f"[{name}] OK in {suite_times[name]:.1f}s")
        except SystemExit as e:
            if e.code not in (0, None):
                failures.append(name)
            else:
                suite_times[name] = time.time() - t0
        except Exception:
            import traceback
            traceback.print_exc()
            failures.append(name)
        sink.emit(
            "suite_end", suite=name, ok=name not in failures,
            wall_s=round(suite_times.get(name, time.time() - t0), 3),
        )

    if args.json:
        path = args.json
        if os.path.isdir(path):
            path = _unique_snapshot_path(path)
        payload = {
            "date": date.today().isoformat(),
            "full": args.full,
            "provenance": prov,
            "suite_wall_s": {k: round(v, 3) for k, v in suite_times.items()},
            "failures": failures,
        }
        ais = _ais_stats() if "ais" in suite_times else None
        if ais:
            payload["ais"] = ais
        fused = _fused_gather_stats() if "fused_gather" in suite_times else None
        if fused:
            payload["fused_gather"] = fused
        step = _step_stats() if "step" in suite_times else None
        if step:
            payload["step"] = step
        analysis = _analysis_stats() if "analysis" in suite_times else None
        if analysis:
            payload["analysis"] = analysis
        with open(path, "w") as f:
            json.dump(payload, f, indent=2)
        print(f"\nwrote trajectory {path}")
        sink.emit("snapshot_written", path=path)

    sink.emit("run_end", ok=not failures, failures=failures)
    if failures:
        print(f"\nFAILED suites: {failures}")
        sys.exit(1)
    print("\nall benchmark suites passed")


if __name__ == "__main__":
    main()
