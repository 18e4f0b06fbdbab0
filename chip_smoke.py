#!/usr/bin/env python3
"""Chip smoke test: the Megopolis kernels and the paper's UNGM filter on one TPU.

    python3 chip_smoke.py

Runs in one process on the first TPU device, compiled by Mosaic (never the
Pallas interpreter), and exits non-zero if JAX finds no TPU or any check
fails.  Two phases:

* A, kernel parity at paper sizes (paper §5 Gaussian weights, y = 2.0):
  the ``pallas`` Megopolis call against the ``ref.py`` oracle run by XLA on
  the same chip, bitwise, at N = 2^16, 2^20 and 2^22; the fused ``apply``
  (state_dim 1 and 4) against the call and an XLA gather, bitwise; the
  fused ``step`` against the composition normalise -> ESS -> branch ->
  ``apply``; ``step_rows`` against ``step`` row by row.
* B, the main path (paper §7, Table 2): ``run_filter`` on UNGM with
  N = 2^20, T = 100 and B in {16, 32, 64}, Alg. 6 and conditional SIR, four
  seeds each, against the same filter on the ``xla`` backend.

Timings on the lines before the last are informational.  The last line is
one JSON object naming the device.
"""

from __future__ import annotations

import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

SEG_ITERS = 32  # Phase A iteration count
N_FILTER = 1 << 20
T_STEPS = 100
B_VALUES = (16, 32, 64)
SEEDS = (0, 1, 2, 3)
RMSE_REL_TOL = 0.10
ESS_THRESHOLD = 0.5

_failures: list[str] = []


def check(ok: bool, what: str) -> None:
    print(("PASS " if ok else "FAIL ") + what, flush=True)
    if not ok:
        _failures.append(what)


def _ulps(a, b) -> int:
    """Distance in units of last place between two f32 scalars."""
    import numpy as np

    ia = np.asarray(a, np.float32).view(np.int32).astype(np.int64)
    ib = np.asarray(b, np.float32).view(np.int32).astype(np.int64)
    return int(abs(ia - ib))


def phase_a(jax, jnp, np):
    from jax.experimental import pallas as pl

    from repro.core.metrics import (
        effective_sample_size,
        log_mean_weight,
        max_normalised_weight,
        normalise_log_weights,
    )
    from repro.core.spec import MegopolisSpec
    from repro.core.weightgen import gaussian_weights
    from repro.kernels.common import key_to_seed
    from repro.kernels.megopolis.megopolis import tiles_per_step
    from repro.kernels.megopolis.ref import megopolis_ref

    r = MegopolisSpec(num_iters=SEG_ITERS, segment=1024, backend="pallas").build()
    key = jax.random.PRNGKey(2021)

    # -- call vs the XLA-run oracle, bitwise
    for logn in (16, 20, 22):
        n = 1 << logn
        kw, kc = jax.random.split(jax.random.fold_in(key, logn))
        w = gaussian_weights(kw, n, 2.0)
        anc = r(kc, w)
        key_off, key_seed = jax.random.split(kc)
        offsets = jax.random.randint(key_off, (SEG_ITERS,), 0, n, dtype=jnp.int32)
        seed = key_to_seed(key_seed).reshape(1)
        ref = megopolis_ref(w, offsets, seed, num_iters=SEG_ITERS)
        bad = int(jnp.sum(anc != ref))
        check(bad == 0, f"A call N=2^{logn}: {bad} ancestor mismatches vs megopolis_ref")

    # -- fused apply: ancestors == call, particles' == particles[ancestors]
    n = N_FILTER
    print(f"INFO tiles per grid step of the fused apply and step at N=2^20, state_dim 1: "
          f"G={tiles_per_step(n // 1024, 1, 4)}", flush=True)
    kw, kc, kp = jax.random.split(jax.random.fold_in(key, 100), 3)
    w = gaussian_weights(kw, n, 2.0)
    anc_call = r(kc, w)
    for d in (1, 4):
        p = jax.random.normal(kp, (n,) if d == 1 else (n, d))
        p_out, anc = r.apply(kc, w, p)
        bad_a = int(jnp.sum(anc != anc_call))
        bad_p = int(jnp.sum(jnp.take(p, anc, axis=0) != p_out))
        check(bad_a == 0 and bad_p == 0,
              f"A apply N=2^20 state_dim={d}: {bad_a} ancestor / {bad_p} state "
              "mismatches vs call + XLA gather")

    # -- exp: Mosaic vs XLA on the same values (the only op the fused step
    #    and its composition oracle do not share bit for bit by construction)
    lw = jax.random.normal(jax.random.fold_in(key, 200), (n,)) * 2.0
    lw2 = (lw - jnp.max(lw)).reshape(n // 128, 128)

    def exp_kernel(x_ref, o_ref):
        o_ref[...] = jnp.exp(x_ref[...])

    e_mosaic = pl.pallas_call(
        exp_kernel, out_shape=jax.ShapeDtypeStruct(lw2.shape, jnp.float32),
        grid=(lw2.shape[0] // 8,),
        in_specs=[pl.BlockSpec((8, 128), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((8, 128), lambda i: (i, 0)),
    )(lw2)
    e_xla = jax.jit(jnp.exp)(lw2)
    n_exp = int(jnp.sum(e_mosaic != e_xla))
    print(f"INFO exp Mosaic vs XLA at N=2^20: {n_exp} of {n} values differ", flush=True)

    # -- fused step vs the composition on the same backend
    @jax.jit
    def composed(k, lw, p):
        ess_n = effective_sample_size(lw) / jnp.float32(lw.shape[-1])
        do = ess_n < ESS_THRESHOLD
        p_res, a_res = r.apply(k, normalise_log_weights(lw), p)
        anc = jnp.where(do, a_res, jnp.arange(lw.shape[-1], dtype=jnp.int32))
        p_out = jnp.where(do, p_res, p)
        incr = jnp.where(do, log_mean_weight(lw), 0.0)
        stats = jnp.stack([ess_n, incr, do.astype(jnp.float32),
                           max_normalised_weight(lw)])
        return p_out, anc, stats

    p = jax.random.normal(kp, (n,))
    for label, scale, fires in (("triggering", 2.0, True), ("holding", 0.1, False)):
        lw = jax.random.normal(jax.random.fold_in(key, 300), (n,)) * scale
        p_out, anc, st = r.step(kc, lw, p, ESS_THRESHOLD)
        e_p, e_anc, e_st = composed(kc, lw, p)
        got = np.array([st.ess_norm, st.log_evidence_incr, st.resampled, st.max_weight])
        want = np.asarray(e_st)
        ulps = [_ulps(g, e) for g, e in zip(got, want)]
        bad_a = int(jnp.sum(anc != e_anc))
        print(f"INFO step N=2^20 {label}: {bad_a} ancestor mismatches vs composition; "
              f"stats (ess_norm, incr, resampled, max_weight) kernel={got.tolist()} "
              f"composed={want.tolist()} ulps={ulps}", flush=True)
        check(bool(st.resampled == 1.0) == fires and float(want[2]) == float(fires),
              f"A step N=2^20 {label}: resample decision {fires} on chip and in the composition")
        bad_p = int(jnp.sum(jnp.take(p, anc, axis=0) != p_out))
        check(bad_p == 0, f"A step N=2^20 {label}: particles' == particles[ancestors] "
              f"({bad_p} mismatches)")
        if not fires:
            check(bool(jnp.all(anc == jnp.arange(n))) and bool(jnp.all(p_out == p)),
                  f"A step N=2^20 {label}: identity ancestors, particles unchanged")

    # -- step_rows row b == step(keys[b])
    s, n = 4, 1 << 18
    keys = jax.random.split(jax.random.fold_in(key, 400), s)
    scales = jnp.array([2.0, 0.1, 1.5, 0.05])[:, None]
    lw = jax.random.normal(jax.random.fold_in(key, 401), (s, n)) * scales
    p = jax.random.normal(jax.random.fold_in(key, 402), (s, n))
    got = r.step_rows(keys, lw, p, ESS_THRESHOLD)
    bad = 0
    for b in range(s):
        want = r.step(keys[b], lw[b], p[b], ESS_THRESHOLD)
        for g, e in zip(jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)):
            bad += int(jnp.sum(g[b] != e))
    fired = np.asarray(got[2].resampled).tolist()
    check(bad == 0, f"A step_rows S=4 N=2^18 (resampled={fired}): {bad} mismatches vs step")


def phase_b(jax, jnp, np):
    from repro.core.spec import MegopolisSpec
    from repro.pf.filter import ParticleFilter, run_filter, simulate
    from repro.pf.models import ungm

    model = ungm()
    trajectories = [simulate(jax.random.PRNGKey(10_000 + s), model, T_STEPS) for s in SEEDS]
    for b in B_VALUES:
        for mode, thr in (("alg6", None), ("conditional", ESS_THRESHOLD)):
            mean_rmse = {}
            for backend in ("pallas", "xla"):
                pf = ParticleFilter(
                    model, N_FILTER, ess_threshold=thr,
                    resampler=MegopolisSpec(num_iters=b, segment=1024, backend=backend),
                )
                fn = jax.jit(lambda k, z, pf=pf: run_filter(k, pf, z))
                t0 = time.perf_counter()
                compiled = fn.lower(jax.random.PRNGKey(0), trajectories[0][1]).compile()
                t_compile = time.perf_counter() - t0
                rmses, walls, finite = [], [], True
                for s, (xs, zs) in zip(SEEDS, trajectories):
                    t0 = time.perf_counter()
                    est = compiled(jax.random.PRNGKey(s), zs).block_until_ready()
                    walls.append(time.perf_counter() - t0)
                    finite &= bool(jnp.all(jnp.isfinite(est)))
                    rmses.append(float(jnp.sqrt(jnp.mean((est - xs) ** 2))))
                mean_rmse[backend] = float(np.mean(rmses))
                print(f"INFO B={b} {mode} {backend}: rmse={rmses} mean={mean_rmse[backend]} "
                      f"compile_s={t_compile} run_s={walls}", flush=True)
                check(finite, f"B B={b} {mode} {backend}: every estimate finite")
            rel = abs(mean_rmse["pallas"] - mean_rmse["xla"]) / mean_rmse["xla"]
            check(rel <= RMSE_REL_TOL,
                  f"B B={b} {mode}: mean RMSE pallas {mean_rmse['pallas']} vs xla "
                  f"{mean_rmse['xla']} (rel {rel} <= {RMSE_REL_TOL})")


def main() -> int:
    # The program's own import first: a lone copy of this script fails here.
    from repro.compile_cache import enable_compile_cache

    import jax
    import jax.numpy as jnp
    import numpy as np

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"chip_smoke: needs a TPU; JAX found {dev.platform!r}")
    cache = enable_compile_cache()
    print(f"INFO device {dev.platform} {dev.device_kind} x{len(jax.devices())}; "
          f"jax {jax.__version__}; compile cache {cache}", flush=True)

    for name, phase in (("A", phase_a), ("B", phase_b)):
        t0 = time.perf_counter()
        phase(jax, jnp, np)
        print(f"INFO phase {name} took {time.perf_counter() - t0} s", flush=True)
    stats = dev.memory_stats() or {}
    print(f"INFO peak_bytes_in_use {stats.get('peak_bytes_in_use')}", flush=True)

    if _failures:
        print(f"chip_smoke: {len(_failures)} check(s) failed", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": len(jax.devices()),
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
