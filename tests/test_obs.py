"""Observability (DESIGN.md §15): StepStats, Telemetry, spans, sink.

Contract under test:

  1. **survivor count** — ``unique_ancestor_count`` on hand-built ancestor
     vectors (identity → N, collapse → 1, known duplicates), batched rows,
     and the no-scatter discipline the §13 census pass depends on.
  2. **StepStats plumbing** — ``stats_from_vector`` unpacks the kernel's
     f32[..., 4] SMEM row (single and batched) into the named record.
  3. **oracle parity** — the fused step's in-kernel stats equal the
     ``core.metrics`` host composition bitwise, both branches of the
     trigger, on the kernel lane.
  4. **telemetry neutrality** — every consumer (``run_filter``/``_bank``,
     ``run_smc_sampler``/``_bank``, ``smc_decode``) returns bit-identical
     primary outputs with telemetry on vs off, and the record's layout
     matches the estimate layout ([T] single, [S, T] banks).
  5. **with_ess shim** — the deprecated diagnostic still returns the old
     ``(estimates, ess_norm)`` pair bit-identically, warns, and refuses to
     combine with ``telemetry=True``.
  6. **spans + sink** — spans are always-on named scopes and metadata
     only: the jaxpr and the compiled program (metadata aside) are those of
     unscoped code (the structural gates depend on it); the filter's four
     ``pf/*`` stages reach the HLO ``op_name`` metadata; every Megopolis
     ``pallas_call`` carries its stable name; the JSONL sink round-trips
     events in order and stringifies rather than drops odd values.
"""

import json
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.metrics import (
    effective_sample_size,
    log_mean_weight,
    max_normalised_weight,
    unique_ancestor_count,
)
from repro.core.spec import spec_for_backend
from repro.obs import (
    JsonlSink,
    StepStats,
    Telemetry,
    dispatch_span,
    span,
    stats_from_vector,
)
from repro.pf import ParticleFilter, run_filter, run_filter_bank, ungm

N = 2048  # whole VMEM tiles — the pallas lanes require N % 1024 == 0


def _tree_equal(got, want):
    got_l, want_l = jax.tree.leaves(got), jax.tree.leaves(want)
    assert len(got_l) == len(want_l)
    for g, w in zip(got_l, want_l):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


# ------------------------------------------------------- 1. survivor count
def test_unique_ancestor_count_hand_built():
    n = 8
    assert int(unique_ancestor_count(jnp.arange(n))) == n  # identity
    assert int(unique_ancestor_count(jnp.full((n,), 3))) == 1  # collapse
    # known duplicates: {0, 1, 2, 3, 7} survive
    anc = jnp.array([0, 0, 1, 2, 3, 3, 3, 7], jnp.int32)
    assert int(unique_ancestor_count(anc)) == 5
    # order-independence: a permutation of the same multiset
    perm = jnp.array([7, 3, 0, 3, 2, 1, 0, 3], jnp.int32)
    assert int(unique_ancestor_count(perm)) == 5


def test_unique_ancestor_count_batched_rows():
    rows = jnp.stack([
        jnp.arange(16),
        jnp.zeros((16,), jnp.int32),
        jnp.repeat(jnp.arange(4), 4),
    ])
    np.testing.assert_array_equal(
        np.asarray(unique_ancestor_count(rows, axis=-1)), [16, 1, 4]
    )


def test_unique_ancestor_count_is_scatter_free():
    """The census pass (DESIGN.md §13) flags scatter-adds over
    kernel-tainted indices; the survivor count must stay on the sort-diff
    formulation so telemetry never trips it."""
    jaxpr = str(jax.make_jaxpr(unique_ancestor_count)(jnp.arange(32)))
    assert "scatter" not in jaxpr


# --------------------------------------------------- 2. StepStats plumbing
def test_stats_from_vector_unpacks_row():
    row = jnp.array([0.25, -1.5, 1.0, 0.75], jnp.float32)
    s = stats_from_vector(row, jnp.int32(17))
    assert isinstance(s, StepStats)
    assert float(s.ess_norm) == 0.25
    assert float(s.log_evidence_incr) == -1.5
    assert float(s.resampled) == 1.0
    assert float(s.max_weight) == 0.75
    assert int(s.survivors) == 17


def test_stats_from_vector_batched():
    rows = jnp.arange(8, dtype=jnp.float32).reshape(2, 4)
    s = stats_from_vector(rows, jnp.array([3, 5], jnp.int32))
    np.testing.assert_array_equal(np.asarray(s.ess_norm), [0.0, 4.0])
    np.testing.assert_array_equal(np.asarray(s.max_weight), [3.0, 7.0])
    np.testing.assert_array_equal(np.asarray(s.survivors), [3, 5])


# ------------------------------------------------------- 3. oracle parity
@pytest.mark.parametrize("name", ("megopolis", "systematic"))
@pytest.mark.parametrize("threshold", (0.995, 0.0))
def test_step_stats_match_metrics_oracle(name, threshold, base_key):
    """The kernel's SMEM stats row must equal the host composition from
    ``core.metrics`` bitwise — weight-side fields from the input
    log-weights, survivors from the launch's own ancestors."""
    r = spec_for_backend(name, "pallas_interpret", num_iters=8).build()
    lw = jax.random.normal(jax.random.PRNGKey(11), (N,)) * 1.5
    p = jax.random.normal(jax.random.PRNGKey(12), (N, 3))
    _, anc, stats = r.step(base_key, lw, p, threshold)
    ess_norm = effective_sample_size(lw) / jnp.float32(N)
    fired = bool(ess_norm < threshold)
    np.testing.assert_array_equal(np.asarray(stats.ess_norm),
                                  np.asarray(ess_norm))
    np.testing.assert_array_equal(np.asarray(stats.max_weight),
                                  np.asarray(max_normalised_weight(lw)))
    assert float(stats.resampled) == (1.0 if fired else 0.0)
    want_incr = log_mean_weight(lw) if fired else jnp.float32(0.0)
    np.testing.assert_array_equal(np.asarray(stats.log_evidence_incr),
                                  np.asarray(want_incr))
    want_survivors = len(np.unique(np.asarray(anc)))
    assert int(stats.survivors) == want_survivors
    if not fired:
        assert want_survivors == N  # identity ancestors on the skip branch


# ------------------------------------------- 4. telemetry neutrality (bit)
def _pf(backend, ess_threshold=None):
    return ParticleFilter(
        model=ungm(),
        num_particles=N,
        resampler=spec_for_backend("megopolis", backend, num_iters=8),
        ess_threshold=ess_threshold,
    )


@pytest.mark.parametrize("backend", ("reference", "pallas_interpret"))
@pytest.mark.parametrize("ess_threshold", (None, 0.5))
def test_run_filter_telemetry_is_neutral(backend, ess_threshold, base_key):
    pf = _pf(backend, ess_threshold)
    zs = jax.random.normal(jax.random.PRNGKey(21), (6,))
    ests_off = run_filter(base_key, pf, zs)
    ests_on, tel = run_filter(base_key, pf, zs, telemetry=True)
    np.testing.assert_array_equal(np.asarray(ests_on), np.asarray(ests_off))
    assert isinstance(tel, Telemetry) and tel.accept is None
    for leaf in jax.tree.leaves(tel.steps):
        assert leaf.shape == (6,)
    resampled = np.asarray(tel.steps.resampled)
    survivors = np.asarray(tel.steps.survivors)
    assert set(resampled.tolist()) <= {0.0, 1.0}
    assert (survivors >= 1).all() and (survivors <= N).all()
    if ess_threshold is None:
        assert (resampled == 1.0).all()  # Alg. 6 resamples every step
    else:
        # a skipped resample leaves the identity ancestors: survivors == N
        assert (survivors[resampled == 0.0] == N).all()


def test_run_filter_bank_telemetry_is_neutral(base_key):
    pf = _pf("reference", ess_threshold=0.5)
    zs = jax.random.normal(jax.random.PRNGKey(22), (3, 5))
    ests_off = run_filter_bank(base_key, pf, zs)
    ests_on, tel = run_filter_bank(base_key, pf, zs, telemetry=True)
    np.testing.assert_array_equal(np.asarray(ests_on), np.asarray(ests_off))
    for leaf in jax.tree.leaves(tel.steps):
        assert leaf.shape == (3, 5)  # [S, T] — the estimate layout
    # row s of the bank record is the single filter's record (§4 contract)
    from repro.core.resamplers.batched import split_batch_keys

    keys = split_batch_keys(base_key, 3)
    for s in range(3):
        _, tel_s = run_filter(keys[s], pf, zs[s], telemetry=True)
        _tree_equal(jax.tree.map(lambda f: f[s], tel.steps), tel_s.steps)


def test_run_smc_sampler_telemetry_is_neutral(base_key):
    from repro.ais import SMCSamplerConfig, isotropic_gaussian, run_smc_sampler

    target = isotropic_gaussian(dim=2)
    cfg = SMCSamplerConfig(num_particles=256, num_temps=6, num_iters=4)
    out_off = run_smc_sampler(base_key, target, cfg)
    out_on, tel = run_smc_sampler(base_key, target, cfg, telemetry=True)
    _tree_equal(out_on, out_off)
    # the record is the scan's own values, re-exposed
    np.testing.assert_array_equal(np.asarray(tel.betas),
                                  np.asarray(out_off["betas"]))
    np.testing.assert_array_equal(np.asarray(tel.accept),
                                  np.asarray(out_off["accept"]))
    np.testing.assert_array_equal(np.asarray(tel.steps.ess_norm),
                                  np.asarray(out_off["ess"]))
    assert int(np.asarray(tel.steps.resampled).sum()) == int(
        out_off["num_resamples"]
    )


def test_run_smc_sampler_bank_telemetry_is_neutral(base_key):
    from repro.ais import (
        SMCSamplerConfig,
        isotropic_gaussian,
        run_smc_sampler_bank,
    )

    target = isotropic_gaussian(dim=2)
    cfg = SMCSamplerConfig(num_particles=256, num_temps=5, num_iters=4)
    out_off = run_smc_sampler_bank(base_key, target, cfg, num_scenarios=2)
    out_on, tel = run_smc_sampler_bank(
        base_key, target, cfg, num_scenarios=2, telemetry=True
    )
    _tree_equal(out_on, out_off)
    for leaf in jax.tree.leaves(tel.steps):
        assert leaf.shape == (2, 5)  # [S, T], matching the dict layout
    np.testing.assert_array_equal(np.asarray(tel.betas),
                                  np.asarray(out_off["betas"]))
    np.testing.assert_array_equal(np.asarray(tel.accept),
                                  np.asarray(out_off["accept"]))


def test_smc_decode_telemetry_is_neutral():
    import dataclasses

    from repro.configs import get_arch
    from repro.models import init_params, prefill
    from repro.smc import SMCDecodeConfig, smc_decode

    cfg = dataclasses.replace(
        get_arch("qwen3-0.6b").smoke, dtype=jnp.float32, remat=False
    )
    key = jax.random.PRNGKey(5)
    params = init_params(key, cfg)
    prompts = jax.random.randint(
        jax.random.fold_in(key, 1), (8, 4), 0, cfg.vocab_size, jnp.int32
    )
    new = 5
    smc = SMCDecodeConfig(num_particles=8, max_new_tokens=new,
                          target_temp=0.5, ess_threshold=0.9)
    _, caches = prefill(params, cfg, prompts, max_seq=4 + new)
    args = (params, cfg, smc, caches, prompts[:, -1], 4,
            jax.random.fold_in(key, 2))
    tokens_off, log_w_off, stats_off = smc_decode(*args)
    tokens_on, log_w_on, stats_on, tel = smc_decode(*args, telemetry=True)
    _tree_equal((tokens_on, log_w_on, stats_on),
                (tokens_off, log_w_off, stats_off))
    for leaf in jax.tree.leaves(tel.steps):
        assert leaf.shape == (new,)
    assert int(np.asarray(tel.steps.resampled).sum()) == int(
        stats_off["num_resamples"]
    )


# ----------------------------------------------------- 5. the with_ess shim
def test_with_ess_shim_warns_and_matches_telemetry(base_key):
    pf = _pf("reference", ess_threshold=0.5)
    zs = jax.random.normal(jax.random.PRNGKey(23), (4,))
    with pytest.warns(DeprecationWarning, match="telemetry=True"):
        ests_old, ess_old = run_filter(base_key, pf, zs, with_ess=True)
    ests_new, tel = run_filter(base_key, pf, zs, telemetry=True)
    np.testing.assert_array_equal(np.asarray(ests_old), np.asarray(ests_new))
    np.testing.assert_array_equal(np.asarray(ess_old),
                                  np.asarray(tel.steps.ess_norm))
    with pytest.raises(ValueError, match="not both"):
        run_filter(base_key, pf, zs, telemetry=True, with_ess=True)


# --------------------------------------------------------- 6. spans + sink
def test_span_disabled_is_trace_identity():
    """Spans are always-on named scopes that leave the jaxpr untouched — the
    §12/§13 identical-program gates compare traces across dispatches that
    open spans against compositions that don't."""

    def plain(x):
        return jnp.sum(x * 2.0)

    def spanned(x):
        with dispatch_span("megopolis", "reference", "step"), span("pf/estimate"):
            return jnp.sum(x * 2.0)

    x = jnp.arange(8, dtype=jnp.float32)
    assert str(jax.make_jaxpr(plain)(x)) == str(jax.make_jaxpr(spanned)(x))
    np.testing.assert_array_equal(np.asarray(plain(x)),
                                  np.asarray(spanned(x)))
    # ...yet the scope is on: it names the traced equations
    eqn = jax.make_jaxpr(spanned)(x).jaxpr.eqns[0]
    assert str(eqn.source_info.name_stack) == "megopolis/reference/step/float32/pf/estimate"


STAGES = ("pf/predict", "pf/update", "pf/resample", "pf/estimate")


def _filter_entry(entry):
    """(jittable fn, args) of one filter entry at a small size."""
    model = ungm()
    key = jax.random.PRNGKey(0)
    spec = spec_for_backend("megopolis", "xla", num_iters=4)
    x, z, t = jnp.zeros((N,), jnp.float32), jnp.float32(1.0), jnp.float32(1.0)
    if entry == "step":
        pf = ParticleFilter(model, N, resampler=spec)
        return pf.step, (key, x, z, t)
    if entry == "step_conditional":
        pf = ParticleFilter(model, N, resampler=spec, ess_threshold=0.5)
        return pf.step_conditional, (key, x, jnp.zeros_like(x), z, t)
    pf = ParticleFilter(model, N, resampler=spec, ess_threshold=0.5)
    return (lambda k, zs: run_filter_bank(k, pf, zs)), (key, jnp.ones((2, 3), jnp.float32))


@pytest.mark.parametrize("entry", ("step", "step_conditional", "run_filter_bank"))
def test_filter_stages_reach_hlo_op_names(entry):
    fn, args = _filter_entry(entry)
    hlo = jax.jit(fn).lower(*args).as_text(dialect="hlo", debug_info=True)
    op_names = set(re.findall(r'op_name="([^"]*)"', hlo))
    for stage in STAGES:
        assert any(re.match(f"(.*/)?{stage}/", n) for n in op_names), (stage, sorted(op_names))
    # the resampler's own dispatch span nests inside its stage
    assert any(re.match("(.*/)?pf/resample/megopolis/xla/", n) for n in op_names)


def _strip_metadata(hlo_text):
    """The program alone: no ``metadata={...}``, no source-location tables."""
    lines = [ln for ln in hlo_text.splitlines()
             if not re.match(r"(FileNames|FunctionNames|FileLocations|StackFrames)$|\d+ ", ln)]
    return re.sub(r",? metadata=\{[^}]*\}", "", "\n".join(lines))


def test_scopes_leave_the_compiled_program_unchanged():
    """A scoped Alg. 6 step and the same statements unscoped compile to
    the same HLO once ``metadata={...}`` is stripped."""
    model = ungm()
    pf = ParticleFilter(model, N, resampler=spec_for_backend("megopolis", "xla", num_iters=4))

    def step(key, particles, z, t):  # ParticleFilter.step without its scopes
        k_pred, k_res = jax.random.split(key)
        x = model.transition(k_pred, particles, t)
        w = model.likelihood(z, x, t)
        x_bar, ancestors = pf._built.apply(k_res, w, x)
        return x_bar, jnp.mean(x_bar), w, ancestors

    args = (jax.random.PRNGKey(0), jnp.zeros((N,), jnp.float32), jnp.float32(1.0),
            jnp.float32(1.0))
    scoped = jax.jit(pf.step).lower(*args).compile().as_text()
    unscoped = jax.jit(step).lower(*args).compile().as_text()
    assert "pf/predict" in scoped and "pf/predict" not in unscoped
    assert _strip_metadata(scoped) == _strip_metadata(unscoped)


MEGOPOLIS_KERNELS = {
    "single": "megopolis_pallas",
    "batch": "megopolis_pallas_batch",
    "apply": "megopolis_pallas_apply",
    "apply_rows": "megopolis_pallas_apply_rows",
    "step": "megopolis_pallas_step",
    "step_rows": "megopolis_pallas_step_rows",
}


def _pallas_names(jaxpr):
    """``params["name"]`` of every ``pallas_call`` equation, nested jaxprs
    included."""
    names = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            names.append(eqn.params["name"])
        for sub in jax.core.jaxprs_in_params(eqn.params):
            names += _pallas_names(sub)
    return names


@pytest.mark.parametrize("entry", sorted(MEGOPOLIS_KERNELS))
def test_megopolis_pallas_calls_carry_stable_names(entry):
    r = spec_for_backend("megopolis", "pallas_interpret", num_iters=4).build()
    key = jax.random.PRNGKey(0)
    w, x = jnp.ones((N,), jnp.float32), jnp.zeros((N,), jnp.float32)
    keys = jax.random.split(key, 2)
    call = {
        "single": lambda: (r, (key, w)),
        "batch": lambda: (r.batch, (key, jnp.stack([w, w]))),
        "apply": lambda: (r.apply, (key, w, x)),
        "apply_rows": lambda: (r.apply_rows, (keys, jnp.stack([w, w]), jnp.stack([x, x]))),
        "step": lambda: (r.step, (key, jnp.zeros_like(w), x, 0.5)),
        "step_rows": lambda: (r.step_rows, (keys, jnp.zeros((2, N)), jnp.stack([x, x]), 0.5)),
    }[entry]
    fn, args = call()
    names = _pallas_names(jax.make_jaxpr(fn)(*args).jaxpr)
    assert names == [MEGOPOLIS_KERNELS[entry]]
    assert all(n.startswith("megopolis_pallas") for n in names)  # the benchmark's kernel_pattern


def test_jsonl_sink_round_trips_in_order(tmp_path):
    path = tmp_path / "sub" / "events.jsonl"  # parent dir auto-created
    sink = JsonlSink(str(path))
    sink.emit("run_start", git_sha="abc1234")
    sink.emit("suite_end", suite="step", ok=True, wall_s=1.25)
    sink.emit("odd_value", arr=jnp.arange(3))  # stringified, never dropped
    lines = [json.loads(l) for l in path.read_text().splitlines()]
    assert [r["event"] for r in lines] == ["run_start", "suite_end", "odd_value"]
    assert lines[0]["git_sha"] == "abc1234"
    assert lines[1]["ok"] is True and lines[1]["wall_s"] == 1.25
    assert isinstance(lines[2]["arr"], str)
    assert all("ts" in r for r in lines)
