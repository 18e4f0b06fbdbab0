"""Compiler rehearsal: the Pallas kernels compiled by Mosaic for a TPU v5e.

Nothing runs: the installed TPU compiler compiles for a chip that is
described, not attached, and refuses what the chip would refuse (lowering
gaps, VMEM overflow).  The topology is described inside a module fixture
and the persistent compilation cache is off around these compiles.  Each
Megopolis case must lower its kernel (``tpu_custom_call``); each other
family is a strict xfail naming today's refusal, so the change that makes
one compile sees it flip.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.spec import MegopolisSpec, spec_for_backend
from repro.pf.filter import ParticleFilter, run_filter
from repro.pf.models import bearings_only, ungm

ITERS = 32
THR = 0.5


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # no compiler logs outside the run
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, *shapes):
    return jax.jit(fn).lower(*shapes).compile()


def _shapes(sharding, *specs):
    return [jax.ShapeDtypeStruct(shape, dtype, sharding=sharding) for shape, dtype in specs]


def _state(lead, d):
    return lead if d == 1 else lead + (d,)


KEY = ((2,), jnp.uint32)


def _megopolis():
    return MegopolisSpec(num_iters=ITERS, segment=1024, backend="pallas").build()


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


def test_megopolis_call_compiles_at_2_22(one_chip):
    r = _megopolis()
    n = 1 << 22
    _assert_kernel(_compile(r, *_shapes(one_chip, KEY, ((n,), jnp.float32))))


@pytest.mark.parametrize("state_dim", [1, 4])
@pytest.mark.parametrize("entry", ["apply", "step"])
def test_megopolis_fused_compiles_at_2_20(entry, state_dim, one_chip):
    r = _megopolis()
    n = 1 << 20
    shapes = _shapes(one_chip, KEY, ((n,), jnp.float32),
                     (_state((n,), state_dim), jnp.float32))
    if entry == "apply":
        compiled = _compile(r.apply, *shapes)
    else:
        compiled = _compile(lambda k, lw, p: r.step(k, lw, p, THR), *shapes)
    _assert_kernel(compiled)


def test_megopolis_apply_compiles_at_2_22(one_chip):
    """The largest blocked launch: 4096 tiles, G at its cap, with the
    state's own blocks beside the weights'."""
    r = _megopolis()
    n = 1 << 22
    shapes = _shapes(one_chip, KEY, ((n,), jnp.float32), ((n,), jnp.float32))
    _assert_kernel(_compile(r.apply, *shapes))


@pytest.mark.parametrize("state_dim", [1, 4])
@pytest.mark.parametrize("entry", ["apply_rows", "step_rows"])
def test_megopolis_rows_compile_at_4x2_18(entry, state_dim, one_chip):
    r = _megopolis()
    s, n = 4, 1 << 18
    shapes = _shapes(one_chip, ((s, 2), jnp.uint32), ((s, n), jnp.float32),
                     (_state((s, n), state_dim), jnp.float32))
    if entry == "apply_rows":
        compiled = _compile(r.apply_rows, *shapes)
    else:
        compiled = _compile(lambda k, lw, p: r.step_rows(k, lw, p, THR), *shapes)
    _assert_kernel(compiled)


@pytest.mark.parametrize("ess_threshold", [None, THR], ids=["alg6", "conditional"])
def test_run_filter_compiles_at_2_20(ess_threshold, one_chip):
    """The main path: the paper's §7 UNGM filter as one jitted program."""
    pf = ParticleFilter(ungm(), 1 << 20, ess_threshold=ess_threshold,
                        resampler=MegopolisSpec(num_iters=ITERS, segment=1024,
                                                backend="pallas"))
    shapes = _shapes(one_chip, KEY, ((2,), jnp.float32))
    _assert_kernel(_compile(lambda k, z: run_filter(k, pf, z), *shapes))


def test_bearings_run_filter_compiles_at_2_20(one_chip):
    """A vector state through the main path: the 4-D bearings-only tracker,
    its ``[N, 4]`` particles packed to 8 planes around the apply kernel."""
    pf = ParticleFilter(bearings_only(), 1 << 20,
                        resampler=MegopolisSpec(num_iters=ITERS, segment=1024,
                                                backend="pallas"))
    shapes = _shapes(one_chip, KEY, ((2,), jnp.float32))
    compiled = _compile(lambda k, z: run_filter(k, pf, z), *shapes)
    _assert_kernel(compiled)
    assert compiled.out_info.shape == (2, 4)


# Today's Mosaic refusals for the other families (a random-access gather
# over the whole resident array; the prefix scan's cumsum).
_REFUSED = {
    "metropolis": "Only 2D gather is supported (in-kernel jnp.take)",
    "metropolis_c1": "Only 2D gather is supported (in-kernel jnp.take)",
    "metropolis_c2": "Only 2D gather is supported (in-kernel jnp.take)",
    "rejection": "Only 2D gather is supported (in-kernel jnp.take)",
    "multinomial": "Unimplemented primitive: cumsum",
    "systematic": "Unimplemented primitive: cumsum",
    "improved_systematic": "Unimplemented primitive: cumsum",
    "stratified": "Unimplemented primitive: cumsum",
    "residual": "Unimplemented primitive: cumsum",
}


@pytest.mark.parametrize(
    "name",
    [pytest.param(name, marks=pytest.mark.xfail(
        strict=True, raises=NotImplementedError, reason=why))
     for name, why in _REFUSED.items()],
)
def test_other_family_call_compiles(name, one_chip):
    r = spec_for_backend(name, "pallas", num_iters=ITERS).build()
    _assert_kernel(_compile(r, *_shapes(one_chip, KEY, ((1 << 16,), jnp.float32))))
