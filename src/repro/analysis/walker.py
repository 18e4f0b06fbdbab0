"""Jaxpr walking primitives shared by every analysis pass (DESIGN.md §13).

The contract auditor never executes a cell — every pass works on the
traced jaxpr.  This module owns the mechanics all of them share:

  * ``iter_eqns`` — depth-first equation iteration that recurses through
    every higher-order primitive (``scan``/``while``/``cond``/``jit``/
    ``custom_*``/``pallas_call``) by inspecting eqn params for nested
    jaxprs, so a pass never needs to know the param-name zoo;
  * ``count_pallas_calls`` — the launch counter (the generalisation of the
    walker that used to live privately in ``tests/test_step_fused.py``);
  * ``ancestor_roundtrips`` — a taint/dataflow pass that finds the HBM
    index round-trip the fused data path exists to remove: a ``gather``/
    ``scatter`` whose *index* operand derives from an integer output of a
    ``pallas_call`` (the ancestor vector leaving the chip and coming back
    as XLA gather indices).  Plain shape-indexing gathers with constant
    indices (e.g. ``key_to_seed``'s scalar picks) are NOT flagged — taint
    starts only at kernel outputs.

Higher-order invar mapping is positional and primitive-specific (jit is
1:1; scan is consts+carry+xs; while is cond_consts+body_consts+carry;
cond is index+operands); loop carries are iterated to a fixpoint, which
terminates because taint only grows and is bounded by the carry width.
"""

from __future__ import annotations

import dataclasses
from collections import Counter
from typing import Iterator, Optional, Union

from jax.extend import core as jex_core

import jax.numpy as jnp

JaxprLike = Union[jex_core.Jaxpr, jex_core.ClosedJaxpr]

#: Primitives that read HBM through an index vector — the round-trip shape.
GATHER_PRIM_PREFIXES = ("gather", "scatter", "take")


def unwrap(jaxpr: JaxprLike) -> jex_core.Jaxpr:
    """Accept either a ``ClosedJaxpr`` (what ``jax.make_jaxpr`` returns) or
    a bare ``Jaxpr`` and hand back the bare one."""
    if isinstance(jaxpr, jex_core.ClosedJaxpr):
        return jaxpr.jaxpr
    return jaxpr


def subjaxprs(eqn) -> Iterator[tuple[str, jex_core.Jaxpr]]:
    """Yield ``(param_name, jaxpr)`` for every nested jaxpr of one eqn."""

    def of_param(name, v):
        if isinstance(v, jex_core.ClosedJaxpr):
            yield name, v.jaxpr
        elif isinstance(v, jex_core.Jaxpr):
            yield name, v
        elif isinstance(v, (tuple, list)):
            for x in v:
                yield from of_param(name, x)

    for name, v in eqn.params.items():
        yield from of_param(name, v)


def iter_eqns(jaxpr: JaxprLike, *, into_kernels: bool = True, _path: str = ""):
    """Depth-first ``(eqn, path)`` iteration through nested jaxprs.

    ``path`` is a human-readable breadcrumb ("scan/jit") for diagnostics.
    ``into_kernels=False`` stops at ``pallas_call`` boundaries — kernel
    bodies address VMEM, so HBM-level passes must not look inside them.
    """
    for eqn in unwrap(jaxpr).eqns:
        yield eqn, _path
        if eqn.primitive.name == "pallas_call" and not into_kernels:
            continue
        child = f"{_path}/{eqn.primitive.name}" if _path else eqn.primitive.name
        for _, sub in subjaxprs(eqn):
            yield from iter_eqns(sub, into_kernels=into_kernels, _path=child)


def count_primitive(jaxpr: JaxprLike, name: str, *, into_kernels: bool = True) -> int:
    return sum(
        1 for eqn, _ in iter_eqns(jaxpr, into_kernels=into_kernels)
        if eqn.primitive.name == name
    )


def count_pallas_calls(jaxpr: JaxprLike) -> int:
    """Number of kernel launches the traced program performs (statically:
    a launch inside ``scan`` counts once — it is one launch per trace
    site, which is the contract DESIGN.md §12 states)."""
    return count_primitive(jaxpr, "pallas_call")


def pallas_call_eqns(jaxpr: JaxprLike) -> list[tuple]:
    """All ``pallas_call`` eqns with their breadcrumb paths."""
    return [
        (eqn, path) for eqn, path in iter_eqns(jaxpr)
        if eqn.primitive.name == "pallas_call"
    ]


def primitive_census(jaxpr: JaxprLike, *, into_kernels: bool = False) -> Counter:
    """Primitive-name histogram of the traced program (report payload)."""
    return Counter(
        eqn.primitive.name for eqn, _ in iter_eqns(jaxpr, into_kernels=into_kernels)
    )


# --------------------------------------------------------------- taint pass
@dataclasses.dataclass(frozen=True)
class Finding:
    """One diagnostic from a pass; ``code`` is the machine-readable id the
    contract table and the waiver list key on."""

    pass_name: str
    code: str
    where: str
    detail: str

    def as_dict(self):
        return dataclasses.asdict(self)

    def __str__(self):
        where = self.where or "<top>"
        return f"[{self.pass_name}:{self.code}] {where}: {self.detail}"


def _is_int(aval) -> bool:
    dtype = getattr(aval, "dtype", None)
    return dtype is not None and jnp.issubdtype(dtype, jnp.integer)


class _TaintScope:
    """Forward taint propagation through one (possibly nested) jaxpr.

    Taint sources are integer outputs of ``pallas_call`` (ancestor/index
    vectors leaving the kernel).  Propagation is conservative: any eqn with
    a tainted operand taints all its outputs.  Call-like primitives map
    taint positionally into their subjaxprs; loop carries run to fixpoint.
    """

    def __init__(self):
        self.findings: list[Finding] = []

    def run(self, jaxpr: jex_core.Jaxpr, tainted_in: frozenset[int], path: str = ""):
        """Returns the set of tainted *outvar positions* of ``jaxpr``."""
        tainted: set = set()
        for i, v in enumerate(jaxpr.invars):
            if i in tainted_in:
                tainted.add(v)

        def is_tainted(v):
            return (not isinstance(v, jex_core.Literal)) and v in tainted

        for eqn in jaxpr.eqns:
            name = eqn.primitive.name
            child = f"{path}/{name}" if path else name
            if name == "pallas_call":
                for ov in eqn.outvars:
                    if _is_int(ov.aval):
                        tainted.add(ov)
                continue
            if name.startswith(GATHER_PRIM_PREFIXES):
                # operand layout: (data, indices, ...updates) for both
                # gather and scatter variants — indices is invars[1].
                if len(eqn.invars) > 1 and is_tainted(eqn.invars[1]):
                    self.findings.append(
                        Finding(
                            "census",
                            "ancestor-roundtrip",
                            child,
                            f"{name} indexes HBM with indices derived from a "
                            "pallas_call output (ancestor round-trip)",
                        )
                    )
            out_taint = self._call_like(eqn, is_tainted, child)
            if out_taint is None:  # generic propagation
                if any(is_tainted(v) for v in eqn.invars):
                    out_taint = set(range(len(eqn.outvars)))
                else:
                    out_taint = set()
            for i in out_taint:
                tainted.add(eqn.outvars[i])

        return {i for i, v in enumerate(jaxpr.outvars) if is_tainted(v)}

    def _call_like(self, eqn, is_tainted, path) -> Optional[set]:
        """Map taint through a higher-order primitive; returns tainted
        outvar positions, or None if the primitive is not call-like."""
        name = eqn.primitive.name
        params = eqn.params
        in_taint = frozenset(
            i for i, v in enumerate(eqn.invars) if is_tainted(v)
        )

        if name == "scan":
            body = unwrap(params["jaxpr"])
            num_consts = params["num_consts"]
            num_carry = params["num_carry"]
            cur = set(in_taint)
            while True:  # carry feedback fixpoint (taint only grows)
                out = self.run(body, frozenset(cur), path)
                fed = {num_consts + i for i in out if i < num_carry}
                if fed <= cur:
                    break
                cur |= fed
            return out
        if name == "while":
            cond_n = params["cond_nconsts"]
            body_n = params["body_nconsts"]
            body = unwrap(params["body_jaxpr"])
            cond = unwrap(params["cond_jaxpr"])
            carry_in = frozenset(
                i - cond_n - body_n for i in in_taint if i >= cond_n + body_n
            )
            body_in = set(
                i - cond_n for i in in_taint if cond_n <= i < cond_n + body_n
            ) | {body_n + i for i in carry_in}
            while True:
                out = self.run(body, frozenset(body_in), path)
                fed = {body_n + i for i in out}
                if fed <= body_in:
                    break
                body_in |= fed
            cond_in = frozenset(i for i in in_taint if i < cond_n) | frozenset(
                cond_n + i - body_n for i in body_in if i >= body_n
            )
            self.run(cond, cond_in, path)  # findings only; no outvar mapping
            return out
        if name == "cond":
            branches = params["branches"]
            op_taint = frozenset(i - 1 for i in in_taint if i >= 1)
            out = set()
            for br in branches:
                out |= self.run(unwrap(br), op_taint, path)
            return out
        if name == "jit" or (
            name in ("closed_call", "core_call", "remat2", "checkpoint")
            and "jaxpr" in params
        ):
            # plain 1:1 call: eqn invars/outvars map positionally
            return self.run(unwrap(params["jaxpr"]), in_taint, path)
        if "call_jaxpr" in params:  # custom_jvp_call / custom_vjp_call / xla_call
            return self.run(unwrap(params["call_jaxpr"]), in_taint, path)
        return None


def ancestor_roundtrips(jaxpr: JaxprLike) -> list[Finding]:
    """Findings for every gather/scatter whose indices derive from a
    ``pallas_call`` integer output — the ancestors-through-HBM round-trip
    forbidden on the fused data path (DESIGN.md §11/§12)."""
    scope = _TaintScope()
    inner = unwrap(jaxpr)
    scope.run(inner, frozenset())
    return scope.findings
