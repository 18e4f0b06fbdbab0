"""RNG-discipline lint (DESIGN.md §13, pass 3).

The Murray–Lee–Jacob survey's warning is that parallel resamplers fail
*silently* through RNG misuse — correlated streams bias the resampled
population without any crash.  PR 6 guarded one instance by hand ("the key
is consumed in BOTH branches"); this pass mechanises the whole class at
the jaxpr level:

  * **key-reuse** — one PRNG key var consumed by two or more random
    primitives (``random_bits``/``random_split``/``random_fold_in``).
    Multiple ``fold_in``s of the same key are exempt unless two data
    operands are provably equal (same var / equal literals) — deriving
    subkeys by folding distinct data is the documented idiom.  Old-style
    raw ``uint32[2]`` keys are tracked through their ``random_wrap``
    lifts and through call boundaries, so wrapping the same raw key twice
    (e.g. two ``jax.random`` calls on the same key) is still reuse.
  * **branch-drop** — a key operand of ``lax.cond`` consumed in one branch
    but not even used in another: whether the stream advances becomes
    data-dependent, so downstream draws diverge between branches (the §12
    rule is that the key must be consumed in BOTH branches).
  * **loop-invariant-key** — a loop-constant key consumed by ``bits``/
    ``split`` inside a ``scan``/``while`` body (or ``fold_in`` with
    loop-invariant data): every iteration draws the SAME randoms.

Consumption counts through call boundaries: passing a key into a ``jit``/
``scan``/``cond`` whose body consumes it is ONE consumption at the caller
(reuse *inside* the callee is reported when its own scope is linted).
Data operands of ``fold_in`` are translated across the boundary so the
distinct-data exemption survives jitted helpers; data that cannot be
resolved to a caller var or literal is treated permissively as distinct.
"""

from __future__ import annotations

from typing import Optional

from jax.extend import core as jex_core

import jax.dtypes
import jax.numpy as jnp

from repro.analysis.walker import Finding, JaxprLike, subjaxprs, unwrap

#: Primitives that advance/consume a PRNG key (key is operand 0).
CONSUMING = ("random_bits", "random_split", "random_fold_in", "random_gamma")

#: A consumption descriptor: (primitive kind, fold_in data id or None).
#: Data ids are ("lit", repr) | ("var", Var) | ("invar", pos) | None.
Desc = tuple[str, Optional[tuple]]


def _aval(v):
    return getattr(v, "aval", None)


def _is_key_dtype(aval) -> bool:
    dtype = getattr(aval, "dtype", None)
    return dtype is not None and jax.dtypes.issubdtype(dtype, jax.dtypes.prng_key)


def _is_single_key(v) -> bool:
    """A scalar typed key — the unit whose reuse the lint tracks.  Keys
    with leading batch dims are arrays of distinct keys; consuming two
    different slices of one is not reuse, and each slice is re-tracked."""
    aval = _aval(v)
    return aval is not None and _is_key_dtype(aval) and getattr(aval, "shape", None) == ()


def _is_raw_key(v) -> bool:
    """Old-style ``uint32[2]`` key candidate.  Cheap shape test only — a
    non-key uint32 pair simply collects zero consumers and is skipped."""
    aval = _aval(v)
    return (
        aval is not None
        and getattr(aval, "shape", None) == (2,)
        and getattr(aval, "dtype", None) == jnp.dtype(jnp.uint32)
    )


def _is_keyish(v) -> bool:
    return not isinstance(v, jex_core.Literal) and (_is_single_key(v) or _is_raw_key(v))


def _call_invar_maps(eqn):
    """Yield ``(subjaxpr, caller_pos -> callee_pos)`` for a call-like eqn,
    mirroring the positional conventions in ``walker._TaintScope``."""
    name = eqn.primitive.name
    params = eqn.params
    n = len(eqn.invars)
    if name == "scan":
        yield unwrap(params["jaxpr"]), {i: i for i in range(n)}
    elif name == "while":
        cond_n = params["cond_nconsts"]
        body_n = params["body_nconsts"]
        yield unwrap(params["cond_jaxpr"]), {
            **{i: i for i in range(cond_n)},
            **{cond_n + body_n + i: cond_n + i for i in range(n - cond_n - body_n)},
        }
        yield unwrap(params["body_jaxpr"]), {cond_n + i: i for i in range(n - cond_n)}
    elif name == "cond":
        for br in params["branches"]:
            yield unwrap(br), {i: i - 1 for i in range(1, n)}
    elif name == "jit" and "jaxpr" in params:
        yield unwrap(params["jaxpr"]), {i: i for i in range(n)}
    elif "call_jaxpr" in params:
        yield unwrap(params["call_jaxpr"]), {i: i for i in range(n)}


def _lit_id(v) -> Optional[tuple]:
    if isinstance(v, jex_core.Literal):
        return ("lit", repr(v.val))
    return None


def _direct_desc(eqn, jaxpr) -> Desc:
    """Descriptor for a direct consuming primitive; fold_in data resolved
    to a literal, an invar position of ``jaxpr``, a local var, or None."""
    name = eqn.primitive.name
    if name != "random_fold_in" or len(eqn.invars) < 2:
        return (name, None)
    data = eqn.invars[1]
    lit = _lit_id(data)
    if lit is not None:
        return (name, lit)
    for j, iv in enumerate(jaxpr.invars):
        if iv is data:
            return (name, ("invar", j))
    return (name, ("var", data))


def _invar_descs(jaxpr, pos: int, memo: dict) -> list[Desc]:
    """Consumption descriptors for invar ``pos`` of ``jaxpr``, with fold_in
    data ids expressed relative to ``jaxpr``'s own invars."""
    key = (id(jaxpr), pos)
    if key in memo:
        return memo[key]
    memo[key] = []  # cycle guard
    descs: list[Desc] = []
    for _, desc in _var_consumers(jaxpr, jaxpr.invars[pos], memo):
        # local ("var", v) data can't be translated past this scope
        kind, data = desc
        if data is not None and data[0] == "var":
            data = None
        descs.append((kind, data))
    memo[key] = descs
    return descs


def _collapse_call(eqn, jaxpr, positions, memo) -> Optional[Desc]:
    """One descriptor for a call-like eqn that consumes the key passed at
    ``positions`` (an eqn executes once, so it is ONE consumption; reuse
    inside the callee is reported when that scope is linted)."""
    sub_descs: list[Desc] = []
    for sub, posmap in _call_invar_maps(eqn):
        inv = {callee: caller for caller, callee in posmap.items()}
        for i in positions:
            if i not in posmap:
                continue
            for kind, data in _invar_descs(sub, posmap[i], memo):
                if data is not None and data[0] == "invar":
                    caller_pos = inv.get(data[1])
                    src = eqn.invars[caller_pos] if caller_pos is not None else None
                    if src is None:
                        data = None
                    elif isinstance(src, jex_core.Literal):
                        data = _lit_id(src)
                    else:
                        data = ("var", src)
                sub_descs.append((kind, data))
    if not sub_descs:
        return None
    if len(sub_descs) == 1:
        return sub_descs[0]
    kinds = {k for k, _ in sub_descs}
    if kinds == {"random_fold_in"}:
        datas = {d for _, d in sub_descs if d is not None}
        return ("random_fold_in", datas.pop() if len(datas) == 1 else None)
    return (eqn.primitive.name, None)


def _var_consumers(jaxpr, var, memo) -> list[tuple[int, Desc]]:
    """All consumption events of ``var`` in this scope, as ``(eqn_id,
    descriptor)``; follows ``random_wrap`` lifts as aliases."""
    out: list[tuple[int, Desc]] = []
    for eqn in jaxpr.eqns:
        positions = [i for i, v in enumerate(eqn.invars) if v is var]
        if not positions:
            continue
        name = eqn.primitive.name
        if name == "random_wrap":
            out.extend(_var_consumers(jaxpr, eqn.outvars[0], memo))
        elif name in CONSUMING and positions[0] == 0:
            out.append((id(eqn), _direct_desc(eqn, jaxpr)))
        else:
            desc = _collapse_call(eqn, jaxpr, positions, memo)
            if desc is not None:
                out.append((id(eqn), desc))
    return out


def _is_violation(descs: list[Desc]) -> bool:
    """>=2 consumptions violate unless all are fold_in with no provably
    equal data (unresolvable data is permissively assumed distinct)."""
    if len(descs) < 2:
        return False
    if any(kind != "random_fold_in" for kind, _ in descs):
        return True
    seen = set()
    for _, data in descs:
        if data is not None and data in seen:
            return True
        if data is not None:
            seen.add(data)
    return False


def _fmt(descs: list[Desc]) -> str:
    return ", ".join(sorted(kind for kind, _ in descs))


def _lint_scope(jaxpr, path, memo, findings, seen):
    tracked = []
    for v in jaxpr.invars:
        if _is_keyish(v):
            tracked.append(v)
    for eqn in jaxpr.eqns:
        for v in eqn.outvars:
            if _is_keyish(v):
                tracked.append(v)

    for v in tracked:
        events = _var_consumers(jaxpr, v, memo)
        if _is_violation([d for _, d in events]):
            dedupe = (path, frozenset(eid for eid, _ in events))
            if dedupe in seen:  # raw key + its wrap lift share consumers
                continue
            seen.add(dedupe)
            findings.append(
                Finding(
                    "rng",
                    "key-reuse",
                    path,
                    f"PRNG key {v} consumed by {len(events)} random "
                    f"primitives ({_fmt([d for _, d in events])})",
                )
            )

    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        child = f"{path}/{name}" if path else name
        if name == "cond":
            _lint_cond_branches(eqn, child, memo, findings)
        if name in ("scan", "while"):
            _lint_loop_keys(eqn, child, memo, findings)
        for _, sub in subjaxprs(eqn):
            _lint_scope(sub, child, memo, findings, seen)


def _lint_cond_branches(eqn, path, memo, findings):
    branches = [unwrap(b) for b in eqn.params["branches"]]
    for i, op in enumerate(eqn.invars[1:]):
        if not _is_keyish(op):
            continue
        consumed = [bool(_invar_descs(b, i, memo)) for b in branches]
        used = [
            any(any(iv is b.invars[i] for iv in e.invars) for e in b.eqns)
            or any(ov is b.invars[i] for ov in b.outvars)
            for b in branches
        ]
        if any(consumed) and not all(used):
            findings.append(
                Finding(
                    "rng",
                    "branch-drop",
                    path,
                    f"cond operand {i} is a PRNG key consumed in "
                    f"{sum(consumed)}/{len(branches)} branches but unused in "
                    f"{len(used) - sum(used)} — streams diverge across the branch",
                )
            )


def _loop_varying_vars(body, const_count: int) -> set:
    """Vars in a loop body derived from carry/xs (change per iteration)."""
    varying = set(body.invars[const_count:])
    for eqn in body.eqns:
        if any(
            not isinstance(v, jex_core.Literal) and v in varying for v in eqn.invars
        ):
            varying.update(eqn.outvars)
    return varying


def _lint_loop_keys(eqn, path, memo, findings):
    if eqn.primitive.name == "scan":
        body = unwrap(eqn.params["jaxpr"])
        const_count = eqn.params["num_consts"]
    else:
        body = unwrap(eqn.params["body_jaxpr"])
        const_count = eqn.params["body_nconsts"]
    varying = _loop_varying_vars(body, const_count)
    for pos in range(const_count):
        var = body.invars[pos]
        if not _is_keyish(var):
            continue
        for _, (kind, data) in _var_consumers(body, var, memo):
            if kind == "random_fold_in":
                if data is None:  # unresolvable data: assume per-iteration
                    continue
                if data[0] == "var" and data[1] in varying:
                    continue
                if data[0] == "invar" and body.invars[data[1]] in varying:
                    continue
            findings.append(
                Finding(
                    "rng",
                    "loop-invariant-key",
                    path,
                    f"loop-constant key {var} consumed by {kind} inside the "
                    "loop body — every iteration draws the same randoms",
                )
            )


def rng_findings(jaxpr: JaxprLike) -> list[Finding]:
    """Run the full RNG lint over a traced program."""
    findings: list[Finding] = []
    memo: dict = {}
    seen: set = set()
    _lint_scope(unwrap(jaxpr), "", memo, findings, seen)
    return findings
