"""The names the program puts on its work, read from the profiler trace.

``tracing.read_xplane`` reads the trace through ``jax.profiler.ProfileData``,
which gives each event a name and a time but not its metadata, and it keeps
only the benchmark's own host spans. This module reads the same
``.xplane.pb`` for what that leaves out:

* ``device_op_scopes``: the ``tf_op`` of each entry of ``device_ops``, in
  the same order: the op's JAX name stack, e.g.
  ``jit(step)/pf/resample/megopolis/pallas/apply/float32/jit(megopolis_pallas_fused)/megopolis_pallas_apply/pallas_call``;
* ``host_events``: ``[name, start_ns, end_ns]`` of every host event that is
  not a ``bench/`` span, from every host line: the runtime's own events
  (``PjitFunction(step)``, ``DevicePut``, ``PJRT_LoadedExecutable_Execute``,
  ...), on the same clock as the device ops.

It decodes the few ``xplane.proto`` messages it needs with a descriptor
built here (``google.protobuf``; TensorFlow is not imported). Each metric
reader calls ``load(ctx)``; a trace is read once per run, and reading it
prints the ``trace idle_by_host_event`` line on standard error.

A device op belongs to the filter stage named by the FIRST ``pf/<stage>``
segment of its name stack: that outer prefix is the caller's, while what
follows an inner ``jit(...)`` may come from an earlier trace of a cached
helper. An op of the configuration's kernel (``kernel_pattern``) is the
kernel's whatever its scope; the others partition ``model_ms``.
"""

from __future__ import annotations

import functools
import re
import sys
from pathlib import Path

import tracing

STAGE_RX = re.compile(r"(?:^|/)pf/(predict|update|resample|estimate)(?:/|$)")
TF_OP = "tf_op"

# xplane.proto, the fields read here: {message: [(field, number, type, message)]}.
# A map<int64, M> is read as its wire form, a repeated {key = 1, value = 2} entry.
_XPLANE = {
    "XSpace": [("planes", 1, "message", "XPlane")],
    "XPlane": [("name", 2, "string", None), ("lines", 3, "message", "XLine"),
               ("event_metadata", 4, "message", "EventMetadataEntry"),
               ("stat_metadata", 5, "message", "StatMetadataEntry")],
    "EventMetadataEntry": [("key", 1, "int64", None), ("value", 2, "message", "XEventMetadata")],
    "StatMetadataEntry": [("key", 1, "int64", None), ("value", 2, "message", "XStatMetadata")],
    "XLine": [("name", 2, "string", None), ("timestamp_ns", 3, "int64", None),
              ("events", 4, "message", "XEvent")],
    "XEvent": [("metadata_id", 1, "int64", None), ("offset_ps", 2, "int64", None),
               ("duration_ps", 3, "int64", None)],
    "XStat": [("metadata_id", 1, "int64", None), ("str_value", 5, "string", None),
              ("ref_value", 7, "uint64", None)],
    "XEventMetadata": [("name", 2, "string", None), ("stats", 5, "message", "XStat")],
    "XStatMetadata": [("name", 2, "string", None)],
}
_REPEATED = {"planes", "lines", "event_metadata", "stat_metadata", "events", "stats"}


@functools.cache
def _xspace_class():
    from google.protobuf import descriptor_pb2, descriptor_pool, message_factory

    fdp = descriptor_pb2.FileDescriptorProto(name="bench_xplane.proto", package="bench_xplane",
                                             syntax="proto3")
    F = descriptor_pb2.FieldDescriptorProto
    for msg, fields in _XPLANE.items():
        m = fdp.message_type.add(name=msg)
        for name, number, kind, sub in fields:
            f = m.field.add(name=name, number=number,
                            type=getattr(F, "TYPE_" + kind.upper()),
                            label=F.LABEL_REPEATED if name in _REPEATED else F.LABEL_OPTIONAL)
            if sub:
                f.type_name = ".bench_xplane." + sub
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fdp)
    return message_factory.GetMessageClass(pool.FindMessageTypeByName("bench_xplane.XSpace"))


def _str_stat(stat, stat_names) -> str:
    """A string stat's value: inline, or a reference to a stat metadata's name."""
    return stat.str_value or stat_names.get(stat.ref_value, "")


def read_names(path) -> dict:
    """``{"device_ops", "device_op_scopes", "host_events", "window"}`` of one
    ``.xplane.pb``; ``device_ops`` and ``window`` equal ``read_xplane``'s."""
    space = _xspace_class()()
    space.ParseFromString(Path(path).read_bytes())
    ops, host, window = [], [], None
    for plane in space.planes:
        device = bool(tracing.DEVICE_PLANE.match(plane.name))
        if not device and not plane.name.startswith("/host:"):
            continue
        stat_names = {e.key: e.value.name for e in plane.stat_metadata}
        tf_op_ids = {k for k, v in stat_names.items() if v == TF_OP}
        meta = {}
        for e in plane.event_metadata:
            scope = ""
            for st in e.value.stats:
                if st.metadata_id in tf_op_ids:
                    # "<name stack>:<op type>"; JAX leaves the type empty
                    scope = _str_stat(st, stat_names).rsplit(":", 1)[0]
            meta[e.key] = (e.value.name, scope)
        for line in plane.lines:
            if device and line.name != tracing.OP_LINE:
                continue
            for ev in line.events:
                name, scope = meta.get(ev.metadata_id, ("", ""))
                # whole nanoseconds, as ProfileData gives them
                start = float(line.timestamp_ns + ev.offset_ps // 1000)
                end = start + ev.duration_ps // 1000
                if device:
                    ops.append([name.split(" = ", 1)[0], start, end, scope])
                elif name == tracing.SPAN_PREFIX + "window":
                    window = window or [start, end]
                elif not name.startswith(tracing.SPAN_PREFIX):
                    host.append([name, start, end])
    ops.sort(key=lambda e: e[1])
    return {"device_ops": [o[:3] for o in ops], "device_op_scopes": [o[3] for o in ops],
            "host_events": sorted(host, key=lambda e: e[1]), "window": window}


def _trace_dirs():
    """Where ``run.py`` writes the window's trace: its ``TRACE_DIR``, run as
    the script or imported."""
    dirs = [getattr(sys.modules.get(m), "TRACE_DIR", None) for m in ("__main__", "run")]
    return [Path(d) for d in dirs if d is not None]


def find(trace) -> dict | None:
    """``read_names`` of the trace file that ``trace`` (``read_xplane``'s
    dict) was read from: the newest ``.xplane.pb`` whose window and device
    ops are the same. None where there is none."""
    files = {f for d in _trace_dirs() if d.is_dir() for f in d.rglob("*.xplane.pb")}
    for f in sorted(files, key=lambda f: f.stat().st_mtime, reverse=True):
        names = read_names(f)
        if names["window"] == trace["window"] and names["device_ops"] == trace["device_ops"]:
            return names
    return None


def load(ctx) -> dict | None:
    """``{"device_op_scopes", "host_events"}`` for ``ctx.trace``: its own
    keys where it holds them (a hand-made trace), else read from its file,
    once per run (kept on ``ctx``). None where there is no device trace to
    read."""
    trace = ctx.trace
    if trace is None or not trace["device_ops"]:
        return None
    if "device_op_scopes" in trace:
        return trace
    if not hasattr(ctx, "trace_names"):
        ctx.trace_names = find(trace)
        if ctx.trace_names is not None:
            print(f"trace idle_by_host_event {idle_by_host_event({**trace, **ctx.trace_names})}",
                  file=sys.stderr, flush=True)
    return ctx.trace_names


def stage_ns(trace, names, kernel_pattern) -> dict | None:
    """Device self time in the window, in ns, of the ops that are not the
    kernel's, split by stage: ``predict``, ``update``, ``estimate``,
    ``resample_glue`` (under ``pf/resample``) and ``unscoped`` (under no
    ``pf/`` scope). The five add up to ``op_time_ns(kernel_pattern,
    match=False)``. None where no op carries a stage: a program without
    the scopes."""
    scopes = names["device_op_scopes"]
    stage = [m.group(1) if (m := STAGE_RX.search(s)) else None for s in scopes]
    if not any(stage):
        return None
    kernel = re.compile(kernel_pattern)
    ops = [[i, s, e] for i, (_, s, e) in enumerate(trace["device_ops"])]
    out = dict.fromkeys(("predict", "update", "estimate", "resample_glue", "unscoped"), 0)
    for i, t in tracing.self_times(tracing.clip(ops, trace["window"])):
        if kernel.search(trace["device_ops"][i][0]):
            continue
        key = {None: "unscoped", "resample": "resample_glue"}.get(stage[i], stage[i])
        out[key] += t
    return out


def host_event_ns(trace, names, event: str, span: str = "bench/dispatch") -> float | None:
    """Host time of the runtime event ``event`` inside the benchmark's
    ``span`` spans of the window, per span, in ns: the union of the event's
    intervals (over every host line) clipped to each span. None where the
    window holds no such span."""
    spans = [s for s in tracing.clip(trace["host_spans"], trace["window"]) if s[0] == span]
    if not spans:
        return None
    busy = tracing.busy_intervals([e for e in names["host_events"] if e[0] == event])
    total, j = 0.0, 0
    for _, lo, hi in sorted(spans, key=lambda s: s[1]):
        while j < len(busy) and busy[j][1] <= lo:
            j += 1
        k = j
        while k < len(busy) and busy[k][0] < hi:
            total += min(busy[k][1], hi) - max(busy[k][0], lo)
            k += 1
    return total / len(spans)


def idle_by_host_event(trace, k=10):
    """``[bench span, runtime event, seconds]`` of the k longest
    device-idle gaps of the window: the gaps of ``tracing.idle_gaps``, each
    also labelled by the innermost runtime host event at its middle."""
    by_span = tracing.idle_gaps(trace, k)
    by_event = tracing.idle_gaps({**trace, "host_spans": trace["host_events"]}, k)
    return [[s, e, t] for (s, t), (e, _) in zip(by_span, by_event)]


def stage_ms(ctx, stage: str) -> float | None:
    """A ``stage_ns`` entry per filter step of the window, in ms."""
    names = load(ctx)
    if names is None or not ctx.window.steps:
        return None
    split = stage_ns(ctx.trace, names, ctx.config["kernel_pattern"])
    return None if split is None else split[stage] / 1e6 / ctx.window.steps


def host_us(ctx, event: str) -> float | None:
    """``host_event_ns`` per observation, in µs; per-observation traffic only."""
    if ctx.traffic["mode"] != "per_observation":
        return None
    names = load(ctx)
    ns = None if names is None else host_event_ns(ctx.trace, names, event)
    return None if ns is None else ns / 1e3
