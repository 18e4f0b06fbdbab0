"""The profiler trace of a window, reduced to plain event lists.

``read_xplane`` turns the JAX profiler's ``.xplane.pb`` into a dict that
holds nothing but numbers and names (it is also the shape of the trace kept
under ``tests/data``):

* ``device_ops``: ``[name, start_ns, end_ns]`` of every operation on the
  first TPU's op line, named by its HLO instruction (``%fusion.5``). A
  loop's op (``%while.4``) spans the ops of its body, so op times are
  taken as self time: an op's interval less those of the ops inside it;
* ``host_spans``: ``[name, start_ns, end_ns]`` of the benchmark's own
  ``bench/...`` host spans;
* ``window``: ``[start_ns, end_ns]`` of the ``bench/window`` span.

All times are on the profiler's one clock. The functions below reduce
those lists; the metric readers call them.
"""

from __future__ import annotations

import re
from pathlib import Path

DEVICE_PLANE = re.compile(r"^/device:TPU:0$")
OP_LINE = "XLA Ops"
SPAN_PREFIX = "bench/"


def read_xplane(trace_dir: Path) -> dict:
    from jax.profiler import ProfileData

    files = sorted(Path(trace_dir).rglob("*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(str(files[-1]))
    ops, spans = [], []
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name == OP_LINE:
                    ops += [[e.name.split(" = ", 1)[0], e.start_ns, e.end_ns]
                            for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans += [[e.name, e.start_ns, e.end_ns] for e in line.events
                          if e.name.startswith(SPAN_PREFIX)]
    windows = [s for s in spans if s[0] == SPAN_PREFIX + "window"]
    if not windows:
        raise ValueError("the trace holds no bench/window span")
    window = [windows[0][1], windows[0][2]]
    spans = [s for s in spans if s[0] != SPAN_PREFIX + "window"]
    return {"device_ops": sorted(ops, key=lambda e: e[1]),
            "host_spans": sorted(spans, key=lambda e: e[1]),
            "window": window}


def clip(events, window):
    """Events cut to the window; those wholly outside it are dropped."""
    lo, hi = window
    return [[n, max(s, lo), min(e, hi)] for n, s, e in events if e > lo and s < hi]


def busy_intervals(events):
    """The union of the events' intervals, as sorted disjoint pairs."""
    merged = []
    for _, s, e in sorted(events, key=lambda x: x[1]):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def busy_ns(trace) -> float:
    return sum(e - s for s, e in busy_intervals(clip(trace["device_ops"], trace["window"])))


def window_ns(trace) -> float:
    return trace["window"][1] - trace["window"][0]


def self_times(events):
    """``[name, self_ns]`` per event: its interval less the intervals of the
    events nested inside it (events on one line nest or are disjoint)."""
    out, stack = [], []  # stack: [index into out, end]
    for n, s, e in sorted(events, key=lambda x: (x[1], -x[2])):
        while stack and stack[-1][1] <= s:
            stack.pop()
        if stack:
            out[stack[-1][0]][1] -= e - s
        out.append([n, e - s])
        stack.append([len(out) - 1, e])
    return out


def op_time_ns(trace, pattern: str, *, match: bool = True) -> float:
    """Summed device self time of the ops whose name matches ``pattern``
    (``match=False``: of the others), inside the window."""
    rx = re.compile(pattern)
    return sum(t for n, t in self_times(clip(trace["device_ops"], trace["window"]))
               if bool(rx.search(n)) == match)


def top_ops(trace, k=10):
    """``[name, seconds]`` of the k ops that took the most device self time."""
    total = {}
    for n, t in self_times(clip(trace["device_ops"], trace["window"])):
        total[n] = total.get(n, 0) + t
    top = sorted(total.items(), key=lambda kv: -kv[1])[:k]
    return [[n, ns / 1e9] for n, ns in top]


def idle_gaps(trace, k=10):
    """``[label, seconds]`` of the k longest device-idle gaps in the
    window, each labelled by the innermost host span at its middle."""
    lo, hi = trace["window"]
    busy = busy_intervals(clip(trace["device_ops"], trace["window"]))
    gaps, t = [], lo
    for s, e in busy:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if hi > t:
        gaps.append((t, hi))
    gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:k]
    spans = trace["host_spans"]
    out = []
    for s, e in gaps:
        mid = (s + e) / 2
        inside = [sp for sp in spans if sp[1] <= mid <= sp[2]]
        label = min(inside, key=lambda sp: sp[2] - sp[1])[0] if inside else "host/other"
        out.append([label, (e - s) / 1e9])
    return out
