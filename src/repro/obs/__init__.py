"""Observability subsystem (DESIGN.md §15): the flight recorder.

Four small layers, strictly ordered by distance from the kernels:

- ``obs.stats``      — ``StepStats``, the fixed per-step diagnostic record
  every ``Resampler.step``/``step_rows`` returns (in-kernel on the pallas
  backends, composed from ``core.metrics`` bitwise-identically elsewhere).
- ``obs.telemetry``  — ``Telemetry``, the scan-carried trajectory record the
  consumers (`run_filter`, `run_smc_sampler`, `smc_decode`) return when
  asked; structurally absent from the jaxpr when off.
- ``obs.trace``      — named scopes, always on and metadata only: the
  filter's stages (``pf/...``) and every dispatch
  ``family/backend/entry/plane_dtype``.
- ``obs.sink``       — JSONL event emitter for the benchmark harness.

The invariant tying them together: telemetry NEVER changes what a program
computes — same launch counts, same ancestor stream, bit-identical
estimates with it on or off (analyzer pass 6, ``analysis/telemetry.py``).
"""

from repro.obs.sink import JsonlSink
from repro.obs.stats import StepStats, stats_from_vector
from repro.obs.telemetry import Telemetry
from repro.obs.trace import dispatch_span, span

__all__ = [
    "JsonlSink",
    "StepStats",
    "Telemetry",
    "dispatch_span",
    "span",
    "stats_from_vector",
]
