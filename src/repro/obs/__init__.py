"""Observability subsystem (DESIGN.md §15): one clock, one tracer, one
metrics registry.

``obs.clock``   — the single monotonic clock source every serving-path
                  timestamp (submit/admit/first-token/deadline/backoff)
                  reads from; fake-able in tests so trace and metrics
                  output is deterministic.
``obs.trace``   — the one span primitive ``phase`` (a ``jax.profiler``
                  annotation, so the span lands in any profiler trace on
                  the device's clock, plus a ``Tracer`` span when one is
                  held) and a low-overhead ring-buffer ``Tracer``
                  emitting span/instant/counter events and exporting
                  Chrome trace-event JSON (load the file in Perfetto or
                  chrome://tracing). Per-request events share one track,
                  so a request's lifecycle — submit → admit →
                  prefill-chunk(s) → first token → decode →
                  done/failed/preempted — renders as one row.
``obs.xplane``  — reads a ``jax.profiler`` trace of the engine back:
                  device time per program, host time per span, idle
                  gaps put down to a program or a span.
``obs.metrics`` — counter/gauge/histogram/EWMA registry plus the shared
                  exact-percentile helper behind the engine's metrics
                  JSON (whose shape is golden-locked by
                  ``tests/test_obs.py``).

The disabled path is zero-cost by construction: call sites hold
``tracer=None`` and guard with one attribute test — no event object is
built, no clock is read; a ``phase`` span then costs one profiler
annotation, which records nothing without a profiler session.
"""
from repro.obs import clock
from repro.obs.metrics import (Counter, Ewma, Gauge, Histogram,
                               MetricsRegistry, RunningStat, percentiles)
from repro.obs.trace import Tracer, load_trace, phase, validate_events

__all__ = [
    "clock", "trace", "metrics",
    "Tracer", "phase", "load_trace", "validate_events",
    "MetricsRegistry", "Counter", "Gauge", "Histogram", "Ewma",
    "RunningStat", "percentiles",
]
