"""Ring-buffer tracer exporting Chrome trace-event JSON (DESIGN.md §15).

Design constraints, in order:

1. **Low overhead when on.** One event = one tuple appended to a
   ``deque(maxlen=capacity)`` — no dict construction, no string
   formatting, no I/O until ``export()``. A long soak cannot OOM the
   host: the ring drops the *oldest* events (counted in ``dropped``)
   while track-naming metadata survives outside the ring.
2. **Zero cost when off.** There is no global "maybe-enabled" tracer to
   consult; call sites hold ``tracer=None`` and guard with a single
   attribute test, so the disabled path never reads the clock or builds
   an event.
3. **Perfetto-loadable output.** ``export()`` writes the Chrome
   trace-event JSON object format (``{"traceEvents": [...]}``) using
   complete ("X"), instant ("i"), counter ("C") and metadata ("M")
   events — load the file at https://ui.perfetto.dev or
   chrome://tracing. Timestamps are integer microseconds relative to the
   tracer's epoch.

Track layout: each engine registers a *process* (``new_pid``); its
phase spans (``engine.step`` and the ``engine.*`` spans nested in it,
written through ``phase``) live on ``tid=0`` and every request
gets its own thread track (``tid = rid + 1``) carrying the request's
whole lifecycle — submit → admit → prefill/chunks → first token →
decode → done/failed/preempted/quarantined — as one row. Spans whose
boundaries are only known after the fact (queue wait, TTFT components)
are emitted retrospectively via ``complete()`` from the same clock
stamps the metrics use, so trace-derived TTFT/TPOT agrees with
``Request.metrics()`` to microsecond rounding.

``phase`` is the one span primitive of the engine. It always opens a
``jax.profiler.TraceAnnotation`` (a ``StepTraceAnnotation`` for a step),
which records only while a profiler session runs, on the profiler's own
clock beside the device's programs, and costs well under a microsecond
otherwise; with a ``Tracer`` it also records the span into the ring.
"""
from __future__ import annotations

import collections
import contextlib
import json
from typing import Any, Dict, List, Optional

from jax.profiler import StepTraceAnnotation, TraceAnnotation

from repro.obs import clock as obs_clock

__all__ = ["Tracer", "phase", "load_trace", "validate_events"]

# tuple layout of one ring entry: (ph, name, cat, ts_us, dur_us, pid,
# tid, args) — ph/dur/args semantics per trace-event phase
_COMPLETE, _INSTANT, _COUNTER = "X", "i", "C"


class Tracer:
    def __init__(self, capacity: int = 65536, clock=None):
        assert capacity >= 1, capacity
        self._clock = clock if clock is not None else obs_clock.now
        self.t0 = self._clock()
        self.capacity = capacity
        self._ring: collections.deque = collections.deque(maxlen=capacity)
        self.dropped = 0
        self._process_names: Dict[int, str] = {}
        self._thread_names: Dict[tuple, str] = {}
        self._next_pid = 0

    # -- track naming (survives ring overflow) -------------------------
    def new_pid(self, name: str) -> int:
        pid = self._next_pid
        self._next_pid += 1
        self._process_names[pid] = name
        return pid

    def thread_name(self, pid: int, tid: int, name: str) -> None:
        self._thread_names[(pid, tid)] = name

    # -- event emission ------------------------------------------------
    def _ts(self, t: Optional[float]) -> int:
        return round(((self._clock() if t is None else t) - self.t0) * 1e6)

    def _push(self, ev: tuple) -> None:
        if len(self._ring) == self.capacity:
            self.dropped += 1
        self._ring.append(ev)

    def complete(self, name: str, t_start: float, t_end: float, *,
                 cat: str = "engine", pid: int = 0, tid: int = 0,
                 args: Optional[dict] = None) -> None:
        """Retrospective span from two absolute clock stamps (the pattern
        for request-lifecycle phases, whose boundaries the engine already
        stamps on the Request)."""
        self._push((_COMPLETE, name, cat, self._ts(t_start),
                    max(self._ts(t_end) - self._ts(t_start), 0),
                    pid, tid, args))

    @contextlib.contextmanager
    def span(self, name: str, *, cat: str = "engine", pid: int = 0,
             tid: int = 0, args: Optional[dict] = None):
        """Measured span around a code region; ``args`` may be mutated
        inside the region (it is read at exit)."""
        t0 = self._clock()
        try:
            yield
        finally:
            self.complete(name, t0, self._clock(), cat=cat, pid=pid,
                          tid=tid, args=args)

    def instant(self, name: str, *, t: Optional[float] = None,
                cat: str = "engine", pid: int = 0, tid: int = 0,
                args: Optional[dict] = None) -> None:
        self._push((_INSTANT, name, cat, self._ts(t), 0, pid, tid, args))

    def counter(self, name: str, values: Dict[str, float], *,
                t: Optional[float] = None, pid: int = 0) -> None:
        """One multi-series counter sample (each key renders as a series
        in the counter track)."""
        self._push((_COUNTER, name, "counter", self._ts(t), 0, pid, 0,
                    dict(values)))

    # -- export --------------------------------------------------------
    def __len__(self) -> int:
        return len(self._ring)

    def __bool__(self) -> bool:
        # an empty tracer is still a tracer — guard sites test
        # `tracer is not None`, but don't let a plain truthiness test
        # silently flip on the first buffered event either
        return True

    def events(self) -> List[Dict[str, Any]]:
        """Ring contents as trace-event dicts (metadata excluded),
        sorted by timestamp — retrospective spans land out of emission
        order, and sorted output keeps validators simple."""
        out = []
        for ph, name, cat, ts, dur, pid, tid, args in self._ring:
            ev: Dict[str, Any] = {"ph": ph, "name": name, "cat": cat,
                                  "ts": ts, "pid": pid, "tid": tid}
            if ph == _COMPLETE:
                ev["dur"] = dur
            if ph == _INSTANT:
                ev["s"] = "t"          # thread-scoped instant
            if args is not None:
                ev["args"] = args
            out.append(ev)
        out.sort(key=lambda e: e["ts"])
        return out

    def to_dict(self) -> Dict[str, Any]:
        meta: List[Dict[str, Any]] = []
        for pid, name in sorted(self._process_names.items()):
            meta.append({"ph": "M", "name": "process_name", "pid": pid,
                         "tid": 0, "args": {"name": name}})
        for (pid, tid), name in sorted(self._thread_names.items()):
            meta.append({"ph": "M", "name": "thread_name", "pid": pid,
                         "tid": tid, "args": {"name": name}})
        return {"traceEvents": meta + self.events(),
                "displayTimeUnit": "ms",
                "otherData": {"dropped_events": self.dropped,
                              "capacity": self.capacity}}

    def export(self, path: str) -> int:
        """Write Perfetto-loadable JSON; returns the event count."""
        doc = self.to_dict()
        with open(path, "w") as f:
            json.dump(doc, f)
        return len(doc["traceEvents"])


def phase(name: str, tracer: Optional[Tracer] = None, pid: int = 0,
          step_num: Optional[int] = None):
    """Context manager around one phase of work: a profiler annotation
    (a step annotation when ``step_num`` is given) and, with ``tracer``,
    a span on the tracer's ``pid``/``tid 0`` track. Nested phases nest in
    both sinks. With ``tracer=None`` nothing reads the clock."""
    ann = (TraceAnnotation(name) if step_num is None
           else StepTraceAnnotation(name, step_num=step_num))
    if tracer is None:
        return ann
    return _both(ann, tracer.span(name, pid=pid))


@contextlib.contextmanager
def _both(ann, span):
    with ann, span:
        yield


def load_trace(path: str) -> Dict[str, Any]:
    with open(path) as f:
        doc = json.load(f)
    assert isinstance(doc.get("traceEvents"), list), (
        "not a Chrome trace-event object file")
    return doc


def validate_events(events: List[Dict[str, Any]]) -> None:
    """Schema conformance check used by tests and ``trace_report``:
    every event carries the required trace-event fields, complete spans
    have non-negative durations, and rid-tagged events sit on the track
    their rid names."""
    for ev in events:
        assert {"ph", "name", "pid", "tid"} <= set(ev), ev
        if ev["ph"] == "M":
            continue
        assert isinstance(ev["ts"], int), ev
        if ev["ph"] == _COMPLETE:
            assert isinstance(ev["dur"], int) and ev["dur"] >= 0, ev
        rid = (ev.get("args") or {}).get("rid")
        if rid is not None:
            assert ev["tid"] == rid + 1, (
                f"rid {rid} event on track tid={ev['tid']}", ev)
