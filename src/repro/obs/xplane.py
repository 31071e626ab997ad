"""Reads a ``jax.profiler`` trace of the serving engine (DESIGN.md §15).

The engine's spans (``obs.trace.phase``: ``engine.step`` and the
``engine.*`` spans nested in it) are annotations on the host planes of
the ``.xplane.pb`` that ``jax.profiler`` writes; the device's programs are
the ``XLA Modules`` line of each ``/device:...`` plane and its operations
the ``XLA Ops`` line, all on the profiler's one clock. Every engine jit
has a stable function name (``engine_decode``, ``engine_chunk_window``,
...), so a module event names the phase it ran.

* program times: the device time of each run of each engine program;
* host self time of a step: ``engine.step`` less the time it spent in
  the ``*_readback`` spans, where the host waits on the device: the host
  work on the step's critical path;
* idle gaps: each stretch with no device operation, put down to the
  program it lies inside (``in-program (<program>)``: a bubble on the
  device) or else to the innermost engine or benchmark span the host was in
  at its middle.
"""
from __future__ import annotations

import bisect
import collections
import glob
import os
import re
from typing import Dict, List, Optional, Sequence, Tuple

__all__ = ["Profile", "read", "program_name", "program_times",
           "step_self_times", "idle_gaps", "summary", "READBACKS"]

Event = Tuple[int, int, str]          # (start_ns, end_ns, name)

# the engine's spans, and those of a benchmark loop that traces a window
# (the benchmark's ``bench.window`` bounds what it measures)
HOST_PREFIXES = ("engine.", "bench.")
WINDOW_SPAN = "bench.window"
READBACKS = ("engine.prefill_readback", "engine.chunk_readback",
             "engine.decode_readback", "engine.verify_readback")
_PROGRAM = re.compile(r"engine_[a-z_]+")
TOP = 12
LOOKBACK = 256


class Profile:
    """Host spans, and per device plane its programs and operations."""

    def __init__(self, host: List[Event], modules: Dict[str, List[Event]],
                 ops: Dict[str, List[Event]]):
        self.host = sorted(host)
        self.modules = {k: sorted(v) for k, v in modules.items()}
        self.ops = {k: sorted(v) for k, v in ops.items()}


def read(path: str) -> Profile:
    """A ``.xplane.pb`` file, or the newest one under a directory."""
    if os.path.isdir(path):
        files = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                                 recursive=True), key=os.path.getmtime)
        if not files:
            raise FileNotFoundError(f"no .xplane.pb under {path}")
        path = files[-1]
    from jax.profiler import ProfileData
    host: List[Event] = []
    modules: Dict[str, List[Event]] = {}
    ops: Dict[str, List[Event]] = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                out = {"XLA Modules": modules, "XLA Ops": ops}.get(line.name)
                if out is not None:
                    out[plane.name] = [
                        (e.start_ns, e.start_ns + e.duration_ns, e.name)
                        for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend((e.start_ns, e.start_ns + e.duration_ns, e.name)
                            for e in line.events
                            if e.name.startswith(HOST_PREFIXES))
    return Profile(host, modules, ops)


def program_name(module: str) -> str:
    """``jit_engine_decode(1234)`` -> ``engine_decode``; another module
    keeps its own name without the program id."""
    m = _PROGRAM.search(module)
    return m.group(0) if m else module.split("(")[0]


def _first_chip(per_chip: Dict[str, List[Event]]) -> List[Event]:
    return per_chip[min(per_chip)] if per_chip else []


def _clip(events: Sequence[Event], lo: int, hi: int) -> List[Event]:
    return [(max(a, lo), min(b, hi), n) for a, b, n in events
            if b > lo and a < hi]


def program_times(modules: Sequence[Event]) -> Dict[str, List[float]]:
    """Seconds of device time of each run, per program."""
    out: Dict[str, List[float]] = collections.defaultdict(list)
    for a, b, n in modules:
        out[program_name(n)].append((b - a) / 1e9)
    return dict(out)


def step_self_times(host: Sequence[Event], step: str = "engine.step",
                    minus: Sequence[str] = READBACKS) -> List[float]:
    """Per step, seconds of ``step`` spent outside the ``minus`` spans
    nested in it."""
    steps = sorted((a, b) for a, b, n in host if n == step)
    waits = sorted((a, b) for a, b, n in host if n in minus)
    starts = [a for a, _ in waits]
    out = []
    for a, b in steps:
        i = bisect.bisect_left(starts, a)
        inner = 0
        while i < len(waits) and waits[i][0] < b:
            inner += min(waits[i][1], b) - waits[i][0]
            i += 1
        out.append((b - a - inner) / 1e9)
    return out


def _union(intervals) -> List[Tuple[int, int]]:
    out: List[List[int]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _innermost(events: List[Event], starts: List[int],
               t: int) -> Optional[str]:
    """The shortest of ``events`` (sorted by start) that holds ``t``,
    looking back at most ``LOOKBACK`` events."""
    best, name = None, None
    i = bisect.bisect_right(starts, t)
    for j in range(i - 1, max(i - LOOKBACK, 0) - 1, -1):
        s, e, n = events[j]
        if s <= t < e and (best is None or e - s < best):
            best, name = e - s, n
        if best is not None and t - s > best:
            break
    return name


def idle_gaps(ops: Sequence[Event], modules: Sequence[Event],
              host: Sequence[Event], lo: int, hi: int) -> List[List]:
    """Idle device time in [lo, hi) per label, longest first: a gap whose
    middle lies inside a program is ``in-program (<program>)``, any other
    goes to the innermost host span at its middle."""
    busy = _union((a, b) for a, b, _ in _clip(ops, lo, hi))
    gap_list, t = [], lo
    for a, b in busy:
        if a > t:
            gap_list.append((t, a))
        t = max(t, b)
    if hi > t:
        gap_list.append((t, hi))
    mods = sorted(modules)
    mod_starts = [a for a, _, _ in mods]
    spans = sorted(host)
    span_starts = [a for a, _, _ in spans]
    tot: Dict[str, int] = collections.Counter()
    cnt: Dict[str, int] = collections.Counter()
    for a, b in gap_list:
        mid = (a + b) // 2
        prog = _innermost(mods, mod_starts, mid)
        if prog is not None:
            label = f"in-program ({program_name(prog)})"
        else:
            label = _innermost(spans, span_starts, mid) or "outside spans"
        tot[label] += b - a
        cnt[label] += 1
    return [[f"{n} ({cnt[n]} gaps)", tot[n] / 1e9]
            for n, _ in tot.most_common(TOP)]


def _median_ms(values: Sequence[float]) -> Optional[float]:
    v = sorted(values)
    return 1e3 * v[(len(v) - 1) // 2] if v else None


def summary(p: Profile) -> Dict:
    """Per-program device times, per-span host times, host self time of
    a step and the idle gaps, over the ``WINDOW_SPAN`` span when the
    trace has one and else from the first step's start to the last
    step's end."""
    wins = [(a, b) for a, b, n in p.host if n == WINDOW_SPAN]
    steps = [(a, b) for a, b, n in p.host if n == "engine.step"]
    if wins:
        lo, hi = wins[0]
    elif steps:
        lo, hi = steps[0][0], max(b for _, b in steps)
    else:
        raise ValueError("the trace has no engine.step span")
    host = _clip(p.host, lo, hi)
    modules = _clip(_first_chip(p.modules), lo, hi)
    ops = _first_chip(p.ops)
    spans: Dict[str, List[float]] = collections.defaultdict(list)
    for a, b, n in host:
        spans[n].append((b - a) / 1e9)
    busy = sum(b - a for a, b in _union(
        (a, b) for a, b, _ in _clip(ops, lo, hi)))
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": busy / 1e9,
        "programs": {n: {"n": len(v), "median_ms": _median_ms(v),
                         "total_s": sum(v)}
                     for n, v in sorted(program_times(modules).items())},
        "host_spans": {n: {"n": len(v), "median_ms": _median_ms(v),
                           "total_s": sum(v)}
                       for n, v in sorted(spans.items())},
        "host_step_ms": _median_ms(step_self_times(host)),
        "idle_gaps": idle_gaps(ops, modules, host, lo, hi),
    }
