"""From a profiler trace and the window's counts to per-layer metrics.

The trace is the ``.xplane.pb`` that ``jax.profiler`` writes, read with
``jax.profiler.ProfileData``. Device time is the ``XLA Ops`` line of each
``/device:TPU:n`` plane; the driver's own spans (``bench.window``,
``bench.engine_step``, ``bench.submit``, ``bench.idle_wait``) are
``TraceAnnotation`` events on the host plane, on the same clock.

* busy: the union of the device's op intervals inside the ``bench.window``
  span, averaged over the chips; idle share is 1 - busy / window;
* kernel time: the summed device time of the ops whose HLO instruction
  name matches one of the patterns of a kernel class, one file per class
  under ``bench/kernels/`` (``gemm.json``: the ternary GEMM and fused-MLP
  Pallas calls; ``attn.json``: paged decode attention). Loop ops
  (``while``) enclose the ops they run and are left out of the per-op
  sums;
* idle gaps: each stretch of the window with no device op, put down to the
  driver span that the host was in at its middle.
"""
from __future__ import annotations

import bisect
import collections
import glob
import json
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

KERNELS_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "kernels")
WINDOW_SPAN = "bench.window"
HOST_SPANS = ("bench.engine_step", "bench.submit", "bench.idle_wait")
TOP = 10

Interval = Tuple[int, int]


# -- metric arithmetic --------------------------------------------------------

def mean_share(values: Sequence[float]) -> Optional[float]:
    if not values:
        return None
    return 100.0 * sum(values) / len(values)


def median_ms(values: Sequence[float]) -> Optional[float]:
    v = sorted(values)
    if not v:
        return None
    return 1e3 * v[(len(v) - 1) // 2]


def mfu(ctx) -> Optional[float]:
    tr, w = ctx["trace"], ctx["work"]
    if not tr or tr["busy_s"] <= 0 or w.useful_ops <= 0:
        return None
    return 100.0 * w.useful_ops / (tr["busy_s"] * ctx["peaks"]["bf16_flops"])


def roofline(ctx, kind: str) -> Optional[float]:
    tr = ctx["trace"]
    work = getattr(ctx["work"], kind)
    t = (tr or {}).get("kernels", {}).get(kind, 0.0)
    if t <= 0 or work.least_s <= 0:
        return None
    return 100.0 * work.least_s / t


def idle_share(ctx) -> Optional[float]:
    tr = ctx["trace"]
    if not tr or tr["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])


# -- intervals ----------------------------------------------------------------

def union(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[List[int]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def gaps(busy: List[Interval], lo: int, hi: int) -> List[Interval]:
    out, t = [], lo
    for a, b in busy:
        if a > t:
            out.append((t, a))
        t = max(t, b)
    if hi > t:
        out.append((t, hi))
    return out


CONTAINERS = ("while", "conditional", "call")


def op_name(event: str) -> str:
    """An ``XLA Ops`` event is named by its HLO text,
    ``%fusion.12 = bf16[...] fusion(...)``: keep the instruction name."""
    m = re.match(r"\s*%?([^\s=]+)\s*=", event)
    return m.group(1) if m else event


def op_family(name: str) -> str:
    """``fusion.123`` -> ``fusion``: one row per kind of op."""
    return re.sub(r"[.\-_]?\d+$", "", op_name(name))


def load_kernel_classes(path: str = KERNELS_DIR) -> Dict[str, re.Pattern]:
    out = {}
    for f in sorted(glob.glob(os.path.join(path, "*.json"))):
        with open(f) as fh:
            pats = json.load(fh)["patterns"]
        out[os.path.splitext(os.path.basename(f))[0]] = re.compile(
            "|".join(f"(?:{p})" for p in pats))
    return out


def attribute(gap_list: List[Interval],
              host: List[Tuple[int, int, str]]) -> List[List]:
    """Idle time per driver span the host was in (the innermost span at
    the gap's middle), longest first."""
    host = sorted(host)
    starts = [a for a, _, _ in host]
    tot: Dict[str, int] = collections.Counter()
    cnt: Dict[str, int] = collections.Counter()
    for a, b in gap_list:
        mid = (a + b) // 2
        i = bisect.bisect_right(starts, mid)
        name = "outside driver spans"
        best = None
        for j in range(i - 1, max(i - 64, -1), -1):
            s, e, n = host[j]
            if s <= mid < e and (best is None or e - s < best):
                best, name = e - s, n
        tot[name] += b - a
        cnt[name] += 1
    return [[f"{n} ({cnt[n]} gaps)", tot[n] / 1e9]
            for n, _ in tot.most_common(TOP)]


# -- the trace ----------------------------------------------------------------

def reduce_events(device: Dict[str, List[Tuple[int, int, str]]],
                  host: List[Tuple[int, int, str]],
                  classes: Dict[str, re.Pattern]) -> Dict:
    """``device``: per chip, its op events (start_ns, end_ns, name);
    ``host``: the driver's spans (start_ns, end_ns, name)."""
    wins = [(a, b) for a, b, n in host if n == WINDOW_SPAN]
    if not wins:
        raise ValueError(f"no {WINDOW_SPAN!r} span in the trace")
    lo, hi = wins[0]
    spans = [h for h in host if h[2] in HOST_SPANS]
    busy_s, kernels, ops = [], collections.Counter(), collections.Counter()
    idle: List[Interval] = []
    for chip, events in sorted(device.items()):
        evs = [(max(a, lo), min(b, hi), n) for a, b, n in events
               if b > lo and a < hi]
        u = union((a, b) for a, b, _ in evs)
        busy_s.append(sum(b - a for a, b in u) / 1e9)
        for a, b, n in evs:
            fam = op_family(n)
            if fam in CONTAINERS:       # holds the ops counted below
                continue
            ops[fam] += b - a
            for kind, pat in classes.items():
                if pat.search(op_name(n)):
                    kernels[kind] += b - a
                    break
        if not idle:
            idle = gaps(u, lo, hi)
    n_chips = max(len(device), 1)
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": sum(busy_s) / n_chips,
        "kernels": {k: v / 1e9 / n_chips for k, v in kernels.items()},
        "device_ops": [[n, t / 1e9 / n_chips] for n, t in ops.most_common(TOP)],
        "idle_gaps": attribute(idle, spans),
    }


def read_xplane(path: str):
    """(device events per chip, host driver spans) of one trace file."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    device: Dict[str, List[Tuple[int, int, str]]] = {}
    host: List[Tuple[int, int, str]] = []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name == "XLA Ops":
                    device[plane.name] = [
                        (e.start_ns, e.start_ns + e.duration_ns, e.name)
                        for e in line.events]
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("bench."):
                        host.append((e.start_ns, e.start_ns + e.duration_ns,
                                     e.name))
    return device, host


def reduce_dir(trace_dir: str) -> Dict:
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    device, host = read_xplane(sorted(files)[-1])
    if not device:
        raise ValueError("the trace has no TPU device plane")
    return reduce_events(device, host, load_kernel_classes())
