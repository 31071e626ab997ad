#!/usr/bin/env python
"""Analyse a ``serve.py --trace`` export or a ``jax.profiler`` trace of
the engine (DESIGN.md §15).

The Chrome trace-event JSON the engine's ``obs.trace.Tracer`` writes —
the same file Perfetto renders visually — gives host-clock sections:

  * **step breakdown** — engine-track spans (``engine.step`` and the
    ``engine.*`` phases nested in it) per name: count, total seconds,
    p50/p90/p99 duration.
  * **interleave** — wall-clock span covered by the engine track, the
    fraction inside steps vs between them, and how much of it the
    prefill-side phases (prefill, chunk window and their readbacks) and
    the decode-side phases (decode, draft, verify and their readbacks)
    cover.
  * **TTFT waterfall** — per request: queue wait vs prefill vs (chunked)
    chunk count, worst first — where the first token actually went.

A profiler trace (a directory ``jax.profiler`` wrote, or its
``.xplane.pb``) gives the device's side through ``repro.obs.xplane``:
each engine program's device time, each span's host time, the host self
time of a step, and each idle gap of the device put down to the program
or the span it fell in.

Usage:
  PYTHONPATH=src python scripts/trace_report.py TRACE.json [--json]
      [--top 8]
  PYTHONPATH=src python scripts/trace_report.py PROFILE_DIR [--json]
"""
from __future__ import annotations

import argparse
import json
import os
import sys
from typing import Any, Dict, List

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.obs import xplane  # noqa: E402
from repro.obs.metrics import percentiles  # noqa: E402
from repro.obs.trace import load_trace, validate_events  # noqa: E402

# engine-track span names by scheduler side; anything else on tid 0 is
# still counted in the by-name breakdown, just not attributed to a side
PREFILL_SIDE = ("engine.prefill", "engine.prefill_readback",
                "engine.chunk_window", "engine.chunk_readback")
DECODE_SIDE = ("engine.decode", "engine.decode_readback", "engine.draft",
               "engine.verify", "engine.verify_readback")


def _engine_spans(events: List[Dict[str, Any]]) -> List[Dict[str, Any]]:
    """Complete spans on an engine's scheduler track (tid 0)."""
    return [e for e in events
            if e.get("ph") == "X" and e.get("tid") == 0]


def _busy_us(spans: List[Dict[str, Any]]) -> int:
    """Union length of [ts, ts+dur) intervals — nested spans (the
    phases inside an ``engine.step``) count once."""
    ivs = sorted((e["ts"], e["ts"] + e["dur"]) for e in spans)
    busy, end = 0, None
    for lo, hi in ivs:
        if end is None or lo > end:
            busy += hi - lo
            end = hi
        elif hi > end:
            busy += hi - end
            end = hi
    return busy


def step_breakdown(spans: List[Dict[str, Any]]) -> Dict[str, Any]:
    by_name: Dict[str, List[float]] = {}
    for e in spans:
        by_name.setdefault(e["name"], []).append(e["dur"] / 1e6)
    return {name: dict(percentiles(durs) or {},
                       total_s=round(sum(durs), 6))
            for name, durs in sorted(by_name.items())}


def interleave(spans: List[Dict[str, Any]]) -> Dict[str, Any]:
    if not spans:
        return {"span_s": 0.0, "busy_frac": None, "bubble_frac": None,
                "prefill_frac": None, "decode_frac": None}
    t_lo = min(e["ts"] for e in spans)
    t_hi = max(e["ts"] + e["dur"] for e in spans)
    span_us = max(t_hi - t_lo, 1)
    busy = _busy_us(spans)
    pre = _busy_us([e for e in spans if e["name"] in PREFILL_SIDE])
    dec = _busy_us([e for e in spans if e["name"] in DECODE_SIDE])
    return {"span_s": round(span_us / 1e6, 6),
            "busy_frac": round(busy / span_us, 4),
            # scheduling bubbles: wall time on the engine track between
            # steps — the caller's own work, queue waits, idle ticks
            "bubble_frac": round(1.0 - busy / span_us, 4),
            "prefill_frac": round(pre / span_us, 4),
            "decode_frac": round(dec / span_us, 4)}


def ttft_waterfall(events: List[Dict[str, Any]]) -> List[Dict[str, Any]]:
    per_rid: Dict[int, Dict[str, Any]] = {}
    for e in events:
        rid = (e.get("args") or {}).get("rid")
        if rid is None:
            continue
        row = per_rid.setdefault(rid, {"rid": rid})
        if e["ph"] == "X" and e["name"] in ("queue_wait", "prefill"):
            row[e["name"] + "_s"] = round(e["dur"] / 1e6, 6)
            if e["name"] == "prefill":
                row["chunks"] = e["args"].get("chunks")
    rows = [r for r in per_rid.values()
            if "queue_wait_s" in r or "prefill_s" in r]
    for r in rows:
        r["ttft_s"] = round(r.get("queue_wait_s", 0.0)
                            + r.get("prefill_s", 0.0), 6)
    rows.sort(key=lambda r: -r["ttft_s"])
    return rows


def report(path: str) -> Dict[str, Any]:
    doc = load_trace(path)
    events = doc["traceEvents"]
    validate_events(events)
    spans = _engine_spans(events)
    return {
        "file": path,
        "events": len(events),
        "dropped": (doc.get("otherData") or {}).get("dropped_events", 0),
        "step_breakdown": step_breakdown(spans),
        "interleave": interleave(spans),
        "ttft_waterfall": ttft_waterfall(events),
    }


def is_profile(path: str) -> bool:
    return os.path.isdir(path) or path.endswith(".xplane.pb")


def print_profile(rep: Dict[str, Any]) -> None:
    print(f"window={rep['window_s']:.4f}s busy={rep['busy_s']:.4f}s "
          f"host step self time p50="
          f"{rep['host_step_ms'] or float('nan'):.2f}ms")
    print("\n== device programs ==")
    for name, s in rep["programs"].items():
        print(f"  {name:<28} n={s['n']:<6} total={s['total_s']:.4f}s "
              f"p50={s['median_ms']:.3f}ms")
    print("\n== host spans ==")
    for name, s in rep["host_spans"].items():
        print(f"  {name:<28} n={s['n']:<6} total={s['total_s']:.4f}s "
              f"p50={s['median_ms']:.3f}ms")
    print("\n== device idle gaps ==")
    for label, sec in rep["idle_gaps"]:
        print(f"  {label:<44} {sec:.4f}s")


def _fmt_pct(v) -> str:
    return "n/a" if v is None else f"{100 * v:5.1f}%"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="summarise a serve.py --trace export")
    ap.add_argument("trace", help="Chrome trace-event JSON from --trace, "
                    "or a jax.profiler trace directory / .xplane.pb")
    ap.add_argument("--json", action="store_true",
                    help="emit the full report as JSON instead of text")
    ap.add_argument("--top", type=int, default=8,
                    help="TTFT waterfall rows shown in text mode")
    args = ap.parse_args(argv)
    if is_profile(args.trace):
        rep = xplane.summary(xplane.read(args.trace))
        if args.json:
            print(json.dumps(rep, indent=2))
        else:
            print_profile(rep)
        return 0
    rep = report(args.trace)
    if args.json:
        print(json.dumps(rep, indent=2))
        return 0

    print(f"trace: {rep['file']}  ({rep['events']} events, "
          f"{rep['dropped']} dropped)")
    print("\n== step-time breakdown (engine track) ==")
    for name, s in rep["step_breakdown"].items():
        print(f"  {name:<24} n={s['n']:<5} total={s['total_s']:.4f}s  "
              f"p50={s['p50'] * 1e3:.2f}ms p90={s['p90'] * 1e3:.2f}ms "
              f"p99={s['p99'] * 1e3:.2f}ms")
    il = rep["interleave"]
    print("\n== interleave ==")
    print(f"  span={il['span_s']:.4f}s busy={_fmt_pct(il['busy_frac'])} "
          f"bubbles={_fmt_pct(il['bubble_frac'])} "
          f"(prefill-side={_fmt_pct(il['prefill_frac'])}, "
          f"decode-side={_fmt_pct(il['decode_frac'])})")
    print(f"\n== TTFT waterfall (worst {args.top}) ==")
    for r in rep["ttft_waterfall"][:args.top]:
        chunks = f" chunks={r['chunks']}" if r.get("chunks") else ""
        print(f"  rid={r['rid']:<4} ttft={r['ttft_s'] * 1e3:8.2f}ms  "
              f"queue={r.get('queue_wait_s', 0.0) * 1e3:8.2f}ms  "
              f"prefill={r.get('prefill_s', 0.0) * 1e3:8.2f}ms{chunks}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
