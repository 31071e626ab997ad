#!/usr/bin/env python3
"""Finds the knee of an open-loop cell once, by a sweep of fixed rates.

    python3 bench/knee.py --workload <cell> --seed <n> --rates 1,2,4 --seconds 20

One process sets the cell up once, then offers each rate for a window,
keeps the load on until the window's requests finish, and lets the engine
drain before the next rate. For each rate it prints one JSON line: whether
the backlog grew (requests still waiting for a slot when the window
closed, against when it opened), the share of the window's requests that
finished within the drain, and the share that met both limits of the
interactive class (TTFT 0.5 s, TPOT 0.1 s, the program's
``DEFAULT_SLO_CLASSES``). The knee is the highest rate the engine
sustains: its backlog grows by at most two requests over the window and
every request of the window finishes; the sweep stops at the first rate
that fails. A cell's mix then records 0.8 of the knee as its rate. Needs the
cell's chips, as ``run.py`` does.
"""
import time

T_PROCESS = time.monotonic()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(ROOT, "src"))

TTFT_S, TPOT_S = 0.5, 0.1


def met(rec) -> bool:
    t = rec.times
    if not t or rec.req.state != "done":
        return False
    tpot = (t[-1] - t[0]) / (len(t) - 1) if len(t) > 1 else 0.0
    return t[0] - rec.arrival <= TTFT_S and tpot <= TPOT_S


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    args = ap.parse_args(argv)

    import arrivals
    import harness
    import spec
    cell = spec.load_cell(args.workload, root=ROOT)
    os.environ.setdefault("REPRO_AUTOTUNE_CACHE",
                          os.path.join(ROOT, ".bench_cache", "autotune.json"))
    try:
        device = harness.check_devices(cell.chips)
        peaks = harness.load_peaks(BENCH, device["kind"])
    except harness.NoChip as e:
        harness.log(f"refused: {e}")
        return 3
    harness.compile_cache_dir(ROOT)
    counter = harness.CompileCounter()
    run = harness.Run(cell, args.seed, peaks=peaks)
    run.setup()
    for rate in [float(r) for r in args.rates.split(",")]:
        run.mix = dataclasses.replace(run.mix, rate=rate)
        run.source = arrivals.Source(run.mix, cell.config["vocab_size"],
                                     args.seed)
        run.recs = []
        run.start_arrivals(run.clock())
        waiting0 = run.engine.queue.depth()
        w = run.window(args.seconds, counter)
        waiting1 = run.engine.queue.depth()
        run.drain(w)
        recs = run.window_recs(w)
        share = sum(met(r) for r in recs) / max(len(recs), 1)
        done = sum(r.req.state == "done" for r in recs) / max(len(recs), 1)
        vals = run.end_to_end(w, 0.0)
        sustained = done == 1.0 and waiting1 <= waiting0 + 2
        print(json.dumps({"workload": cell.name, "rate": rate,
                          "sustained": sustained,
                          "requests": len(recs), "done_share": done,
                          "met_share": share,
                          "waiting_open": waiting0, "waiting_close": waiting1,
                          "ttft_p95_ms": vals.get("ttft_p95_ms"),
                          "itl_p95_ms": vals.get("itl_p95_ms"),
                          "submit_lag_s": w.submit_lag_s}), flush=True)
        if not sustained:       # higher rates fail too
            break
        run.next_arrival = None
        run.pump(until=run.clock() + harness.GRACE_S,
                 stop=lambda: not run.engine.has_work())
    return 0


if __name__ == "__main__":
    sys.exit(main())
