#!/usr/bin/env python3
"""Readings of the output check's two ends, for setting its limit.

    python3 bench/control.py --workload <cell> --seeds 1,2,3 --seconds 10

For each seed, in one process: set up the cell, serve a short window at the
cell's own load, and take the same sample of finished requests a run
checks. Then, on the same prompts and served tokens, the float32 reference
gives two readings: the widest gap of a served token (the program, whose
largest over many seeds is the limit's lower end) and the widest gap of the
token the float8 control puts first (the control, whose smallest is the
upper end). The reference is the cell's family's (``spec.load_family``).
One JSON line per seed on standard output. Needs the cell's chips, as
``run.py`` does.
"""
import time

T_PROCESS = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(ROOT, "src"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args(argv)

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
    for seed in [int(s) for s in args.seeds.split(",")]:
        print(json.dumps(readings(cell, seed, peaks, args.seconds, counter)),
              flush=True)
    return 0


def readings(cell, seed: int, peaks, seconds: float, counter):
    """Serve one window of ``cell`` at ``seed`` and read both ends of its
    output check with the family's reference."""
    import harness
    t = time.monotonic()
    run = harness.Run(cell, seed, peaks=peaks)
    run.setup()
    w = run.window(seconds, counter)
    run.drain(w)
    sample = run.sample(w, cell.engine["check_requests"])
    seqs = [(r.req.prompt, list(r.req.tokens)) for r in sample]
    run.free()
    ctl, srv = cell.family.control_gaps(cell.config, seed, seqs)
    return {"workload": cell.name, "seed": seed,
            "program_gap": max(srv), "control_gap": max(ctl),
            "per_request": {"program": srv, "control": ctl},
            "checked_tokens": sum(len(s) for _, s in seqs),
            "seconds": time.monotonic() - t}


if __name__ == "__main__":
    sys.exit(main())
