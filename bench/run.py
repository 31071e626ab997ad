#!/usr/bin/env python3
"""Runs one benchmark cell once and prints one JSON result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

From the root of a checkout. The cell, its configuration, traffic mix,
engine settings and metrics are found by name (``bench/spec.py``). With
``--trace 0`` the result carries the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics, read from a profiler trace of the same
window. The last line of standard output is the result; the numbers the
output check compared, each beside its limit, are the last lines of
standard error and the result's last key. Without a TPU, or with fewer
chips than the cell asks for, it exits non-zero and prints no result.
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
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
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
    out = harness.execute(cell, args.seed, args.seconds, bool(args.trace),
                          T_PROCESS, device, peaks)
    for name, c in out["checks"].items():
        harness.log(f"check {name} {c['value']!r} limit {c['limit']!r}")
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
