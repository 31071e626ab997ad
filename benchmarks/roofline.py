"""Roofline reporting, two layers:

1. **Model-level aggregation** — read experiments/dryrun/*.json (written
   by ``repro.launch.dryrun``) and emit the §Roofline table (CSV +
   markdown).
2. **Per-kernel report** (DESIGN.md §12) — build a representative
   ``GemmPlan``/``FusedMlpPlan`` for every registered kernel lowering and
   emit each plan's ``roofline()`` dict (achieved vs ceiling FLOP/s,
   modeled HBM bytes from block shapes + occupancy metadata, headroom) as
   JSON alongside the bench output. CI runs ``roofline.py --quick --json
   roofline_ci.json`` in the bench leg and uploads the artifact; README
   ("Reading a roofline report") explains how to interpret it.
"""
from __future__ import annotations

import argparse
import glob
import json
import os
from typing import Dict, List

PEAK_FLOPS = 197e12
HBM_BW = 819e9
ICI_BW = 50e9
HBM_PER_CHIP = 16 * 2**30   # v5e-class


def load(out_dir: str = "experiments/dryrun") -> List[Dict]:
    recs = []
    for path in sorted(glob.glob(os.path.join(out_dir, "*.json"))):
        with open(path) as f:
            recs.append(json.load(f))
    return recs


def roofline_rows(recs: List[Dict], mesh: str = "16x16") -> List[Dict]:
    rows = []
    for r in recs:
        if r.get("mesh") != mesh or r.get("quant") not in ("none", ""):
            continue
        if r.get("overrides"):
            continue
        row = {"arch": r["arch"], "shape": r["shape"], "status": r["status"]}
        if r["status"] == "ok":
            tc, tm, tl = (r["t_compute_s"], r["t_memory_s"],
                          r["t_collective_s"])
            dom = r["dominant"]
            t_bound = max(tc, tm, tl)
            row.update({
                "t_compute_s": f"{tc:.3e}", "t_memory_s": f"{tm:.3e}",
                "t_collective_s": f"{tl:.3e}", "dominant": dom,
                "roofline_frac": f"{tc / t_bound:.3f}" if t_bound else "",
                "useful_ratio": f"{(r.get('useful_flops_ratio') or 0):.2f}",
                "hbm_frac": f"{(r['memory'].get('argument_size_in_bytes', 0) + r['memory'].get('temp_size_in_bytes', 0)) / HBM_PER_CHIP:.2f}"
                if r.get("memory") else "",
            })
        else:
            row["dominant"] = r.get("reason", r.get("error", ""))[:60]
        rows.append(row)
    return rows


def markdown_table(rows: List[Dict]) -> str:
    if not rows:
        return "(no dry-run records yet)"
    cols = ["arch", "shape", "status", "t_compute_s", "t_memory_s",
            "t_collective_s", "dominant", "roofline_frac", "useful_ratio",
            "hbm_frac"]
    out = ["| " + " | ".join(cols) + " |",
           "|" + "|".join("---" for _ in cols) + "|"]
    for r in rows:
        out.append("| " + " | ".join(str(r.get(c, "")) for c in cols) + " |")
    return "\n".join(out)


def _ternary(rng, k: int, n: int, density: float = 0.5):
    import numpy as np
    w = rng.integers(-1, 2, size=(k, n)).astype(np.int8)
    return np.where(rng.random((k, n)) < density, w, 0).astype(np.int8)


def kernel_report(quick: bool = False) -> Dict[str, Dict]:
    """Per-registered-kernel roofline: one representative plan per
    ``(format, impl)`` lowering in the GEMM registry plus one per fused-MLP
    impl, each entry carrying the plan's modeled ``roofline()`` dict
    (achieved vs ceiling FLOP/s, HBM bytes from occupancy metadata)."""
    import jax.numpy as jnp
    import numpy as np

    from repro.core import weights
    from repro.kernels import ops

    m, k, ff, n = (128, 512, 1024, 512) if quick else (512, 1024, 4096, 1024)
    rng = np.random.default_rng(0)
    packed = {fmt: weights.pack(_ternary(rng, k, n), fmt)
              for fmt in ("dense2bit", "tiled", "bitplane")}
    x = jnp.asarray(rng.normal(size=(m, k)).astype(np.float32))

    report: Dict[str, Dict] = {}
    for (fmt, impl) in sorted(ops.kernel_registry()):
        w = packed.get(fmt)
        if w is None:
            continue
        plan = ops.ternary_gemm_plan(w, m, impl=impl, phase=None)
        rec = {
            "kind": "gemm", "m": m, "k": k, "n": n,
            "blocks": {"block_m": plan.block_m, "block_n": plan.block_n,
                       "block_k": plan.block_k},
            "occupancy": plan.occupancy,
            "roofline": plan.roofline(),
        }
        report[f"{fmt}/{impl}"] = rec

    wi = weights.pack(_ternary(rng, k, ff), "dense2bit")
    wg = weights.pack(_ternary(rng, k, ff), "dense2bit")
    wo = weights.pack(_ternary(rng, ff, n), "dense2bit")
    for impl in sorted(ops.fused_registry()):
        plan = ops.fused_mlp_plan(wi, wo, wg, m=m, impl=impl, phase=None)
        rec = {
            "kind": "fused_mlp", "m": m, "k": k, "ff": ff, "n": n,
            "blocks": {"block_m": plan.block_m, "block_n1": plan.block_n1,
                       "block_k1": plan.block_k1, "block_n2": plan.block_n2,
                       "block_k2": plan.block_k2},
            "roofline": plan.roofline(),
        }
        report[f"fused_mlp/{impl}"] = rec
    return report


def write_kernel_report(path: str, quick: bool = False) -> Dict[str, Dict]:
    report = kernel_report(quick=quick)
    doc = {"version": 1, "quick": quick, "kernels": report}
    d = os.path.dirname(path)
    if d:
        os.makedirs(d, exist_ok=True)
    with open(path, "w") as f:
        json.dump(doc, f, indent=2, sort_keys=True)
    return report


def print_kernel_report(report: Dict[str, Dict]) -> None:
    print("\n== kernel roofline ==")
    print("kernel,bound,arithmetic_intensity,achieved_gflops,"
          "ceiling_gflops,headroom")
    for name, rec in sorted(report.items()):
        rl = rec["roofline"]
        print(f"{name},{rl['bound']},{rl['arithmetic_intensity']:.1f},"
              f"{rl['achieved_flops'] / 1e9:.1f},"
              f"{rl['ceiling_flops'] / 1e9:.1f},{rl['headroom']:.3f}")


def main(out_dir: str = "experiments/dryrun"):
    recs = load(out_dir)
    for mesh in ("16x16", "2x16x16"):
        rows = roofline_rows(recs, mesh)
        if not rows:
            continue
        print(f"\n== roofline {mesh} ==")
        for r in rows:
            print(",".join(str(r.get(c, "")) for c in
                           ("arch", "shape", "status", "dominant",
                            "t_compute_s", "t_memory_s", "t_collective_s")))


if __name__ == "__main__":
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--quick", action="store_true",
                    help="small representative shapes (CI bench leg)")
    ap.add_argument("--json", default="",
                    help="write the per-kernel roofline report to this path")
    ap.add_argument("--out-dir", default="experiments/dryrun",
                    help="dry-run records for the model-level table")
    args = ap.parse_args()
    main(args.out_dir)
    rep = (write_kernel_report(args.json, quick=args.quick) if args.json
           else kernel_report(quick=args.quick))
    print_kernel_report(rep)
