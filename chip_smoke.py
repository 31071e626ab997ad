#!/usr/bin/env python3
"""Smoke run of packed ternary serving on a TPU.

Drives the serving path once at the full width of ``ternary-paper``
(12 layers, d_model 1024, d_ff 4096, 16 heads, vocab 32768; random weights
from ``--seed``): the continuous-batching engine over the paged KV pool,
every projection packed into 2-bit words and served by the Pallas ternary
GEMM, fused-MLP and paged-attention kernels. The same prompts then go
through the XLA reference lowering (``ternary_kernel="xla"``) on the same
chip, and the prefill logits of the two must agree within ``TOLERANCE``.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # only --mesh 2,2 (two TP=2 replicas
                                      # behind the prefix-affinity router)
                                      # against one chip, same prompts

Any failed check, or a backend that is not a TPU, raises: the exit code is
non-zero and no result line is printed. The timings printed on the way are
those of one smoke run, not measurements. The last line of stdout is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

ARCH = "ternary-paper"
REQUESTS, SLOTS, PROMPT_LEN, GEN_LENS = 8, 4, 512, (32, 64)
MAX_LEN, PAGE_SIZE = 1024, 16
# ||a - b||_2 / ||b||_2 between the Pallas path and its reference, over
# every entry: the last-position prefill logits of all requests (Pallas
# lowerings vs the XLA reference, or the TP mesh vs one chip) and one
# paged-attention call (Pallas kernel vs the jax gather lowering). Both
# sides feed bf16 to f32-accumulating matmuls; they differ in accumulation
# order and in where bf16 rounding happens.
TOLERANCE = 5e-2


def say(msg: str) -> None:
    print(f"smoke: {msg}", flush=True)


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SystemExit(f"chip_smoke: FAILED: {msg}")


def build_engine(cfg, params, mesh=None, paged_attn=None):
    from repro.serving import ContinuousScheduler
    eng = ContinuousScheduler(cfg, max_slots=SLOTS, max_len=MAX_LEN,
                              cache="paged", page_size=PAGE_SIZE, mesh=mesh,
                              paged_attn=paged_attn)
    eng.load(params)
    return eng


def check_kernels(eng) -> None:
    """Every warmed plan is a compiled Pallas lowering; paged attention
    resolved to the Pallas kernel."""
    plans = list(eng.gemm_plans.values()) + list(eng.fused_plans.values())
    check(bool(eng.gemm_plans) and bool(eng.fused_plans),
          "engine.load() warmed no GEMM or fused-MLP plans")
    bad = [p for p in plans if p.interpret or p.impl in ("ref", "chain")]
    check(not bad, f"{len(bad)} warmed plans are not compiled Pallas "
                   f"lowerings, e.g. {bad[:1]}")
    check(eng.cfg.paged_attn_impl == "pallas",
          f"paged attention resolved to {eng.cfg.paged_attn_impl!r}")
    say(f"{len(eng.gemm_plans)} GEMM + {len(eng.fused_plans)} fused-MLP "
        f"plans warmed, all Pallas, interpret=False; paged attention: "
        f"pallas")


def serve(front, prompts, gens, label: str):
    from repro.launch import serve as serve_lib
    t0 = time.perf_counter()
    outs, metrics = serve_lib.run_continuous(front, prompts, gens)
    wall = time.perf_counter() - t0
    check(len(outs) == len(gens)
          and all(len(o) == g for o, g in zip(outs, gens)),
          f"{label}: requests did not drain with their tokens: "
          f"{[len(o) for o in outs]} vs budgets {list(gens)}")
    say(f"{label}: {len(outs)} requests drained, {sum(gens)} tokens, "
        f"wall {wall:.3f} s ({sum(gens) / wall:.1f} tok/s, smoke run)")
    return outs, metrics


def prefill_logits(cfg, params, prompts, mesh=None):
    import jax
    import jax.numpy as jnp
    from repro.kernels import ops as kops
    from repro.models import LM
    model = LM(cfg)
    fn = jax.jit(lambda p, t: model.prefill(p, {"tokens": t},
                                            t.shape[1])[1])
    with kops.tensor_parallel(mesh), kops.serving_phase("prefill"):
        return fn(params, jnp.asarray(prompts))


def check_paged_attention(cfg, seed: int) -> None:
    """The Pallas paged kernels against the jax gather lowering, at the
    engine's shapes: every slot at a different length up to max_len, one
    token a row (decode) and a window of tokens a row (chunked prefill)."""
    import jax
    import jax.numpy as jnp
    from repro.kernels import ops as kops
    from repro.paging import kernels as pk
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(seed), 3)
    pages_per_row = MAX_LEN // PAGE_SIZE
    shape = (SLOTS * pages_per_row + 1, PAGE_SIZE, cfg.num_kv_heads,
             cfg.head_dim)
    q = jax.random.normal(kq, (SLOTS, cfg.num_heads, cfg.head_dim),
                          jnp.bfloat16)
    k_pages = jax.random.normal(kk, shape, jnp.bfloat16)
    v_pages = jax.random.normal(kv, shape, jnp.bfloat16)
    table = 1 + jnp.arange(SLOTS * pages_per_row,
                           dtype=jnp.int32).reshape(SLOTS, pages_per_row)
    lengths = jnp.linspace(1, MAX_LEN, SLOTS).astype(jnp.int32)
    compare(pk.paged_decode_attention_pallas(q, k_pages, v_pages, table,
                                             lengths),
            pk.paged_decode_attention_jax(q, k_pages, v_pages, table,
                                          lengths),
            f"paged attention pallas vs jax, lengths {lengths.tolist()}")
    # a chunk window of S tokens a row, against its flattened rows
    s = 2 * PAGE_SIZE
    qw = jax.random.normal(kq, (SLOTS, s, cfg.num_heads, cfg.head_dim),
                           jnp.bfloat16)
    first = jnp.minimum(lengths, MAX_LEN - s + 1)
    compare(kops.paged_window_attention(qw, k_pages, v_pages, table, first,
                                        impl="pallas"),
            kops.paged_window_attention(qw, k_pages, v_pages, table, first,
                                        impl="jax"),
            f"paged window attention ({s} tokens) pallas vs jax")


def compare(got, ref, label: str) -> None:
    import jax.numpy as jnp
    got, ref = got.astype(jnp.float32), ref.astype(jnp.float32)
    check(bool(jnp.all(jnp.isfinite(got))), f"{label}: non-finite values")
    rel = float(jnp.linalg.norm(got - ref) / jnp.linalg.norm(ref))
    say(f"{label}: rel. L2 error {rel:.3e} "
        f"(tolerance {TOLERANCE:g}), max |diff| "
        f"{float(jnp.max(jnp.abs(got - ref))):.3e}, shape "
        f"{tuple(got.shape)}")
    check(rel <= TOLERANCE, f"{label}: logits differ by {rel:.3e} > "
                            f"{TOLERANCE:g}")


def agreement(a, b) -> str:
    same = sum(int(x == y) for oa, ob in zip(a, b) for x, y in zip(oa, ob))
    whole = sum(int(list(oa) == list(ob)) for oa, ob in zip(a, b))
    return (f"greedy tokens agree {same}/{sum(len(o) for o in a)}, "
            f"{whole}/{len(a)} requests identical")


def one_chip(cfg, params, prompts, gens, seed: int) -> None:
    t0 = time.perf_counter()
    eng = build_engine(cfg, params)
    check_kernels(eng)
    say(f"engine built in {time.perf_counter() - t0:.1f} s")
    outs, _ = serve(eng, prompts, gens, "pallas first run (incl. compile)")
    outs2, _ = serve(eng, prompts, gens, "pallas second run")
    say(f"second run vs first: {agreement(outs2, outs)}")

    check_paged_attention(cfg, seed)
    cfg_x = dataclasses.replace(cfg, ternary_kernel="xla")
    outs_x, _ = serve(build_engine(cfg_x, params, paged_attn="jax"),
                      prompts, gens, "xla reference (incl. compile)")
    say(f"pallas vs xla reference: {agreement(outs, outs_x)}")
    compare(prefill_logits(cfg, params, prompts),
            prefill_logits(cfg_x, params, prompts),
            "last-position prefill logits, pallas vs xla")


def four_chips(cfg, params, prompts, gens, seed: int) -> None:
    from repro.distributed import router as router_lib
    from repro.distributed import tp as tp_lib
    outs_1, _ = serve(build_engine(cfg, params), prompts, gens,
                      "one chip (incl. compile)")
    meshes = tp_lib.replica_meshes(2, 2)
    engines = [build_engine(cfg, params, mesh=m) for m in meshes]
    for eng in engines:
        check_kernels(eng)
    outs_m, metrics = serve(router_lib.Router(engines), prompts, gens,
                            "mesh 2,2 (incl. compile)")
    say(f"router affinity {json.dumps(metrics['affinity'])}, per-replica "
        f"drained {[r['drained'] for r in metrics['per_replica']]}")
    say(f"mesh 2,2 vs one chip: {agreement(outs_m, outs_1)}")
    compare(prefill_logits(cfg, engines[0].params, prompts, meshes[0]),
            prefill_logits(cfg, params, prompts),
            "last-position prefill logits, TP=2 mesh vs one chip")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the --mesh 2,2 phase and the one-chip "
                         "engine it is compared with")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default=os.path.join(ROOT, "smoke_out"),
                    help="directory for the run's autotune cache")
    args = ap.parse_args(argv)

    # block shapes come from the code, never from a cache left behind
    os.makedirs(args.out, exist_ok=True)
    tune = os.path.join(args.out, "autotune.json")
    if os.path.exists(tune):
        os.remove(tune)
    os.environ["REPRO_AUTOTUNE_CACHE"] = tune

    import jax
    from repro.launch import serve as serve_lib
    cache_dir = serve_lib.enable_compile_cache()
    devices = jax.devices()
    dev = devices[0]
    check(dev.platform == "tpu",
          f"no TPU: JAX found {dev.platform!r} devices")
    check(len(devices) >= args.chips,
          f"--chips {args.chips} needs {args.chips} devices, JAX found "
          f"{len(devices)}")
    say(f"device {dev.device_kind!r} x {len(devices)}, jax "
        f"{jax.__version__}, compile cache {cache_dir}")

    cfg, params = serve_lib.load_model(ARCH, packed=True, seed=args.seed)
    check(cfg.quantization == "ternary_packed"
          and (cfg.num_layers, cfg.d_model, cfg.d_ff, cfg.num_heads,
               cfg.vocab_size) == (12, 1024, 4096, 16, 32768),
          f"not ternary-paper at full width: {cfg}")
    prompts, gens, _ = serve_lib.build_workload(cfg, REQUESTS, PROMPT_LEN,
                                                GEN_LENS, seed=args.seed)
    (four_chips if args.chips == 4 else one_chip)(cfg, params, prompts,
                                                  gens, args.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))


if __name__ == "__main__":
    main()
