"""Serving CLI: continuous-batching engine (default) with a static-batch
fallback for A/B comparison.

The default mode drives ``repro.serving.ContinuousScheduler``: a request
queue feeding a slot-allocated KV/SSM cache pool, prefill of newly admitted
requests interleaved with decode steps of in-flight ones, per-request
TTFT/latency and queue-depth metrics emitted as JSON (DESIGN.md §7).
``--static`` runs the legacy whole-batch loop (a batch must fully finish its
generation budget before the next is admitted) on the *same* workload so the
two modes are directly comparable; both modes handle request counts that are
not a multiple of the batch/slot size.

Usage:
  PYTHONPATH=src python -m repro.launch.serve --arch ternary-paper --reduced \
      --requests 32 --slots 8 --prompt-len 32 --gen-lens 8,64
  ... --static --batch 8     # legacy static-batch A/B reference
  ... --packed --ternary-min-dim 64   # TernaryWeight packed serving
                                      # (reduced configs need the override)
  ... --cache paged --page-size 16 --kv-dtype int8   # paged KV cache
                                      # (block tables + quantized pages +
                                      #  prefix reuse, DESIGN.md §9)
  ... --spec layer_skip --spec-k 4    # self-speculative decoding: draft k
                                      # tokens cheaply, verify all k+1 in
                                      # one small-M GEMM forward, roll back
                                      # rejects — token-exact (DESIGN.md
                                      # §10; resparsify needs --packed)
  ... --chaos --deadline-s 5 --max-retries 2   # seeded fault injection +
                                      # lifecycle hardening: NaN quarantine,
                                      # retry-with-replay, deadlines, the
                                      # degradation ladder (DESIGN.md §11)
  ... --chunked-prefill --chunk-tokens 32 \\
      --traffic poisson --arrival-rate 12 \\
      --slo-ttft-ms 200 --slo-tpot-ms 50   # SLO-aware chunked prefill
                                      # under open-loop offered load:
                                      # prompts stream in alongside decode
                                      # under a per-step token budget, and
                                      # the JSON reports p50/p90/p99 TTFT
                                      # (split queue-wait + prefill) and
                                      # TPOT per class (DESIGN.md §14)
  XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
  ... --mesh 2,4                      # dp x tp mesh serving: 2 engine
                                      # replicas, each tensor-parallel over
                                      # 4 devices, behind the prefix-
                                      # affinity router (DESIGN.md §13);
                                      # token-exact vs single device
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.data import SyntheticLM
from repro.obs import clock as obs_clock
from repro.models import LM


class BatchedServer:
    """Static-batch server: groups requests into batches of size B, runs one
    prefill + N decode steps per batch. (Decode-step jit is shared across
    batches; the cache is donated between steps.)"""

    def __init__(self, cfg, max_len: int):
        self.cfg = cfg
        self.model = LM(cfg)
        self.max_len = max_len
        self.params = None
        self._prefill = jax.jit(
            lambda p, b: self.model.prefill(p, b, max_len))
        self._decode = jax.jit(self.model.decode_step, donate_argnums=(1,))

    def load(self, params):
        self.params = params

    def generate(self, prompts: np.ndarray, gen_len: int,
                 extras: Dict[str, Any] | None = None) -> np.ndarray:
        batch = {"tokens": jnp.asarray(prompts)}
        if extras:
            batch.update({k: jnp.asarray(v) for k, v in extras.items()})
        cache, logits = self._prefill(self.params, batch)
        out: List[np.ndarray] = []
        tok = jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32)
        for _ in range(gen_len):
            out.append(np.asarray(tok))
            logits, cache = self._decode(self.params, cache, tok)
            tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return np.concatenate(out, axis=1)


# ---------------------------------------------------------------------------
# Set-up shared with chip_smoke.py
# ---------------------------------------------------------------------------

CHECKOUT = os.path.abspath(os.path.join(os.path.dirname(__file__),
                                        "..", "..", ".."))


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache for an entry point: the
    directory ``$JAX_COMPILATION_CACHE_DIR`` names when set (JAX reads it
    itself), else the fixed, gitignored ``<checkout>/.jax_cache`` — a fixed
    path, so a later run finds what an earlier one compiled. Called by
    entry points only, never on import. Returns the directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(CHECKOUT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path


def load_model(arch: str, *, reduced: bool = False, packed: bool = False,
               ternary_min_dim: int = 0, seed: int = 0, **overrides):
    """(cfg, params) for serving: random weights from ``seed``; with
    ``packed`` every ternarizable projection is packed into the 2-bit
    ``TernaryWeight`` serving format (cfg switches to
    ``quantization="ternary_packed"`` when anything was packed)."""
    if ternary_min_dim > 0:
        overrides["ternary_min_dim"] = ternary_min_dim
    cfg = get_config(arch, reduced=reduced, **overrides)
    params = LM(cfg).init(jax.random.PRNGKey(seed))
    if packed:
        from repro.core import weights
        from repro.models import layers as L
        params = L.pack_params(params, cfg)
        n_packed = sum(isinstance(w, weights.TernaryWeight)
                       for w in jax.tree_util.tree_leaves(
                           params, is_leaf=lambda v: isinstance(
                               v, weights.TernaryWeight)))
        if n_packed:
            cfg = dataclasses.replace(cfg, quantization="ternary_packed")
        else:
            print(f"warning: --packed converted nothing (quantization="
                  f"{cfg.quantization!r}, no projection meets "
                  f"ternary_min_dim={cfg.ternary_min_dim}); serving the "
                  f"dense model", file=sys.stderr)
    return cfg, params


# ---------------------------------------------------------------------------
# Workload + drivers (shared with benchmarks/serving_bench.py and tests)
# ---------------------------------------------------------------------------

def build_workload(cfg, requests: int, prompt_len: int,
                   gen_lens: Sequence[int], seed: int = 0,
                   ) -> Tuple[np.ndarray, List[int], Dict[str, np.ndarray]]:
    """(prompts (R, prompt_len) int32, per-request gen budgets, extras).
    Prompts come from the deterministic SyntheticLM stream; budgets are drawn
    uniformly from ``gen_lens`` — mixed lengths are what continuous batching
    exploits. ``extras`` carries per-request frontend rows (vision/encoder
    embeds) for the families that need them (static mode only)."""
    data = SyntheticLM(cfg, requests, max(prompt_len, 16), seed=seed)
    b = data.global_batch(0)
    prompts = b["tokens"][:, :prompt_len]
    extras = {k: v for k, v in b.items()
              if k in ("vision_embeds", "enc_embeds")}
    rng = np.random.default_rng(seed + 1)
    gens = [int(g) for g in rng.choice(list(gen_lens), size=requests)]
    return prompts.astype(np.int32), gens, extras


def run_continuous(engine, prompts: np.ndarray, gens: Sequence[int],
                   ) -> Tuple[List[np.ndarray], Dict[str, Any]]:
    """Submit the whole workload, drain it, return per-request token arrays
    (in submit order) + the engine metrics dict."""
    reqs = [engine.submit(p, g) for p, g in zip(prompts, gens)]
    metrics = engine.run()
    outs = [np.asarray(r.tokens, np.int32) for r in reqs]
    return outs, metrics


def run_static(server: BatchedServer, prompts: np.ndarray,
               gens: Sequence[int], batch: int,
               extras: Optional[Dict[str, np.ndarray]] = None,
               ) -> Tuple[List[np.ndarray], Dict[str, Any]]:
    """Static-batch A/B reference on the same workload. Requests are grouped
    in submit order; each batch decodes max(batch budgets) steps and every
    request keeps its own budget's prefix. A ragged final batch is padded by
    repeating its last row and the padding outputs dropped — no request is
    silently left unserved."""
    n = len(prompts)
    assert n == len(gens) and n > 0
    outs: List[np.ndarray] = []
    t0 = obs_clock.now()
    n_decode = 0
    for lo in range(0, n, batch):
        chunk = prompts[lo:lo + batch]
        ext = {k: v[lo:lo + batch] for k, v in (extras or {}).items()}
        budgets = list(gens[lo:lo + batch])
        real = len(chunk)
        if real < batch:        # ragged final batch: pad, serve, trim
            pad_rows = batch - real
            chunk = np.concatenate(
                [chunk, np.repeat(chunk[-1:], pad_rows, axis=0)], axis=0)
            ext = {k: np.concatenate(
                [v, np.repeat(v[-1:], pad_rows, axis=0)], axis=0)
                for k, v in ext.items()}
        gen = max(budgets)
        toks = server.generate(chunk, gen, ext or None)
        n_decode += gen
        for i, g in enumerate(budgets):
            outs.append(toks[i, :g].astype(np.int32))
    wall = obs_clock.now() - t0
    assert len(outs) == n, (len(outs), n)
    useful = sum(len(o) for o in outs)
    return outs, {
        "engine": "static",
        "batch": batch,
        "submitted": n,
        "drained": len(outs),
        "generated_tokens": useful,
        "wall_s": round(wall, 4),
        "tok_per_s": round(useful / wall, 2) if wall > 0 else None,
        "decode_steps": n_decode,
    }


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------

def main(argv: Optional[Sequence[str]] = None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="ternary-paper")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--slots", type=int, default=4,
                    help="continuous mode: KV/SSM cache pool capacity")
    ap.add_argument("--batch", type=int, default=4,
                    help="--static mode: static batch size")
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen-lens", default="32",
                    help="comma list; per-request budgets drawn uniformly")
    ap.add_argument("--max-len", type=int, default=0,
                    help="cache capacity (0: prompt+max(gen-lens)+1)")
    ap.add_argument("--static", action="store_true",
                    help="legacy static-batch loop (A/B reference)")
    ap.add_argument("--cache", default="dense", choices=("dense", "paged"),
                    help="continuous mode cache: dense slot rows, or the "
                         "paged block-table pool (DESIGN.md §9)")
    ap.add_argument("--page-size", type=int, default=16,
                    help="--cache paged: tokens per KV page")
    ap.add_argument("--pages", type=int, default=0,
                    help="--cache paged: page-pool capacity incl. the "
                         "trash page (0: slots*ceil(max_len/page_size)+1)")
    ap.add_argument("--kv-dtype", default="", choices=("", "int8"),
                    help="--cache paged: int8-quantized pages with "
                         "per-page scales (default: cfg.cache_dtype)")
    ap.add_argument("--no-prefix-cache", action="store_true",
                    help="--cache paged: disable shared-prefix page reuse")
    ap.add_argument("--paged-attn", default=None,
                    choices=("auto", "jax", "pallas"),
                    help="--cache paged: decode-attention lowering "
                         "(default: inherit cfg.paged_attn_impl; auto = "
                         "pallas on TPU, dense-bit-identical jax gather "
                         "elsewhere)")
    ap.add_argument("--spec", default="off",
                    choices=("off", "resparsify", "layer_skip"),
                    help="speculative decoding draft strategy (DESIGN.md "
                         "§10): resparsify = re-ternarized packed weights "
                         "at --draft-sparsity (needs --packed), layer_skip "
                         "= a prefix of the stack + shared lm_head. "
                         "Outputs stay token-exact vs --spec off")
    ap.add_argument("--spec-k", type=int, default=4,
                    help="--spec: draft tokens proposed (and verified) per "
                         "round; each slot emits 1..k+1 tokens per round")
    ap.add_argument("--draft-sparsity", type=float, default=0.125,
                    help="--spec resparsify: draft nnz fraction")
    ap.add_argument("--draft-layers", type=int, default=0,
                    help="--spec layer_skip: draft stack depth (0: half "
                         "the layers, rounded to the stack period)")
    ap.add_argument("--packed", action="store_true",
                    help="quantize+pack ternarizable projections into the "
                         "TernaryWeight serving format before load (the "
                         "engine precomputes phase-keyed GemmPlans)")
    ap.add_argument("--ternary-min-dim", type=int, default=0,
                    help=">0: override cfg.ternary_min_dim — reduced smoke "
                         "configs need ~64 for --packed to convert their "
                         "small projections")
    ap.add_argument("--mesh", default="",
                    help="continuous mode: 'dp,tp' (or bare 'tp') — dp "
                         "engine replicas, each TP-sharded over tp devices "
                         "of a ('model',) mesh, behind the prefix-affinity "
                         "Router (DESIGN.md §13). Needs dp*tp devices; on "
                         "CPU set XLA_FLAGS="
                         "--xla_force_host_platform_device_count=N")
    ap.add_argument("--eos-id", type=int, default=-1,
                    help=">=0: stop a request early on this token")
    ap.add_argument("--chaos", action="store_true",
                    help="continuous mode: arm the seeded fault injector "
                         "(NaN logits, forced page OOM, slow steps, draft "
                         "failures at modest rates; seeded from --seed). "
                         "Outputs of surviving requests stay token-exact "
                         "vs a fault-free run (DESIGN.md §11)")
    ap.add_argument("--deadline-s", type=float, default=0.0,
                    help=">0: per-request wall-clock deadline; expired "
                         "requests are cancelled (queued or mid-decode) "
                         "and drain as failed with reason 'deadline'")
    ap.add_argument("--max-retries", type=int, default=2,
                    help="quarantine replays allowed per request before "
                         "it terminates failed (reason 'nan_logits')")
    ap.add_argument("--chunked-prefill", action="store_true",
                    help="continuous mode: chunked prefill + SLO-aware "
                         "admission (DESIGN.md §14) — prompts stream in "
                         "--chunk-tokens per step alongside decode, so a "
                         "long prompt never monopolises a step. Token-"
                         "exact vs whole-prompt admission")
    ap.add_argument("--chunk-tokens", type=int, default=32,
                    help="--chunked-prefill: max prompt tokens one request "
                         "prefills per step (windows are rounded down to "
                         "powers of two for bounded jit shapes)")
    ap.add_argument("--step-token-budget", type=int, default=0,
                    help="--chunked-prefill: total model-forward tokens "
                         "per step, decode charged first (0 = auto: "
                         "slots*(1+spec_k) + chunk-tokens)")
    ap.add_argument("--slo-ttft-ms", type=float, default=0.0,
                    help=">0: tag interactive-class requests with this "
                         "TTFT objective; admission orders by (priority, "
                         "deadline) and boosts deadline-pressed prefills")
    ap.add_argument("--slo-tpot-ms", type=float, default=0.0,
                    help=">0: interactive-class decode time-per-token "
                         "objective; prefill residual shrinks when steps "
                         "run over it")
    ap.add_argument("--traffic", default="off",
                    choices=("poisson", "bursty", "off"),
                    help="continuous mode: drive the engine open-loop "
                         "from a seeded arrival schedule instead of "
                         "submit-all-then-drain; requests split between "
                         "the interactive and batch SLO classes "
                         "(DESIGN.md §14)")
    ap.add_argument("--arrival-rate", type=float, default=8.0,
                    help="--traffic: mean offered load, requests/second")
    ap.add_argument("--trace", default="",
                    help="continuous mode: write a Perfetto-loadable "
                         "Chrome trace-event JSON of the run — per-request "
                         "lifecycle tracks, kernel spans carrying modeled "
                         "roofline attributes, per-step scheduler counters "
                         "(DESIGN.md §15). Load at https://ui.perfetto.dev "
                         "or analyse with scripts/trace_report.py")
    ap.add_argument("--trace-buffer", type=int, default=65536,
                    help="--trace: ring capacity in events; the oldest "
                         "events drop first and the drop count is "
                         "recorded in the file")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    enable_compile_cache()
    cfg, params = load_model(args.arch, reduced=args.reduced,
                             packed=args.packed,
                             ternary_min_dim=args.ternary_min_dim,
                             seed=args.seed)
    gen_lens = [int(g) for g in args.gen_lens.split(",")]
    spec_headroom = args.spec_k if args.spec != "off" else 0
    max_len = args.max_len or (args.prompt_len + max(gen_lens) + 1
                               + spec_headroom)
    prompts, gens, extras = build_workload(cfg, args.requests,
                                           args.prompt_len, gen_lens,
                                           seed=args.seed)

    tracer = None
    if args.trace:
        from repro.obs import Tracer
        tracer = Tracer(capacity=args.trace_buffer)

    if args.static:
        if args.mesh:
            raise SystemExit("--mesh is a continuous-engine feature; "
                             "drop --static")
        if args.chunked_prefill or args.traffic != "off":
            raise SystemExit("--chunked-prefill/--traffic drive the "
                             "continuous engine; drop --static")
        if args.trace:
            raise SystemExit("--trace instruments the continuous engine; "
                             "drop --static")
        server = BatchedServer(cfg, max_len)
        server.load(params)
        _, metrics = run_static(server, prompts, gens, args.batch,
                                extras=extras)
    else:
        from repro.serving import (ContinuousScheduler, FaultConfig,
                                   ResilienceConfig, SchedConfig, SLOClass)
        eos = args.eos_id if args.eos_id >= 0 else None
        spec = None
        if args.spec != "off":
            from repro.spec import SpecConfig
            spec = SpecConfig(draft=args.spec, k=args.spec_k,
                              draft_sparsity=args.draft_sparsity,
                              draft_layers=args.draft_layers)
        faults = None
        if args.chaos:
            faults = FaultConfig(seed=args.seed, nan_rate=0.05,
                                 oom_rate=0.05, slow_rate=0.02,
                                 slow_s=0.01, draft_fail_rate=0.05)
        resilience = ResilienceConfig(
            deadline_s=args.deadline_s if args.deadline_s > 0 else None,
            max_retries=args.max_retries)
        # SLO classes (DESIGN.md §14): the interactive class carries the
        # CLI latency objectives; batch-class requests ride priority 1
        slo_on = (args.chunked_prefill or args.slo_ttft_ms > 0
                  or args.slo_tpot_ms > 0 or args.traffic != "off")
        interactive = SLOClass(
            "interactive",
            ttft_target_s=(args.slo_ttft_ms / 1e3
                           if args.slo_ttft_ms > 0 else 0.5),
            tpot_target_s=(args.slo_tpot_ms / 1e3
                           if args.slo_tpot_ms > 0 else 0.1),
            priority=0)
        batch_cls = SLOClass("batch", ttft_target_s=None,
                             tpot_target_s=None, priority=1)
        sched = None
        if slo_on:
            sched = SchedConfig(
                chunk_tokens=args.chunk_tokens if args.chunked_prefill
                else 0,
                step_token_budget=args.step_token_budget)

        def build_engine(mesh=None):
            eng = ContinuousScheduler(
                cfg, max_slots=args.slots, max_len=max_len, eos_id=eos,
                cache=args.cache, page_size=args.page_size,
                n_pages=args.pages, kv_dtype=args.kv_dtype or None,
                prefix_cache=not args.no_prefix_cache,
                paged_attn=args.paged_attn, spec=spec, faults=faults,
                resilience=resilience, sched=sched, mesh=mesh,
                tracer=tracer)
            eng.load(params)
            return eng

        if args.mesh:
            if args.traffic != "off":
                raise SystemExit("--traffic drives a single engine "
                                 "open-loop; drop --mesh")
            from repro.distributed import router as router_lib
            from repro.distributed import tp as tp_lib
            dp, tp = tp_lib.parse_mesh(args.mesh)
            meshes = tp_lib.replica_meshes(dp, tp)
            front = router_lib.Router([build_engine(m) for m in meshes])
        else:
            front = build_engine()
        if args.traffic != "off":
            from repro.serving import (TrafficConfig, make_schedule,
                                       run_open_loop)
            tc = TrafficConfig(kind=args.traffic, rate=args.arrival_rate,
                               n_requests=args.requests,
                               prompt_lens=(args.prompt_len,),
                               gen_lens=tuple(gen_lens), seed=args.seed)
            schedule = make_schedule(tc, cfg.vocab_size,
                                     classes=(interactive, batch_cls),
                                     class_weights=(0.75, 0.25))
            _, metrics = run_open_loop(front, schedule)
        else:
            slo = interactive if slo_on else None
            reqs = [front.submit(p, g, slo=slo)
                    for p, g in zip(prompts, gens)]
            metrics = front.run()
            del reqs
        if tracer is not None:
            # one file even under --mesh: every replica engine registered
            # its own pid on the shared tracer, so replica timelines load
            # as separate process groups in the same Perfetto view
            n_ev = tracer.export(args.trace)
            print(f"# trace: {args.trace} ({n_ev} events, "
                  f"{tracer.dropped} dropped)", file=sys.stderr)
    print(json.dumps(metrics))
    return metrics


if __name__ == "__main__":
    main()
