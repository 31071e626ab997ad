"""Continuous-batching scheduler over the slot pool.

Each scheduler step:

1. **admit** — while the queue is non-empty and a slot is free, pop a
   request, prefill it (B=1, its exact prompt length) and scatter the
   resulting cache into the allocated slot; the prefill's last-position
   argmax is the request's first token (TTFT stamps here).
2. **decode** — one jitted step over *all* ``max_slots`` rows with a
   per-slot position vector (``cache["pos"]`` as ``(B,)``): live slots each
   attend to their own valid prefix and scatter their token K/V at their own
   offset; free slots compute garbage that is never read and whose writes
   land in rows fully overwritten on the next admit.
3. **evict** — requests that hit their token budget (or EOS) release their
   slot back to the free list; the next step's admit refills it.

Short requests therefore drain and are replaced while long ones keep
decoding — no static-batch barrier. The decode jit compiles once (fixed
``max_slots`` batch); prefill compiles once per distinct (admission-group
size, prompt length) pair — bounded by ``max_slots`` sizes per length, a
deliberate trade against padding every admission to a full-pool prefill.

The decode hot loop is device-resident: cache, position and token vectors
stay on device, the greedy argmax runs inside the jit, and the only
per-step transfer is the ``(max_slots,)`` next-token vector the scheduler
needs for EOS/budget checks. Host state is pushed to the device only after
admit/evict events (O(requests), not O(tokens)).

Kernel selection: prefill traces under ``ops.serving_phase("prefill")``
(M=B·L GEMM-shaped) and decode under ``"decode"`` (M=slots GEMV-shaped), so
the block-shape autotuner keys the two phases separately.

Cache modes (DESIGN.md §9): ``cache="dense"`` is the original fixed
``max_slots x max_len`` slot pool (kept bit-exact as the A/B baseline);
``cache="paged"`` swaps in ``repro.paging.PagePool`` — per-request block
tables over a global page pool, on-demand page growth each decode step,
OOM-safe admission (requests defer instead of crashing), copy-on-write
prefix sharing, and preempt-and-replay (greedy decoding is deterministic,
so a preempted request replayed from its original prompt reproduces its
tokens exactly) when the pool runs dry mid-decode.

Speculative decoding (DESIGN.md §10): ``spec=SpecConfig(...)`` replaces
the one-token decode with a draft -> verify -> rollback round. A cheap
draft model (``repro.spec.draft``) proposes ``k`` tokens per slot from its
own dense KV cache; the target verifies the whole ``(slots, k+1)`` window
in one forward traced under ``serving_phase("verify")`` (M = slots·(k+1)
GEMM-shaped — the regime the sparse ternary kernels are built for) and
accepts the longest exactly-matching prefix plus one bonus token. The
window forward is bitwise-equal to sequential decode, so spec serving is
token-exact vs the non-spec engine; rejected tokens roll back by length
bookkeeping (dense) plus O(1) tail-page reclamation (paged). Each round
emits 1..k+1 tokens per live slot.

Chunked prefill + SLO scheduling (DESIGN.md §14): ``sched=SchedConfig``
replaces grouped whole-prompt admission with chunked prefill — a request
is admitted the moment a slot (and, paged, its prompt's pages) is free,
then its prompt streams into the cache ``chunk_tokens`` at a time,
co-scheduled with the decode batch under a per-step token budget: the
decode batch is charged first, mid-prefill requests split the residual
(earliest TTFT deadline first, deadline-pressed requests claiming the
whole residual). The chunk forward reuses the (B, S) decode window that
speculative verify proved bitwise-equal to sequential decode, so chunked
output streams are token-exact vs whole-prompt admission. Admission
ordering comes from ``sched.SLOQueue`` (priority + earliest deadline,
preserving preempt-at-head / retry-at-tail / backoff semantics), and
``run()`` grows exact p50/p90/p99 TTFT/TPOT aggregates plus per-class
SLO violation counts. Chunked prefill shares spec's model restrictions
(attention-only, ``cache_layout='bshd'``, no sliding window): the
prefilling slots ride the decode batch as garbage lanes, which only the
overwrite-before-read attention argument makes safe.

Fault tolerance (DESIGN.md §11): every decode/verify step runs a jit'd
finite check over each slot's logits; a slot with non-finite logits is
*quarantined* — its uncommitted token is dropped, its slot/pages released,
and the request replays from its prompt (greedy determinism makes the
retry token-exact) up to ``ResilienceConfig.max_retries`` attempts before
terminating ``failed`` with a reason code. Per-request wall-clock
deadlines cancel requests wherever they are (queued or mid-decode). A
``FaultConfig`` arms the seeded chaos injector (NaN logits, forced page
OOM, slow steps, draft failures); the degradation ladder auto-disables
speculation below a rolling acceptance floor and pauses admission under
page-pool pressure. All of it surfaces in ``run()`` under ``faults{...}``.

Observability (DESIGN.md §15): every step is an ``engine.step`` profiler
step annotation with the work in it as nested ``engine.*`` spans
(``obs.trace.phase``), and every jitted program has a stable name
(``engine_prefill``, ``engine_chunk_window``, ``engine_decode``,
``engine_draft``, ``engine_verify``), so a profiler trace puts each
device program and each host gap down to a phase. Where each program is
dispatched the engine counts the rows it computes and the rows that
carry a token (``rows_computed.<phase>`` / ``rows_real.<phase>``).
"""
from __future__ import annotations

import collections
import dataclasses
import logging
import time
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.kernels import ops as kops
from repro.models import LM
from repro.obs import clock as obs_clock
from repro.obs.metrics import MetricsRegistry, RunningStat, percentiles
from repro.obs.trace import phase
from repro.serving.faults import (FAIL_DEADLINE, FAIL_NUMERIC, FaultConfig,
                                  FaultInjector, ResilienceConfig)
from repro.serving.queue import Request, RequestQueue
from repro.serving.sched import ChunkRunner, SchedConfig, SLOQueue
from repro.serving.sched.chunker import checks_and_stats
from repro.serving.sched.slo import plan_chunks
from repro.serving.slots import SlotPool

log = logging.getLogger("repro.serving")

# both primitives moved to repro.obs.metrics (DESIGN.md §15); the old
# private names stay importable for anything that grew against them
_RunningStat = RunningStat
_pcts = percentiles

# a step this many times slower than the step-time EWMA is a straggler —
# generous because serving steps legitimately vary (whole-prompt prefill
# vs GEMV decode); the signal targets pathological stalls, not phase mix
_STRAGGLER_FACTOR = 8.0

# the phases whose programs compute token rows, each with a pair of row
# counters (see ``ContinuousScheduler.__init__``)
ROW_PHASES = ("prefill", "chunk", "decode", "verify")
# the phases whose programs route rows through MoE layers with a token mask
MOE_PHASES = ("chunk", "decode")


class ContinuousScheduler:
    def __init__(self, cfg: ModelConfig, max_slots: int, max_len: int,
                 eos_id: Optional[int] = None, *, cache: str = "dense",
                 page_size: int = 16, n_pages: int = 0,
                 kv_dtype: Optional[str] = None, prefix_cache: bool = True,
                 paged_attn: Optional[str] = None, spec=None,
                 faults: Optional[FaultConfig] = None,
                 resilience: Optional[ResilienceConfig] = None,
                 mesh=None, sched: Optional[SchedConfig] = None,
                 tracer=None):
        if cfg.is_encdec or cfg.family == "vlm":
            raise ValueError(
                f"family {cfg.family!r} needs per-request encoder/frontend "
                "state; use the static BatchedServer for it")
        assert cache in ("dense", "paged"), cache
        # paged_attn=None inherits cfg.paged_attn_impl; an explicit value
        # overrides it for this engine only. "auto" resolves here, once, to
        # the lowering this backend runs (visible on engine.cfg)
        if cache == "paged":
            impl = kops.resolve_paged_attn(paged_attn or cfg.paged_attn_impl)
            cfg = dataclasses.replace(cfg, paged_attn_impl=impl)
        self.cfg = cfg
        self.cache_mode = cache
        # every ad-hoc `self.x = 0; self.x += 1` counter below is
        # registry-backed (DESIGN.md §15) behind unchanged attribute
        # names — see the property block after the class body
        self.metrics = MetricsRegistry()
        # obs.trace.Tracer or None; None is the zero-cost path (one
        # attribute test per site, no clock read, no event)
        self.tracer = tracer
        self._trace_pid = tracer.new_pid("engine") if tracer is not None else 0
        if tracer is not None:
            tracer.thread_name(self._trace_pid, 0, "scheduler")
        # mesh != None = tensor-parallel engine (DESIGN.md §13): params
        # shard over the mesh's "model" axis at load(), the KV cache over
        # its head dim, and every jit below runs under GSPMD on the
        # mesh's devices. mesh=None is the unchanged single-device path.
        self.mesh = mesh
        self.model = LM(cfg)
        self.max_slots = max_slots
        self.max_len = max_len
        self.eos_id = eos_id
        if spec is not None:
            if spec.k < 1:
                raise ValueError(f"spec.k must be >= 1, got {spec.k}")
            if max_len < spec.k + 2:
                raise ValueError(f"max_len={max_len} leaves no room for a "
                                 f"k={spec.k} verify window")
            if any(kind != "attn" for kind, _ in self.model.block_kinds):
                raise ValueError(
                    "speculative decoding needs an attention-only stack: "
                    "SSM recurrent state advanced past a rejected token "
                    "cannot be rolled back by position bookkeeping")
            if cfg.cache_layout == "opt":
                raise ValueError("speculative decoding needs "
                                 "cache_layout='bshd' (the 'opt' "
                                 "delta-commit layout is one-token-only)")
            if cfg.sliding_window:
                raise ValueError(
                    "speculative decoding does not support rolling "
                    "sliding-window caches: a rejected window write "
                    "overwrites the oldest live entry, which rollback "
                    "cannot restore")
            if self.model.n_lead or any(cfg.layer_windows):
                raise ValueError(
                    "speculative decoding needs a stack without leading "
                    "layers or per-layer windows: the draft is a slice "
                    "of whole scan groups")
        self.spec = spec
        # ---- chunked prefill + SLO admission (DESIGN.md §14) ----
        if sched is not None and sched.chunked:
            if any(kind != "attn" for kind, _ in self.model.block_kinds):
                raise ValueError(
                    "chunked prefill needs an attention-only stack: "
                    "mid-prefill slots ride the decode batch as garbage "
                    "lanes, and SSM recurrent state advanced on garbage "
                    "tokens cannot be overwritten later")
            if cfg.cache_layout == "opt":
                raise ValueError("chunked prefill needs "
                                 "cache_layout='bshd' (the 'opt' "
                                 "delta-commit layout is one-token-only)")
            if cfg.sliding_window:
                raise ValueError(
                    "chunked prefill does not support rolling "
                    "sliding-window caches: padded chunk-window writes "
                    "would overwrite live rolled entries")
        self.sched = sched
        self.params = None
        self.queue = (SLOQueue() if sched is not None
                      and sched.admission == "slo" else RequestQueue())
        self._chunker = (ChunkRunner(self.model, max_len,
                                     paged=cache == "paged",
                                     rows=max_slots)
                         if sched is not None and sched.chunked else None)
        self._prefills: Dict[int, Request] = {}      # slot -> mid-prefill
        self.chunk_steps = 0
        self.chunk_tokens_committed = 0
        self.prefill_completions = 0
        self._chunk_meta = None       # last plan_chunks meta, for tracing
        # recent per-step wall time (EWMA) — drives the budgeter's
        # deadline-pressure and TPOT-protection heuristics, and (with
        # _STRAGGLER_FACTOR) flags anomalous steps through the same
        # registry mechanism the train supervisor's watchdog uses
        self._step_time = self.metrics.ewma("step_time_s", alpha=0.3)
        # (rows computed, rows that carry a token) per phase, counted
        # where each program is dispatched; held as attributes so the hot
        # path does no lookup by name
        (self._rows_prefill, self._rows_chunk, self._rows_decode,
         self._rows_verify) = [
            (self.metrics.counter(f"rows_computed.{ph}"),
             self.metrics.counter(f"rows_real.{ph}")) for ph in ROW_PHASES]
        # MoE layers' (row, held expert) pairs computed and held experts
        # with a row, summed over the layers; each program returns them
        # in the tail of its finite-check vector, read back with it
        self._has_moe = any(ffn == "moe" for _, ffn in
                            self.model.lead_kinds + self.model.block_kinds)
        self._experts_chunk, self._experts_decode = [
            (self.metrics.counter(f"expert_rows.{ph}"),
             self.metrics.counter(f"experts_active.{ph}"))
            for ph in MOE_PHASES] if self._has_moe else (None, None)
        if cache == "paged":
            from repro.paging import PagePool
            self.pool = PagePool(self.model, max_slots, max_len,
                                 page_size=page_size, n_pages=n_pages,
                                 kv_dtype=kv_dtype,
                                 prefix_cache=prefix_cache)
            self._dev_table = jnp.asarray(self.pool.table)
            self.pool.table_dirty = False
        else:
            self.pool = SlotPool(self.model, max_slots, max_len)
        self._live: Dict[int, Request] = {}          # slot -> request
        self._pos = np.zeros(max_slots, np.int32)    # host mirror
        self._tok = np.zeros(max_slots, np.int32)    # host mirror
        # spec: second-newest committed token per slot (the draft round's
        # re-sync feed; see repro.spec.draft.make_draft_round)
        self._prev_tok = np.zeros(max_slots, np.int32)
        self._dev_pos = jnp.zeros(max_slots, jnp.int32)
        self._dev_tok = jnp.zeros(max_slots, jnp.int32)
        # rows of the decode batch that carry a request (MoE routing mask)
        self._dev_live = jnp.zeros(max_slots, jnp.bool_)
        self._dev_prev = jnp.zeros(max_slots, jnp.int32)
        self._dirty = False           # host mirrors newer than device state
        self._finished: List[Request] = []
        self.total_drained = 0
        self.prefill_steps = 0
        self.decode_steps = 0
        self.preemptions = 0
        self.deferrals = 0
        self.spec_rounds = 0
        self.spec_slot_rounds = 0
        self.spec_proposed = 0
        self.spec_accepted = 0
        self.spec_emitted = 0
        self.spec_page_reclaims = 0
        self._depth_stat = RunningStat("queue_depth")
        self._live_stat = RunningStat("live_slots")

        # ---- fault tolerance (DESIGN.md §11) ----
        self.resilience = resilience or ResilienceConfig()
        self.injector = FaultInjector(faults) if faults is not None else None
        self._step_no = 0
        self._any_deadline = self.resilience.deadline_s is not None
        self.quarantines = 0
        self.fault_retries = 0
        self.failed_requests = 0
        self.admission_pauses = 0
        self.deadline_cancels = 0
        self.spec_disabled = False
        self.spec_disables = 0
        self.draft_fallbacks = 0
        self._accept_ring = collections.deque(
            maxlen=max(self.resilience.spec_floor_window, 1))
        # all-false NaN mask: the fault-free guard input (where() with an
        # all-false mask is bitwise-neutral on the logits)
        self._no_nan = jnp.zeros((max_slots,), jnp.bool_)

        if self.mesh is not None:
            # Commit every persistent device buffer onto the mesh now:
            # freshly created arrays are committed to the default device,
            # and mixing those with mesh-committed params in one jit is a
            # placement error. The KV cache shards on the head dim
            # (matching the column-split K/V projections); the small
            # scheduler vectors replicate. Host pushes inside step()
            # (jnp.asarray of a numpy mirror) stay uncommitted and follow
            # the computation, so only the init-time buffers need this.
            from repro.distributed import tp as tp_lib
            self.pool.layers = tp_lib.device_put_cache(
                self.pool.layers, cfg, self.mesh)
            (self._dev_pos, self._dev_tok, self._dev_prev,
             self._no_nan, self._dev_live) = jax.device_put(
                (self._dev_pos, self._dev_tok, self._dev_prev,
                 self._no_nan, self._dev_live),
                tp_lib.replicated_sharding(
                    (self._dev_pos, self._dev_tok, self._dev_prev,
                     self._no_nan, self._dev_live), self.mesh))
            if cache == "paged":
                self._dev_table = jax.device_put(
                    self._dev_table,
                    tp_lib.replicated_sharding(self._dev_table, self.mesh))

        # each jit's function name names its program in a profiler trace
        def engine_prefill(params, toks):
            # paged: a page-aligned cache length, the pool scatters whole
            # pages
            length = (max_len if cache == "dense"
                      else -(-toks.shape[1] // page_size) * page_size)
            cache_, logits = self.model.prefill(params, {"tokens": toks},
                                                length)
            return cache_["layers"], jnp.argmax(logits[:, -1],
                                                axis=-1).astype(jnp.int32)

        def engine_decode(params, layers, pos, toks, nan_mask, live,
                          table=None):
            # free slots keep decoding garbage; clamp their write position
            # so it can never run past the cache (live rows are bounded by
            # the submit-time prompt+budget <= max_len assertion). Paged,
            # their block tables are all-zero, so the clamped garbage
            # writes land in the pool's reserved trash page 0. Rows not
            # ``live`` route to no expert
            cache_ = {"layers": layers,
                      "pos": jnp.minimum(pos, max_len - 1),
                      "token_mask": live[:, None]}
            if table is not None:
                cache_["block_table"] = table
            logits, new_cache, stats = self.model.decode_step(
                params, cache_, toks[:, None], with_stats=True)
            # §11 numerical guard: fault injection corrupts masked rows
            # *before* the finite check (all-false mask = bitwise no-op);
            # a non-finite row quarantines its slot instead of committing
            row = jnp.where(nan_mask[:, None], jnp.nan, logits[:, 0, :])
            ok = jnp.all(jnp.isfinite(row), axis=-1)
            nxt = jnp.argmax(row, axis=-1).astype(jnp.int32)
            return (new_cache["layers"], new_cache["pos"], nxt,
                    checks_and_stats(ok, stats))

        self._prefill = jax.jit(engine_prefill)
        # one program; the paged path calls it as ``_decode_paged`` (the
        # attribute the benchmark's altered-token test swaps)
        self._decode = self._decode_paged = jax.jit(engine_decode,
                                                    donate_argnums=(1,))

    # ------------------------------------------------------------------
    def load(self, params) -> None:
        """Install params and precompute phase-keyed GEMM plans.

        Packed ``TernaryWeight`` containers live directly in the param
        pytree; for each of them every (M-bucket, phase) the hot loop can
        dispatch is planned *now* — prefill at the power-of-two M buckets
        up to ``max_slots * max_len`` (admission groups flatten to
        M = batch·prompt_len rows) and decode at M = ``max_slots`` — so the
        autotuner cache is warm before the first request and no serving
        step pays a first-call tune or cache write.

        With a mesh, params are placed first: the model's logical
        PartitionSpecs resolve against the mesh (packed spec twins
        validated for pack-multiple shard boundaries) and the tree is
        ``device_put`` accordingly, so every jit below runs GSPMD-sharded.
        The precomputed plans then read each placed array's sharding and
        record the per-shard problem plus its collective (DESIGN.md §13)."""
        shard_fn = None
        if self.mesh is not None:
            from repro.distributed import tp as tp_lib
            _, spec_tree = self.model.init_with_specs_abstract()
            params = tp_lib.shard_params(params, spec_tree, self.mesh)
            shard_fn = tp_lib.gemm_shard_fn(self.mesh)
        self.params = params
        top = max(self.max_slots * self.max_len, 1)
        # every pow2 bucket from M=1 up: a single short-prompt admission
        # (M = prompt_len < 8) must hit a warm entry too
        prefill_ms = [1 << i for i in range((top - 1).bit_length() + 1)]
        from repro.models.layers import gemm_impl
        is_packed_linear = (lambda path, w:
                            getattr(path[-1], "key", None) == "w_packed")
        # chunk windows flatten to M = P·S rows, P <= max_slots rows of at
        # most chunk_tokens each (a deadline-pressed row can claim the
        # whole step budget) — warm every pow2 bucket up to that ceiling
        # under the "chunk" phase (DESIGN.md §14)
        chunk_ms = ()
        if self._chunker is not None:
            ctop = max(self.max_slots * self.sched.budget_for(
                self.max_slots, self.spec.k if self.spec else 0), 1)
            ctop = min(ctop, top)
            chunk_ms = [1 << i for i in range((ctop - 1).bit_length() + 1)]
        self.gemm_plans = kops.precompute_plans(
            params, prefill_ms=prefill_ms, decode_ms=(self.max_slots,),
            # verify windows flatten to M = slots·(k+1) rows; their plans
            # key under the "verify" phase so they never thrash the GEMV
            # decode entries (DESIGN.md §10)
            verify_ms=((self.max_slots * (self.spec.k + 1),)
                       if self.spec else ()),
            chunk_ms=chunk_ms,
            # only packed linears dispatch through ternary_gemm; MoE expert
            # banks run through moe_expert_gemm, which takes no plan
            select=is_packed_linear,
            # warm exactly the impl linear_apply will dispatch ("ref"
            # off-TPU touches no autotune state)
            impl=gemm_impl(self.cfg),
            shard=shard_fn)
        # fused-MLP plans warm alongside (mlp_apply dispatches the fused
        # lowering for fully-packed MLP blocks when the Pallas path is on —
        # the fused autotune keys must be resolved before the hot loop too)
        if getattr(self.cfg, "fused_mlp", "auto") != "off" \
                and gemm_impl(self.cfg) != "ref":
            self.fused_plans = kops.precompute_fused_plans(
                params, prefill_ms=prefill_ms, decode_ms=(self.max_slots,),
                verify_ms=((self.max_slots * (self.spec.k + 1),)
                           if self.spec else ()),
                chunk_ms=chunk_ms,
                tp=(1 if self.mesh is None else
                    dict(self.mesh.shape).get("model", 1)))
        else:
            self.fused_plans = {}
        if self.spec is not None:
            from repro import spec as spec_lib
            self.draft = spec_lib.build_draft(self.spec, self.model, params)
            dlm = self.draft.model
            self._draft_layers = dlm.init_cache(self.max_slots,
                                                self.max_len)["layers"]
            if self.mesh is not None:
                # the draft is cheap: replicate it (and its cache) on the
                # mesh rather than TP-sharding it — token exactness needs
                # only the target's shards, and a replicated draft keeps
                # the draft round free of collectives
                from repro.distributed import tp as tp_lib
                dparams = jax.device_put(
                    self.draft.params, tp_lib.replicated_sharding(
                        self.draft.params, self.mesh))
                if dataclasses.is_dataclass(self.draft):
                    self.draft = dataclasses.replace(self.draft,
                                                     params=dparams)
                else:
                    self.draft.params = dparams
                self._draft_layers = jax.device_put(
                    self._draft_layers, tp_lib.replicated_sharding(
                        self._draft_layers, self.mesh))
            self._draft_insert = jax.jit(dlm.insert_cache,
                                         donate_argnums=(0,))

            def engine_draft_prefill(dp, toks):
                c, _ = dlm.prefill(dp, {"tokens": toks}, self.max_len)
                return c["layers"]

            self._draft_prefill = jax.jit(engine_draft_prefill)
            self._draft_round = spec_lib.make_draft_round(
                self.draft, self.max_len, self.spec.k)
            self._verify = spec_lib.make_verify_step(
                self.model, self.max_len, self.spec.k,
                paged=self.cache_mode == "paged", guard=True)
            # the draft's own packed GEMV decodes warm under "decode" too
            self.gemm_plans.update(
                (("draft",) + key, plan) for key, plan in
                kops.precompute_plans(
                    self.draft.params, decode_ms=(self.max_slots,),
                    select=is_packed_linear,
                    impl=gemm_impl(dlm.cfg)).items())
        if self._chunker is not None:
            # XLA-compile every chunk-window shape before traffic: rows
            # are always padded to max_slots and plan_chunks quantizes S
            # to powers of two <= min(budget, max_len), so the shape set
            # is small and closed — a mid-traffic compile costs seconds
            # and would wreck the p99 the scheduler exists to protect
            smax = min(self.sched.budget_for(
                self.max_slots, self.spec.k if self.spec else 0),
                self.max_len)
            with kops.tensor_parallel(self.mesh):
                self._chunker.warmup(
                    self.params, self.pool,
                    [1 << i for i in range(smax.bit_length())])

    def submit(self, prompt: np.ndarray, max_new: int, *,
               deadline_s: Optional[float] = None,
               max_retries: Optional[int] = None,
               slo=None, submit_t: Optional[float] = None) -> Request:
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        # spec mode reserves k positions of headroom: the last emitted
        # token's verify window writes up to position prompt+gen-1+k
        headroom = self.spec.k if self.spec is not None else 0
        assert prompt.size + max_new + headroom <= self.max_len, (
            f"prompt {prompt.size} + gen {max_new} + spec headroom "
            f"{headroom} exceeds max_len {self.max_len}")
        if deadline_s is None:
            deadline_s = self.resilience.deadline_s
        if deadline_s is not None:
            self._any_deadline = True
        req = self.queue.submit(prompt, max_new, eos_id=self.eos_id,
                                deadline_s=deadline_s,
                                max_retries=max_retries, slo=slo,
                                submit_t=submit_t)
        tr = self.tracer
        if tr is not None:
            tr.thread_name(self._trace_pid, req.rid + 1, f"req {req.rid}")
            tr.instant("submit", t=req.submit_t, cat="request",
                       pid=self._trace_pid, tid=req.rid + 1,
                       args={"rid": req.rid, "prompt_len": req.prompt_len,
                             "max_new": max_new,
                             "slo": slo.name if slo is not None else None})
        return req

    # ------------------------------------------------------------------
    # tracing helpers (DESIGN.md §15). Callers on hot paths guard with
    # `if self.tracer is not None` so the disabled engine pays exactly
    # one attribute test per site.
    def _trace_first_token(self, req: Request) -> None:
        """Retrospective TTFT components on the request's track, from the
        same clock stamps the metrics use: queue_wait (submit → admit)
        and prefill (admit → first token) sum to ``Request.ttft_s`` up
        to microsecond rounding."""
        tr, pid, tid = self.tracer, self._trace_pid, req.rid + 1
        tr.complete("queue_wait", req.submit_t, req.admit_t,
                    cat="request", pid=pid, tid=tid,
                    args={"rid": req.rid, "attempts": req.attempts})
        tr.complete("prefill", req.admit_t, req.first_token_t,
                    cat="request", pid=pid, tid=tid,
                    args={"rid": req.rid, "chunks": req.chunks})
        tr.instant("first_token", t=req.first_token_t, cat="request",
                   pid=pid, tid=tid, args={"rid": req.rid})

    def _trace_req(self, req: Request, name: str,
                   t: Optional[float] = None, **extra) -> None:
        tr = self.tracer
        if tr is not None:
            tr.instant(name, t=t, cat="request", pid=self._trace_pid,
                       tid=req.rid + 1, args={"rid": req.rid, **extra})

    # ------------------------------------------------------------------
    def _prefill_group(self, group) -> None:
        """Prefill one admitted group and wire up per-request state.
        ``group`` is ``[(request, slot, Admission|None)]`` — the admission
        carries the paged pool's page plan, ``None`` in dense mode. Shared
        between both cache modes so their bookkeeping cannot diverge."""
        t_admit = obs_clock.now()
        for req, _, _ in group:
            req.admit_t = t_admit       # slot granted; prefill starts now
        prompts = np.stack([r.prompt for r, _, _ in group])
        tr, pid = self.tracer, self._trace_pid
        with phase("engine.prefill", tr, pid):
            with kops.serving_phase("prefill"):
                req_layers, toks_dev = self._prefill(
                    self.params, jnp.asarray(prompts))
            if self.cache_mode == "paged":
                self.pool.insert([a for _, _, a in group], req_layers)
            else:
                self.pool.insert([s for _, s, _ in group], req_layers)
            if self.spec is not None:
                # the draft keeps its own dense KV cache of the same stream
                with kops.serving_phase("prefill"):
                    draft_layers = self._draft_prefill(self.draft.params,
                                                       jnp.asarray(prompts))
                self._draft_layers = self._draft_insert(
                    self._draft_layers, draft_layers,
                    jnp.asarray([s for _, s, _ in group]))
        self.prefill_steps += 1
        computed, real = self._rows_prefill
        computed.inc(prompts.size)
        real.inc(prompts.size)
        with phase("engine.prefill_readback", tr, pid):
            toks = np.asarray(toks_dev)
        now = obs_clock.now()
        for (req, slot, _), tok in zip(group, toks):
            req.slot = slot
            req.state = "live"
            req.tokens.append(int(tok))
            req.first_token_t = now
            self._pos[slot] = req.prompt_len
            self._tok[slot] = tok
            self._prev_tok[slot] = req.prompt[-1]
            self._live[slot] = req
            self._dirty = True
            if tr is not None:
                self._trace_first_token(req)
            if req.done:                 # max_new == 1 (or instant EOS)
                self._evict(slot)

    def _head_ready(self, now: float) -> bool:
        """Admission gate: queue non-empty and the head request past its
        retry-backoff window. FIFO order is preserved — a backing-off head
        stalls admission for this step rather than being skipped."""
        if self.queue.empty():
            return False
        return self.queue.peek().not_before <= now

    def _admission_paused(self) -> bool:
        """Degradation ladder rung 2 (DESIGN.md §11): under page-pool
        pressure, pause admission while live requests drain — shedding
        load *before* the preempt-and-replay storm rather than during."""
        frac = self.resilience.admission_pause_frac
        if (not frac or self.cache_mode != "paged"
                or not (self._live or self._prefills)
                or self.queue.empty()):
            return False
        if self.pool.n_free_pages / self.pool.usable_pages < frac:
            self.admission_pauses += 1
            tr = self.tracer
            if tr is not None:
                tr.instant("admission_pause", pid=self._trace_pid,
                           args={"free_page_frac": round(
                               self.pool.n_free_pages
                               / self.pool.usable_pages, 4)})
            return True
        return False

    def _admit_paged(self, now: float) -> None:
        """Paged admission: a request is admitted only when the page pool
        can cover its whole prompt (shared prefix pages + fresh pages,
        reclaiming cold prefix pages under pressure). A request the pool
        cannot place right now *defers* — admission stops for this step and
        retries after the next round of evictions frees pages."""
        while self._head_ready(now) and self.pool.n_free:
            adm = self.pool.admit(self.queue.peek().prompt)
            if adm is None:
                self.deferrals += 1
                self._trace_req(self.queue.peek(), "defer")
                return
            group = [(self.queue.pop(), adm.slot, adm)]
            plen = group[0][0].prompt_len
            deferred = False
            while (self._head_ready(now) and self.pool.n_free
                   and self.queue.peek().prompt_len == plen):
                nxt = self.pool.admit(self.queue.peek().prompt)
                if nxt is None:
                    self.deferrals += 1
                    deferred = True
                    break
                group.append((self.queue.pop(), nxt.slot, nxt))
            self._prefill_group(group)
            if deferred:    # already counted — don't re-attempt this step
                return

    def _admit_chunked(self, now: float) -> None:
        """Chunked admission (DESIGN.md §14): grant a slot (and, paged,
        the prompt's pages — private ones only, see ``PagePool.admit``'s
        ``use_prefix``) the moment one is free; no prefill forward runs
        here. The request enters ``_prefills`` at ``prefill_pos=0`` and
        streams its prompt in via ``_run_chunks`` over subsequent
        steps."""
        while self._head_ready(now) and self.pool.n_free:
            req = self.queue.peek()
            if self.cache_mode == "paged":
                adm = self.pool.admit(req.prompt, use_prefix=False)
                if adm is None:
                    self.deferrals += 1
                    self._trace_req(req, "defer")
                    return
                slot = adm.slot
            else:
                slot = self.pool.alloc()
            popped = self.queue.pop()
            assert popped is req, (popped.rid, req.rid)
            req.slot = slot
            req.state = "live"
            req.prefill_pos = 0
            req.admit_t = obs_clock.now()
            self._prefills[slot] = req
            self._trace_req(req, "admit", t=req.admit_t, slot=slot)

    def _admit(self) -> None:
        now = obs_clock.now()
        if self._admission_paused():
            return
        if self._chunker is not None:
            self._admit_chunked(now)
            return
        if self.cache_mode == "paged":
            self._admit_paged(now)
            return
        while self._head_ready(now) and self.pool.n_free:
            # grouped admission: prefill a FIFO run of equal-length prompts
            # (up to the free-slot count) as one batch — one kernel dispatch
            # and one pool scatter instead of k
            group = [self.queue.pop()]
            plen = group[0].prompt_len
            while (len(group) < self.pool.n_free and self._head_ready(now)
                   and self.queue.peek().prompt_len == plen):
                group.append(self.queue.pop())
            self._prefill_group(
                [(req, self.pool.alloc(), None) for req in group])

    def _release_slot(self, slot: int) -> Request:
        """Common tail of every live-slot exit: pop the request (from the
        decode batch or the mid-prefill set), return the slot's cache
        (pages or dense row) to its pool, zero the host mirrors. Shared
        by evict/preempt/quarantine/fail so slot accounting cannot
        diverge between the happy and failure paths."""
        req = self._live.pop(slot, None)
        if req is None:
            req = self._prefills.pop(slot)
        req.slot = None
        self._pos[slot] = 0
        self._tok[slot] = 0
        self._prev_tok[slot] = 0
        self._dirty = True
        if self.cache_mode == "paged":
            self.pool.release(slot)
        else:
            self.pool.free(slot)
        return req

    def _evict(self, slot: int) -> None:
        req = self._release_slot(slot)
        req.state = "done"
        req.done_t = obs_clock.now()
        self._finished.append(req)
        self.total_drained += 1
        tr = self.tracer
        if tr is not None and req.first_token_t is not None:
            # the decode phase as one retrospective span: its dur over
            # (gen_len - 1) tokens is exactly Request.tpot_s
            tr.complete("decode", req.first_token_t, req.done_t,
                        cat="request", pid=self._trace_pid,
                        tid=req.rid + 1,
                        args={"rid": req.rid, "tokens": len(req.tokens)})
            self._trace_req(req, "done", t=req.done_t,
                            tokens=len(req.tokens))

    def _replay(self, slot: int) -> Request:
        """Reset a live request for a from-scratch replay (preemption or
        quarantine retry). Greedy decode is deterministic, so the replay
        regenerates the exact same tokens — replays trade wasted compute
        for memory/robustness, never correctness."""
        req = self._release_slot(slot)
        req.tokens.clear()
        req.first_token_t = None
        req.admit_t = None            # re-stamped at the retry admission
        req.prefill_pos = 0           # chunked prefill restarts from 0
        req.spec_proposed = 0         # replay re-counts draft stats
        req.spec_accepted = 0
        return req

    def _preempt(self, slot: int) -> None:
        """Paged OOM recovery: release the slot's pages and replay the
        request from scratch later; it re-enters at the queue *head* (the
        oldest-never-preempted rule in ``_grow_paged`` guarantees drain
        progress)."""
        req = self._replay(slot)
        self.queue.push_front(req)
        self.preemptions += 1
        self._trace_req(req, "preempt", slot=slot)

    def _fail_live(self, slot: int, reason: str) -> None:
        """Terminal failure of an in-flight request: slot and pages are
        reclaimed exactly as on eviction (refcount-clean), the request
        drains with ``state="failed"`` + reason code instead of wedging
        the pool."""
        req = self._release_slot(slot)
        self._fail(req, reason)

    def _fail(self, req: Request, reason: str) -> None:
        req.state = "failed"
        req.fail_reason = reason
        req.done_t = obs_clock.now()
        self._finished.append(req)
        self.total_drained += 1
        self.failed_requests += 1
        self._trace_req(req, "failed", t=req.done_t, reason=reason,
                        attempts=req.attempts)
        log.warning("request %d failed: %s (attempts=%d, %d tokens in)",
                    req.rid, reason, req.attempts, len(req.tokens))

    def _quarantine(self, slot: int) -> None:
        """Numerical-guard response (DESIGN.md §11): the slot produced
        non-finite logits this step. Its uncommitted token is dropped and
        the request retries from scratch (token-exact by greedy
        determinism) with exponential backoff, up to its retry budget;
        other slots are untouched — one poisoned row never kills the
        batch."""
        req = self._live.get(slot) or self._prefills[slot]
        self.quarantines += 1
        req.attempts += 1
        retries = (req.max_retries if req.max_retries is not None
                   else self.resilience.max_retries)
        if req.attempts > retries:
            self._fail_live(slot, FAIL_NUMERIC)
            return
        self.fault_retries += 1
        backoff = self.resilience.retry_backoff_s
        req.not_before = (obs_clock.now()
                          + backoff * (2 ** (req.attempts - 1))
                          if backoff else 0.0)
        self._trace_req(req, "quarantine", slot=slot,
                        attempts=req.attempts)
        self.queue.requeue(self._replay(slot))
        log.warning("quarantined slot %d (request %d): non-finite logits; "
                    "retry %d/%d", slot, req.rid, req.attempts, retries)

    def _expire_deadlines(self) -> None:
        """Cancel every request past its wall-clock deadline — queued
        requests before they waste a prefill, live ones mid-decode (their
        slot/pages are reclaimed refcount-clean)."""
        if not self._any_deadline:
            return
        now = obs_clock.now()
        for req in self.queue.take_expired(now):
            self._fail(req, FAIL_DEADLINE)
            self.deadline_cancels += 1
        for slot in list(self._live):
            if self._live[slot].expired(now):
                self._fail_live(slot, FAIL_DEADLINE)
                self.deadline_cancels += 1
        for slot in list(self._prefills):
            if self._prefills[slot].expired(now):
                self._fail_live(slot, FAIL_DEADLINE)
                self.deadline_cancels += 1

    def _grow_paged(self, horizon: int = 1) -> None:
        """Before each paged decode step, make every live row's next
        ``horizon`` write positions appendable: allocate pages crossed into
        this step and COW shared pages about to be written (spec mode
        grows the whole k+1 verify window, so every speculative write
        lands in a privately owned page). When the pool is dry, preempt
        the *youngest* live request and retry — the oldest request is
        never preempted while others live, which guarantees drain
        progress."""
        for slot in list(self._live):
            if slot not in self._live:       # preempted by an earlier turn
                continue
            p = 0
            while p < horizon:
                if self.pool.ensure_append(slot, int(self._pos[slot]) + p):
                    p += 1
                    continue
                # preempt mid-prefill slots before decoding ones: they
                # have produced no tokens yet, so replaying them wastes
                # the least work — and the oldest-never-preempted rule
                # still holds (a decode slot outranks every prefill)
                victim = (next(reversed(self._prefills))
                          if self._prefills
                          else next(reversed(self._live)))
                self._preempt(victim)
                if victim == slot:
                    break

    def _run_chunks(self) -> None:
        """Advance every mid-prefill slot by its planned chunk
        (DESIGN.md §14): budget the step's residual tokens across
        ``_prefills`` (earliest TTFT deadline first), run one batched
        chunk window, then commit — a request whose prompt completes this
        step reads its first token from the window's last real position
        and joins the decode batch immediately (spec mode additionally
        catches the draft cache up with a B=1 whole-prompt draft
        prefill)."""
        if not self._prefills:
            self._chunk_meta = None
            return
        spec_active = self.spec is not None and not self.spec_disabled
        k = self.spec.k if spec_active else 0
        tr, pid = self.tracer, self._trace_pid
        with phase("engine.plan_chunks", tr, pid):
            tpots = [r.slo.tpot_target_s for r in self._live.values()
                     if r.slo is not None
                     and getattr(r.slo, "tpot_target_s", None) is not None]
            jobs, meta = plan_chunks(
                list(self._prefills.items()), cfg=self.sched,
                budget=self.sched.budget_for(self.max_slots, k),
                n_decode_tokens=len(self._live) * (1 + k),
                max_len=self.max_len, now=obs_clock.now(),
                step_s=self._step_ema,
                tpot_floor=min(tpots) if tpots else None)
        self._chunk_meta = meta
        if not jobs:
            return
        t_window = obs_clock.now()
        with phase("engine.chunk_window", tr, pid):
            greedy_dev, ok_dev = self._chunker.advance(self.params,
                                                       self.pool, jobs)
        self.chunk_steps += 1
        computed, real = self._rows_chunk
        computed.inc(greedy_dev.size)
        real.inc(sum(c for _, _, c in jobs))
        with phase("engine.chunk_readback", tr, pid):
            greedy = np.asarray(greedy_dev)
            ok = self._split_stats(np.asarray(ok_dev), self._experts_chunk)
        now = obs_clock.now()
        with phase("engine.commit", tr, pid):
            self._commit_chunks(jobs, greedy, ok, t_window, now)

    def _commit_chunks(self, jobs, greedy, ok, t_window: float,
                       now: float) -> None:
        """Commit one chunk window's results: advance each row's prefill
        frontier; a row whose prompt completes takes its first token and
        joins the decode batch (spec mode also prefills the draft)."""
        tr = self.tracer
        completed = []
        for i, (slot, req, c) in enumerate(jobs):
            if not ok[i]:
                self._quarantine(slot)
                continue
            if tr is not None:
                tr.complete("chunk", t_window, now, cat="request",
                            pid=self._trace_pid, tid=req.rid + 1,
                            args={"rid": req.rid, "tokens": c,
                                  "pos": req.prefill_pos})
            req.prefill_pos += c
            req.chunks += 1
            self.chunk_tokens_committed += c
            # the slot's garbage decode lane follows the prefill frontier;
            # its writes land at positions the next chunk (or the first
            # real decode) overwrites before any query attends there
            self._pos[slot] = req.prefill_pos
            self._dirty = True
            if req.prefill_pos >= req.prompt_len:
                tok = int(greedy[i, c - 1])
                del self._prefills[slot]
                self._live[slot] = req
                req.tokens.append(tok)
                req.first_token_t = now
                self._tok[slot] = tok
                self._prev_tok[slot] = int(req.prompt[-1])
                self.prefill_completions += 1
                if tr is not None:
                    self._trace_first_token(req)
                if req.done:             # max_new == 1 (or instant EOS)
                    self._evict(slot)
                elif self.spec is not None:
                    completed.append((slot, req))
        for slot, req in completed:
            # the draft runs its own dense whole-prompt prefill — cheap
            # (draft-sized), and chunking it would buy nothing since the
            # draft cache is not the serving-latency bottleneck
            with kops.serving_phase("prefill"):
                dl = self._draft_prefill(self.draft.params,
                                         jnp.asarray(req.prompt[None]))
            self._draft_layers = self._draft_insert(
                self._draft_layers, dl, jnp.asarray([slot]))

    def _plan_faults(self):
        """Draw this step's chaos schedule and apply the engine-external
        faults (sleep, armed page-OOM) immediately; NaN/draft faults are
        returned for the decode path to apply."""
        if self.injector is None:
            return None
        f = self.injector.plan(self._step_no)
        if f.slow:
            self.injector.count("slow_step")
            time.sleep(self.injector.cfg.slow_s)
        if f.oom and self.cache_mode == "paged":
            self.injector.count("page_oom")
            self.pool.inject_alloc_failures(self.injector.cfg.oom_burst)
        return f

    def _nan_mask(self, faults):
        """Device mask of the slots whose logits this step's schedule
        corrupts (all-false — a cached constant — when nothing fires)."""
        if faults is None or not faults.nan or not self._live:
            return self._no_nan
        victim = self.injector.choose_slot(list(self._live))
        mask = np.zeros(self.max_slots, bool)
        mask[victim] = True
        return jnp.asarray(mask)

    def step(self) -> None:
        """One scheduler iteration: inject scheduled faults, expire
        deadlines, admit (+ prefill, or advance chunked prefills), decode
        (or the spec draft -> verify -> rollback round) under the
        numerical guard, evict/quarantine. Model code traced here runs
        its Pallas kernels per shard of this engine's mesh."""
        with kops.tensor_parallel(self.mesh):
            self._step()

    def _step(self) -> None:
        self._step_no += 1
        with phase("engine.step", self.tracer, self._trace_pid,
                   step_num=self._step_no):
            self._step_phases()

    def _step_phases(self) -> None:
        t_step = obs_clock.now()
        tr, pid = self.tracer, self._trace_pid
        faults = self._plan_faults()
        with phase("engine.admit", tr, pid):
            self._expire_deadlines()
            self._depth_stat.push(self.queue.depth())
            self._admit()
        if self._chunker is not None:
            self._run_chunks()
        # a draft fault (or the acceptance-floor ladder) downgrades this
        # step to plain one-token decode; growth only needs horizon 1 then
        spec_active = self.spec is not None and not self.spec_disabled
        draft_down = (spec_active and faults is not None
                      and faults.draft_fail)
        if draft_down:
            self.injector.count("draft_fail")
            self.draft_fallbacks += 1
            if self.tracer is not None:
                self.tracer.instant("draft_fallback", pid=self._trace_pid,
                                    args={"step": self._step_no})
        if self.cache_mode == "paged":
            with phase("engine.grow_pages", tr, pid):
                self._grow_paged(1 + (self.spec.k if spec_active
                                      and not draft_down else 0))
        if not self._live:
            if self._prefills:       # chunk-only step: still real work
                self._note_step_time(t_step)
            return
        self._live_stat.push(len(self._live) + len(self._prefills))
        with phase("engine.upload", tr, pid):
            self._upload()
        if spec_active and not draft_down:
            self._step_spec(faults)
            self._note_step_time(t_step)
            return
        mask = self._nan_mask(faults)
        with phase("engine.decode", tr, pid):
            with kops.serving_phase("decode"):
                if self.cache_mode == "paged":
                    out = self._decode_paged(
                        self.params, self.pool.layers, self._dev_pos,
                        self._dev_tok, mask, self._dev_live,
                        self._dev_table)
                else:
                    out = self._decode(self.params, self.pool.layers,
                                       self._dev_pos, self._dev_tok, mask,
                                       self._dev_live)
                self.pool.layers, self._dev_pos, self._dev_tok, ok_dev = out
        self.decode_steps += 1
        with phase("engine.decode_readback", tr, pid):
            toks = np.asarray(self._dev_tok)
            ok = self._split_stats(np.asarray(ok_dev), self._experts_decode)
        committed = 0
        with phase("engine.commit", tr, pid):
            for slot in list(self._live):
                req = self._live[slot]
                if not ok[slot]:
                    self._quarantine(slot)
                    continue
                if self.spec is not None:
                    # keep the draft-round re-sync feed consistent across
                    # plain-decode fallback rounds (spec.draft docstring)
                    self._prev_tok[slot] = self._tok[slot]
                    self._dirty = True
                req.tokens.append(int(toks[slot]))
                committed += 1
                self._pos[slot] += 1
                self._tok[slot] = toks[slot]
                if req.done:
                    self._evict(slot)
        computed, real = self._rows_decode
        computed.inc(self.max_slots)
        real.inc(committed)
        self._note_step_time(t_step)

    def _upload(self) -> None:
        """Push the host mirrors the device is behind on: positions, tokens
        and live rows after admit/evict events, the block table after page
        growth."""
        if self._dirty:
            self._dev_pos = jnp.asarray(self._pos)
            self._dev_tok = jnp.asarray(self._tok)
            if self._has_moe:
                live = np.zeros(self.max_slots, bool)
                live[list(self._live)] = True
                self._dev_live = jnp.asarray(live)
            if self.spec is not None:
                self._dev_prev = jnp.asarray(self._prev_tok)
            self._dirty = False
        if self.cache_mode == "paged" and self.pool.table_dirty:
            self._dev_table = jnp.asarray(self.pool.table)
            self.pool.table_dirty = False

    def _split_stats(self, checks: np.ndarray, counters) -> np.ndarray:
        """A program's finite-check vector with its MoE stats in the tail
        (``checks_and_stats``): count the stats, return the checks."""
        n = checks.size - 2
        if counters is not None:
            for c, v in zip(counters, checks[n:]):
                c.inc(int(v))
        return checks[:n]

    @property
    def _step_ema(self) -> float:
        """Registry-backed EWMA of recent step wall time — the
        budgeter's clock for deadline pressure (how many steps fit
        before a TTFT deadline) and TPOT protection (is the step already
        slower than the tightest live target)."""
        return self._step_time.value or 0.0

    def _note_step_time(self, t0: float) -> None:
        """Feed the step-time EWMA, flag stragglers (same registry
        mechanism as the train supervisor's ``StragglerWatchdog``), and
        emit the per-step timeline counters."""
        dt = obs_clock.now() - t0
        prev = self._step_time.value
        self._step_time.update(dt)
        straggler = prev is not None and dt > _STRAGGLER_FACTOR * prev
        if straggler:
            self.metrics.counter("straggler_steps").inc()
        tr = self.tracer
        if tr is None:
            return
        if straggler:
            tr.instant("straggler_step", pid=self._trace_pid,
                       args={"dt_s": round(dt, 6),
                             "ewma_s": round(prev, 6)})
        tr.counter("sched", {"queue_depth": self.queue.depth(),
                             "live_slots": len(self._live),
                             "prefilling": len(self._prefills)},
                   pid=self._trace_pid)
        util = {"step_ms": round(dt * 1e3, 3)}
        if self.cache_mode == "paged":
            util["free_page_frac"] = round(
                self.pool.n_free_pages / self.pool.usable_pages, 4)
        meta = self._chunk_meta
        if meta is not None:
            util["token_budget_util"] = round(min(1.0, (
                meta["assigned"] + meta["decode_tokens"])
                / max(meta["budget"], 1)), 4)
        tr.counter("util", util, pid=self._trace_pid)

    def _step_spec(self, faults=None) -> None:
        """One speculative round (DESIGN.md §10): draft k tokens per slot
        from the draft's own cache, verify the (slots, k+1) window in one
        target forward, emit the accepted prefix + bonus token, roll the
        target cache back past the rejected tail."""
        k = self.spec.k
        tr, pid = self.tracer, self._trace_pid
        with phase("engine.draft", tr, pid):
            with kops.serving_phase("decode"):   # draft GEMMs are M=slots
                self._draft_layers, drafts = self._draft_round(
                    self.draft.params, self._draft_layers, self._dev_pos,
                    self._dev_prev, self._dev_tok)
        with phase("engine.verify", tr, pid):
            window = jnp.concatenate([self._dev_tok[:, None], drafts],
                                     axis=1)
            mask = self._nan_mask(faults)
            with kops.serving_phase("verify"):
                if self.cache_mode == "paged":
                    self.pool.layers, greedy, n_acc, _, ok_dev = \
                        self._verify(self.params, self.pool.layers,
                                     self._dev_table, self._dev_pos, window,
                                     mask)
                else:
                    self.pool.layers, greedy, n_acc, _, ok_dev = \
                        self._verify(self.params, self.pool.layers,
                                     self._dev_pos, window, mask)
        self.decode_steps += 1
        self.spec_rounds += 1
        with phase("engine.verify_readback", tr, pid):
            greedy = np.asarray(greedy)
            n_acc = np.asarray(n_acc)
            ok = np.asarray(ok_dev)
        with phase("engine.commit", tr, pid):
            emitted = self._commit_spec(greedy, n_acc, ok)
        computed, real = self._rows_verify
        computed.inc(greedy.size)
        real.inc(emitted)

    def _commit_spec(self, greedy, n_acc, ok) -> int:
        """Commit one verify window: each live slot takes its accepted
        drafts plus the bonus token and rolls back past the rejected
        tail; returns the tokens committed."""
        from repro.spec import rollback as rb
        k = self.spec.k
        tr, pid = self.tracer, self._trace_pid
        total = 0
        round_slots = 0
        round_accepted = 0
        for slot in list(self._live):
            req = self._live[slot]
            if not ok[slot]:
                # corrupted window: commit nothing from it — quarantine
                # replays the request from its prompt (token-exact under
                # greedy decode), so the NaN never reaches the output
                self._quarantine(slot)
                continue
            na = int(n_acc[slot])
            round_slots += 1
            round_accepted += na
            self.spec_slot_rounds += 1
            self.spec_proposed += k
            self.spec_accepted += na
            req.spec_proposed += k
            req.spec_accepted += na
            old_tok = int(self._tok[slot])
            emitted = 0
            for j in range(na + 1):               # accepted drafts + bonus
                req.tokens.append(int(greedy[slot, j]))
                emitted += 1
                if req.done:                      # budget / EOS mid-window
                    break
            self.spec_emitted += emitted
            total += emitted
            self._pos[slot] += emitted
            self._tok[slot] = int(greedy[slot, emitted - 1])
            self._prev_tok[slot] = (int(greedy[slot, emitted - 2])
                                    if emitted >= 2 else old_tok)
            self._dirty = True
            if req.done:
                self._evict(slot)                 # release() drops all pages
                continue
            with phase("engine.rollback", tr, pid):
                if self.cache_mode == "paged":
                    self.spec_page_reclaims += rb.rollback_paged(
                        self.pool, slot, int(self._pos[slot]))
                else:
                    # dense rollback is length bookkeeping only — the _pos
                    # update above IS the rollback (see spec.rollback)
                    rb.rollback_dense(self.pool, slot,
                                      int(self._pos[slot]))
        # degradation rung 1 (DESIGN.md §11): rolling acceptance floor.
        # A draft that stops agreeing with the target makes every round
        # cost a k+1-wide verify for ~1 emitted token — worse than plain
        # decode — so the engine sheds speculation instead of limping.
        floor = self.resilience.spec_accept_floor
        if floor > 0.0 and round_slots:
            self._accept_ring.append(round_accepted / (k * round_slots))
            if (len(self._accept_ring) == self._accept_ring.maxlen
                    and not self.spec_disabled):
                mean = sum(self._accept_ring) / len(self._accept_ring)
                if mean < floor:
                    self.spec_disabled = True
                    self.spec_disables += 1
                    if tr is not None:
                        tr.instant("spec_disabled", pid=self._trace_pid,
                                   args={"acceptance": round(mean, 4),
                                         "floor": floor})
                    log.warning(
                        "spec decoding disabled: rolling acceptance %.3f "
                        "< floor %.3f over %d rounds", mean, floor,
                        self._accept_ring.maxlen)
        return total

    # ------------------------------------------------------------------
    def has_work(self) -> bool:
        """Anything queued, mid-prefill, or decoding — the loop condition
        for external step drivers (``serving.traffic.run_open_loop``)."""
        return bool(self.queue) or bool(self._live) or bool(self._prefills)

    def begin_metrics(self) -> Dict[str, Any]:
        """Snapshot every cumulative counter and reset the windowed stats.
        ``run()`` calls this at entry; an external driver that steps the
        engine itself (the open-loop traffic harness) calls it before its
        own loop and ``collect_metrics`` after, so manually-driven spans
        report the same JSON ``run()`` would."""
        assert self.params is not None, "load(params) first"
        self._depth_stat = _RunningStat("queue_depth")
        self._live_stat = _RunningStat("live_slots")
        return {
            "t0": obs_clock.now(),
            "n0": self.total_drained,
            "p0": self.prefill_steps,
            "d0": self.decode_steps,
            "c0": (self.chunk_steps, self.chunk_tokens_committed,
                   self.prefill_completions),
            "s0": (self.spec_rounds, self.spec_proposed,
                   self.spec_accepted, self.spec_emitted,
                   self.spec_page_reclaims, self.spec_slot_rounds),
            "f0": {"quarantines": self.quarantines,
                   "retries": self.fault_retries,
                   "failed": self.failed_requests,
                   "pauses": self.admission_pauses,
                   "deadline_cancels": self.deadline_cancels,
                   "spec_disables": self.spec_disables,
                   "draft_fallbacks": self.draft_fallbacks,
                   "injected": (dict(self.injector.injected)
                                if self.injector else {})},
            "r0": self._row_counts(),
        }

    def _row_counts(self) -> Dict[str, Dict[str, int]]:
        """Rows computed and rows that carried a token, per phase."""
        return {ph: {"computed": c.value, "real": r.value}
                for ph, (c, r) in zip(ROW_PHASES, (
                    self._rows_prefill, self._rows_chunk,
                    self._rows_decode, self._rows_verify))}

    def run(self) -> Dict[str, Any]:
        """Drain the queue completely; return the metrics JSON dict."""
        snap = self.begin_metrics()
        budget = (self.queue.depth() + len(self._live)
                  + len(self._prefills)) * self.max_len + 1
        if self._chunker is not None:
            # chunked prefill spends up to prompt_len extra chunk steps
            # per request (worst case: the 1-token/step liveness trickle)
            budget *= 2
        if self.cache_mode == "paged":
            # preempt-and-replay re-runs requests; each replay costs at most
            # max_len extra steps and the oldest-never-preempted rule bounds
            # the churn, but give the watchdog generous headroom
            budget *= 8
        if self.injector is not None or self.resilience.max_retries > 0:
            # quarantine replays restart requests from the prompt, so each
            # of the max_retries attempts can cost another full generation
            budget *= 2 + self.resilience.max_retries
        idle = 0
        while self.queue or self._live or self._prefills:
            assert budget > 0, "scheduler failed to make progress"
            progress = (self.prefill_steps, self.decode_steps,
                        self.chunk_steps, self.total_drained)
            self.step()
            if (self.prefill_steps, self.decode_steps, self.chunk_steps,
                    self.total_drained) == progress:
                # idle tick — nothing live and the queue head is inside its
                # retry-backoff window. Waiting costs no work, so it must
                # not eat the progress budget; yield briefly instead.
                idle += 1
                assert idle < 1_000_000, "scheduler stuck on idle ticks"
                time.sleep(5e-4)
            else:
                idle = 0
                budget -= 1
        assert self.total_drained == self.queue.submitted, (
            "drained-request count != submitted count",
            self.total_drained, self.queue.submitted)
        return self.collect_metrics(snap)

    def _slo_report(self, done) -> Optional[Dict[str, Any]]:
        """Per-class SLO violation counts over a span's terminal
        requests. Targets are objectives, not guarantees — this is the
        honest scoreboard."""
        classes: Dict[str, Dict[str, Any]] = {}
        for r in done:
            if r.slo is None:
                continue
            ttft_t = getattr(r.slo, "ttft_target_s", None)
            tpot_t = getattr(r.slo, "tpot_target_s", None)
            c = classes.setdefault(r.slo.name, {
                "n": 0, "ttft_target_s": ttft_t, "tpot_target_s": tpot_t,
                "ttft_violations": 0, "tpot_violations": 0})
            c["n"] += 1
            if ttft_t is not None and r.ttft_s is not None \
                    and r.ttft_s > ttft_t:
                c["ttft_violations"] += 1
            if tpot_t is not None and r.tpot_s is not None \
                    and r.tpot_s > tpot_t:
                c["tpot_violations"] += 1
        return classes or None

    def collect_metrics(self, snap: Dict[str, Any]) -> Dict[str, Any]:
        """Build the metrics JSON for the span since ``begin_metrics``."""
        n0, p0, d0 = snap["n0"], snap["p0"], snap["d0"]
        s0, f0, c0 = snap["s0"], snap["f0"], snap["c0"]
        wall = obs_clock.now() - snap["t0"]
        done = self._finished[n0:]
        gen = sum(len(r.tokens) for r in done)
        ttfts = [r.ttft_s for r in done if r.ttft_s is not None]
        cache_metrics: Dict[str, Any] = {
            "mode": self.cache_mode,
            "nbytes": int(self.pool.nbytes),
        }
        if self.cache_mode == "paged":
            cache_metrics.update(self.pool.stats())
            cache_metrics["preemptions"] = self.preemptions
            cache_metrics["deferrals"] = self.deferrals
        spec_metrics = None
        if self.spec is not None:
            rounds = self.spec_rounds - s0[0]
            proposed = self.spec_proposed - s0[1]
            accepted = self.spec_accepted - s0[2]
            emitted = self.spec_emitted - s0[3]
            slot_rounds = self.spec_slot_rounds - s0[5]
            spec_metrics = {
                "draft": self.draft.name,
                "k": self.spec.k,
                "rounds": rounds,
                "draft_tokens_proposed": proposed,
                "draft_tokens_accepted": accepted,
                "acceptance_rate": (round(accepted / proposed, 4)
                                    if proposed else None),
                # emitted tokens per (slot, round): 1 (nothing accepted)
                # .. k+1 (whole window + bonus)
                "mean_accepted_len": (round(emitted / slot_rounds, 3)
                                      if slot_rounds else None),
                "rollback_page_reclaims": self.spec_page_reclaims - s0[4],
                "disabled": self.spec_disabled,
                "draft_fallbacks": (self.draft_fallbacks
                                    - f0["draft_fallbacks"]),
                "per_request": [
                    {"rid": r.rid, "proposed": r.spec_proposed,
                     "accepted": r.spec_accepted,
                     "rate": (round(r.spec_accepted / r.spec_proposed, 4)
                              if r.spec_proposed else None)}
                    for r in done],
            }
        return {
            "engine": "continuous",
            "max_slots": self.max_slots,
            "max_len": self.max_len,
            "mesh": (None if self.mesh is None else
                     {"tp": int(np.prod(list(dict(
                          self.mesh.shape).values()))),
                      "axes": dict(self.mesh.shape),
                      "collective_plans": sum(
                          1 for p in getattr(self, "gemm_plans",
                                             {}).values()
                          if getattr(p, "collective", None))}),
            "cache": cache_metrics,
            "spec": spec_metrics,
            "concurrency": {"peak": self._live_stat.peak,
                            "mean": round(self._live_stat.mean, 3)},
            "planned_gemms": len(getattr(self, "gemm_plans", {})),
            "per_request": [r.metrics() for r in done],
            "submitted": len(done),
            "drained": len(done),
            "generated_tokens": gen,
            "wall_s": round(wall, 4),
            "tok_per_s": round(gen / wall, 2) if wall > 0 else None,
            "prefill_steps": self.prefill_steps - p0,
            "decode_steps": self.decode_steps - d0,
            # rows the span's programs computed and rows that carried a
            # token, per phase: 1 - real / computed is the pad share
            "rows": {ph: {k: v - snap["r0"][ph][k] for k, v in n.items()}
                     for ph, n in self._row_counts().items()},
            "ttft_s": {"mean": float(np.mean(ttfts)) if ttfts else None,
                       "max": float(np.max(ttfts)) if ttfts else None},
            # exact percentile aggregates over the span's terminal
            # requests (DESIGN.md §14) — no reservoir approximation at
            # our scales
            "latency": {
                "ttft_s": _pcts(r.ttft_s for r in done),
                "queue_wait_s": _pcts(r.queue_wait_s for r in done),
                "prefill_s": _pcts(r.prefill_s for r in done),
                "tpot_s": _pcts(r.tpot_s for r in done),
                "e2e_s": _pcts(r.latency_s for r in done),
            },
            "sched": (None if self.sched is None else {
                "chunked_prefill": self._chunker is not None,
                "chunk_tokens": self.sched.chunk_tokens,
                "step_token_budget": self.sched.budget_for(
                    self.max_slots,
                    self.spec.k if self.spec is not None else 0),
                "admission": self.sched.admission,
                "chunk_steps": self.chunk_steps - c0[0],
                "chunk_tokens_committed":
                    self.chunk_tokens_committed - c0[1],
                "prefill_completions": self.prefill_completions - c0[2],
                "slo": self._slo_report(done),
            }),
            "queue_depth": {"max": self._depth_stat.peak,
                            "mean": self._depth_stat.mean},
            "faults": {
                "injected": {k: v - f0["injected"].get(k, 0)
                             for k, v in (self.injector.injected.items()
                                          if self.injector else ())},
                "quarantines": self.quarantines - f0["quarantines"],
                "retries": self.fault_retries - f0["retries"],
                "failed_requests": self.failed_requests - f0["failed"],
                "degradations": {
                    "spec_disabled": self.spec_disabled,
                    "spec_disables": (self.spec_disables
                                      - f0["spec_disables"]),
                    "admission_pauses": (self.admission_pauses
                                         - f0["pauses"]),
                    "deadline_cancellations": (self.deadline_cancels
                                               - f0["deadline_cancels"]),
                },
            },
        }


# ---------------------------------------------------------------------------
# Registry-backed scheduler counters (DESIGN.md §15). Call sites — and
# external readers like distributed.router and the test suite — keep the
# bare attribute idiom (``eng.total_drained += 1``); these properties
# route every read/write through the engine's MetricsRegistry, so
# ``engine.metrics.snapshot()`` sees the full counter set without a
# second bookkeeping path. ``spec_disabled`` stays a plain bool flag.
_ENGINE_COUNTERS = (
    "total_drained", "prefill_steps", "decode_steps", "preemptions",
    "deferrals", "spec_rounds", "spec_slot_rounds", "spec_proposed",
    "spec_accepted", "spec_emitted", "spec_page_reclaims", "chunk_steps",
    "chunk_tokens_committed", "prefill_completions", "quarantines",
    "fault_retries", "failed_requests", "admission_pauses",
    "deadline_cancels", "spec_disables", "draft_fallbacks",
)


def _counter_property(name: str) -> property:
    def _get(self):
        return self.metrics.counter(name).value

    def _set(self, v):
        self.metrics.counter(name).value = int(v)

    return property(_get, _set, doc=f"registry-backed counter {name!r}")


for _cname in _ENGINE_COUNTERS:
    setattr(ContinuousScheduler, _cname, _counter_property(_cname))
del _cname
