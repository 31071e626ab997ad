"""One run of one cell: set-up, a measured window, the output check.

Set-up builds the model from its configuration file with every projection
packed (``quantization="ternary_packed"``), fills the weights on the device
from the seed in one jitted call (``gen``), builds the continuous-batching
engine over the paged KV cache with chunked prefill as the cell's file
states, compiles the decode step and every chunk window (``engine.load``),
and runs the cell's traffic for its warm-up's number of decode steps. The
window then drives ``ContinuousScheduler.submit`` and ``.step()`` for
``seconds`` from one thread; every output token is stamped on the host
clock when the step that made it returns. After the window
the served tokens of a sample of finished requests are checked against
the plain float32 reference. What is particular to the model's family
(its ``ModelConfig``, its seeded weights, the work a step asks for and its
reference) comes from the cell's family module (``spec.load_family``).
"""
from __future__ import annotations

import dataclasses
import gc
import json
import os
import shutil
import sys
import tempfile
import time
from typing import Any, Callable, Dict, List, Optional

import numpy as np

import arrivals
import reduce
import spec as spec_lib

GRACE_S = 60.0       # how long past the window an answer may still come


def log(msg: str) -> None:
    print(f"bench: {msg}", file=sys.stderr, flush=True)


class NoChip(RuntimeError):
    """The machine lacks the accelerator the cell asks for."""


def load_peaks(bench_dir: str, kind: str) -> Dict[str, float]:
    with open(os.path.join(bench_dir, "peaks.json")) as f:
        table = json.load(f)["devices"]
    if kind not in table:
        raise NoChip(f"device kind {kind!r} is not in peaks.json "
                     f"({sorted(table)})")
    return table[kind]


def check_devices(chips: int) -> Dict[str, Any]:
    """The TPU devices the cell needs, or ``NoChip``."""
    import jax
    try:
        devs = jax.devices()
    except RuntimeError as e:
        raise NoChip(f"no JAX backend: {e}") from e
    if devs[0].platform != "tpu":
        raise NoChip(f"backend is {devs[0].platform!r}, not a TPU")
    if len(devs) < chips:
        raise NoChip(f"{len(devs)} chips found, the cell needs {chips}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": chips}


def compile_cache_dir(root: str) -> str:
    """The persistent compile cache: ``$JAX_COMPILATION_CACHE_DIR`` when
    set, else the fixed ``<checkout>/.jax_cache``."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        root, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileCounter:
    """Counts compilations and persistent-cache loads (JAX monitoring)."""

    EVENTS = ("/jax/core/compile/backend_compile_duration",
              "/jax/compilation_cache/cache_retrieval_time_sec")

    def __init__(self):
        import jax
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **kw) -> None:
        if event in self.EVENTS:
            self.n += 1


def _leaf_name(path) -> str:
    return "/".join(str(getattr(p, "key", getattr(p, "name", p)))
                    for p in path)


def make_params(model, seed: int, leaf: Callable):
    """Every leaf of the model's parameter tree, drawn on the device from
    the seed in one jitted call (no host copy, no float projection) by the
    family's ``leaf(root, name, shape, layer, k_in)``."""
    import jax
    import jax.numpy as jnp

    import gen
    shapes, _ = model.init_with_specs_abstract()
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    names = [_leaf_name(p) for p, _ in flat]
    sds = [s for _, s in flat]
    k_in = {n[:-len("packed")] + "scale": s.shape[-2] * 16
            for n, s in zip(names, sds) if n.endswith("w_packed/packed")}

    def build(root):
        out = []
        for n, s in zip(names, sds):
            if n.startswith("block"):
                out.append(jax.vmap(
                    lambda l, n=n, s=s: leaf(root, n, s.shape[1:], l,
                                             k_in.get(n, 0)))(
                    jnp.arange(s.shape[0])))
            else:
                out.append(leaf(root, n, s.shape, None, k_in.get(n, 0)))
        for o, s in zip(out, sds):
            assert o.shape == s.shape and o.dtype == s.dtype, (o, s)
        return out

    leaves = jax.jit(build)(gen.root_key(seed))
    return jax.tree_util.tree_unflatten(treedef, leaves)


def _counters(engine) -> Dict[str, int]:
    """The engine registry's counters (its integer readings), by name."""
    return {k: v for k, v in engine.metrics.snapshot().items()
            if isinstance(v, (int, np.integer)) and not isinstance(v, bool)}


@dataclasses.dataclass
class Rec:
    """One request as the driver saw it."""
    req: Any
    arrival: float                 # intended arrival (host clock)
    times: List[float] = dataclasses.field(default_factory=list)
    in_window: bool = False


@dataclasses.dataclass
class Window:
    t0: float
    t1: float
    tokens: int
    steps: int
    compiles: int
    occupancy: List[float]
    work: Any                      # the family's work accumulator
    submit_lag_s: float
    trace_dir: Optional[str] = None
    trace: Optional[Dict[str, Any]] = None


class Run:
    """Set-up, window and check of one cell at one seed."""

    def __init__(self, cell: spec_lib.Cell, seed: int, *,
                 peaks: Dict[str, float],
                 clock: Callable[[], float] = time.monotonic):
        self.cell = cell
        self.seed = int(seed)
        self.peaks = peaks
        self.clock = clock
        self.mix = arrivals.Mix.from_dict(cell.traffic)
        e = cell.engine
        if self.mix.longest > e["max_len"]:
            raise ValueError(f"{cell.traffic_name}: prompt + output "
                             f"{self.mix.longest} exceeds max_len "
                             f"{e['max_len']}")
        self.recs: List[Rec] = []
        self.live: List[Rec] = []
        self.next_arrival: Optional[float] = None
        self.pending: Optional[arrivals.Item] = None
        self.t_stop: Optional[float] = None

    # ------------------------------------------------------------------
    def setup(self) -> None:
        import jax
        from repro.models import LM
        from repro.serving import ContinuousScheduler
        from repro.serving.sched import SchedConfig
        c, e = self.cell.config, self.cell.engine
        family = self.cell.family
        self.cfg = family.model_config(c)
        model = LM(self.cfg)
        params = make_params(model, self.seed, family.leaf)
        jax.block_until_ready(params)
        self.engine = ContinuousScheduler(
            self.cfg, max_slots=e["max_slots"], max_len=e["max_len"],
            cache="paged", page_size=e["page_size"], n_pages=e["n_pages"],
            sched=SchedConfig(chunk_tokens=e["chunk_tokens"],
                              step_token_budget=e["step_token_budget"],
                              admission=e["admission"]))
        self.engine.load(params)
        del params
        self.source = arrivals.Source(self.mix, c["vocab_size"], self.seed)
        # the warm-up traffic: the same loop as the window, not counted
        self.start_arrivals(self.clock())
        eng = self.engine
        self.pump(until=float("inf"),
                  stop=lambda: eng.decode_steps >= self.mix.warmup_steps)

    # ------------------------------------------------------------------
    def start_arrivals(self, t: float) -> None:
        if self.mix.kind == "poisson":
            self.pending = self.source.take()
            self.next_arrival = t + self.pending.gap_s

    def _submit(self, item: arrivals.Item, t_arrival: float,
                in_window: bool) -> None:
        import jax
        with jax.profiler.TraceAnnotation("bench.submit"):
            req = self.engine.submit(item.prompt, item.max_new,
                                     submit_t=t_arrival)
        rec = Rec(req=req, arrival=t_arrival, in_window=in_window)
        self.recs.append(rec)
        self.live.append(rec)

    def _arrive(self, now: float, window) -> float:
        """Submit what is due; returns how late the latest submit ran."""
        lag = 0.0
        if self.mix.kind == "backlog":
            while self.engine.queue.depth() < self.mix.backlog:
                self._submit(self.source.take(), now, False)
            return lag
        while self.next_arrival is not None and self.next_arrival <= now:
            t = self.next_arrival
            lag = max(lag, now - t)
            self._submit(self.pending, t, window is not None
                         and window[0] <= t < window[1])
            self.pending = self.source.take()
            self.next_arrival = t + self.pending.gap_s
        return lag

    def _step(self, acc: Any) -> int:
        """One engine step; stamps the tokens it made and, with ``acc``,
        adds the work it asked of the chip and hands it the step's
        counter deltas. Returns tokens made."""
        import jax
        eng = self.engine
        before = [(r, len(r.req.tokens), r.req.prefill_pos)
                  for r in self.live]
        d0, c0 = eng.decode_steps, eng.chunk_steps
        seen = _counters(eng) if acc is not None else None
        with jax.profiler.TraceAnnotation("bench.engine_step"):
            eng.step()
        t = self.clock()
        made = 0
        decoded = eng.decode_steps > d0
        chunked = eng.chunk_steps > c0
        chunks: List[tuple] = []
        dec_rows: List[int] = []
        prefilling: List[int] = []
        for r, n0, p0 in before:
            req = r.req
            n1 = len(req.tokens)
            if n1 > n0:
                r.times.extend([t] * (n1 - n0))
                made += n1 - n0
            if req.prefill_pos > p0:
                chunks.append((p0, req.prefill_pos - p0))
            finished_prefill = n0 == 0 and n1 > 0
            dec = n1 - n0 - (1 if finished_prefill else 0)
            if dec > 0:
                dec_rows.append(req.prompt_len + n1 - 1)
            elif req.state == "live" and n1 == 0:
                prefilling.append(req.prefill_pos)
        if acc is not None:
            rows = eng.max_slots
            if chunked and chunks:
                s = max(c for _, c in chunks)
                real = sum(c for _, c in chunks)
                att_real = sum(c * p + c * (c + 1) // 2 for p, c in chunks)
                # a short row repeats its last token up to the window's
                # width; pad lanes sit at position 0
                att_pad = (rows - len(chunks)) * s * (s + 1) // 2 + sum(
                    (s - c) * p + s * (s + 1) // 2 - c * (c + 1) // 2
                    for p, c in chunks)
                # each row reads its slot's pages once for its S queries:
                # p + S keys for a job, the S of the trash page for a pad
                keys = sum(p for p, _ in chunks) + rows * s
                acc.forward(rows * s, att_real + att_pad, keys)
                acc.useful(real, att_real)
            if decoded:
                att = sum(dec_rows) + sum(p + 1 for p in prefilling) + (
                    rows - len(dec_rows) - len(prefilling))
                acc.forward(rows, att)
                acc.useful(len(dec_rows), sum(dec_rows))
            acc.counters({k: v - seen.get(k, 0)
                          for k, v in _counters(eng).items()})
        self.live = [r for r in self.live if not r.req.terminal]
        return made

    def pump(self, until: float, window=None,
             acc: Any = None,
             occupancy: Optional[List[float]] = None,
             stop: Optional[Callable[[], bool]] = None) -> Dict[str, float]:
        """Drive arrivals and steps until the clock passes ``until`` at a
        step boundary (or ``stop()`` holds). Returns counts."""
        import jax
        made, steps, lag = 0, 0, 0.0
        eng = self.engine
        while True:
            now = self.clock()
            if now >= until or (stop is not None and stop()):
                break
            lag = max(lag, self._arrive(now, window))
            if eng.has_work():
                made += self._step(acc)
                steps += 1
                if occupancy is not None:
                    occupancy.append(sum(1 for r in self.live
                                         if r.req.state == "live")
                                     / eng.max_slots)
            else:
                wait = (self.next_arrival - now
                        if self.next_arrival is not None else 0.0005)
                with jax.profiler.TraceAnnotation("bench.idle_wait"):
                    time.sleep(min(max(wait, 0.0), 0.002))
        return {"tokens": made, "steps": steps, "lag": lag,
                "t": self.clock()}

    # ------------------------------------------------------------------
    def window(self, seconds: float, counter: CompileCounter,
               trace: bool = False) -> Window:
        import jax
        tdir = None
        if trace:
            tdir = tempfile.mkdtemp(prefix="bench-trace-")
            jax.profiler.start_trace(tdir)
        acc = self.cell.family.work(self.cell.config, self.peaks)
        occ: List[float] = []
        n_compiles = counter.n
        t0 = self.clock()
        with jax.profiler.TraceAnnotation("bench.window"):
            got = self.pump(until=t0 + seconds, window=(t0, t0 + seconds),
                            acc=acc, occupancy=occ)
        t1 = got["t"]
        compiles = counter.n - n_compiles
        if trace:
            jax.profiler.stop_trace()
        return Window(t0=t0, t1=t1, tokens=got["tokens"], steps=got["steps"],
                      compiles=compiles, occupancy=occ, work=acc,
                      submit_lag_s=got["lag"], trace_dir=tdir)

    def read_trace(self, w: Window) -> None:
        """Reduce the window's trace into ``w.trace`` and delete it. Called
        after ``drain``, so that the requests in flight at the close are
        served before, not after, the minute or more the read takes."""
        if w.trace_dir is None:
            return
        w.trace = reduce.reduce_dir(w.trace_dir)
        shutil.rmtree(w.trace_dir, ignore_errors=True)
        w.trace_dir = None

    def drain(self, w: Window) -> None:
        """Keep the load on until every request that arrived in the window
        has finished, or ``GRACE_S`` of serving has passed. The grace runs
        from the drain's start: a traced run stops its profiler between
        the close and the drain, which can take longer than the grace."""
        if self.mix.kind == "backlog":
            return
        pending = [r for r in self.recs if r.in_window]
        self.pump(until=self.clock() + GRACE_S, window=None,
                  stop=lambda: all(r.req.terminal for r in pending))
        self.t_stop = self.clock()

    # ------------------------------------------------------------------
    def window_recs(self, w: Window) -> List[Rec]:
        """The requests the window is judged on: those that arrived in it
        (open loop), or those that finished in it (backlog)."""
        if self.mix.kind == "backlog":
            return [r for r in self.recs if r.req.done_t is not None
                    and w.t0 <= r.req.done_t <= w.t1]
        return [r for r in self.recs if r.in_window]

    def end_to_end(self, w: Window, setup_s: float) -> Dict[str, float]:
        recs = self.window_recs(w)
        out = {"setup_s": setup_s,
               "output_tok_s": arrivals.window_rate(w.tokens, w.t1 - w.t0)}
        if self.mix.kind == "poisson":
            # a request with no first token by the end of the drain counts
            # as missing (and as failed in the check): its TTFT is at
            # least the time it waited
            t_stop = self.t_stop or w.t1
            ttft = [(r.times[0] if r.times else t_stop) - r.arrival
                    for r in recs]
            gaps = [b - a for r in recs for a, b in zip(r.times, r.times[1:])]
            if not gaps:
                gaps = [t_stop - w.t0]
            out["ttft_p95_ms"] = 1e3 * arrivals.nearest_rank(ttft, 0.95)
            out["itl_p95_ms"] = 1e3 * arrivals.nearest_rank(gaps, 0.95)
            log(f"requests in window {len(recs)}; ttft p50 "
                f"{1e3 * arrivals.nearest_rank(ttft, 0.5):.1f} ms; itl p50 "
                f"{1e3 * arrivals.nearest_rank(gaps, 0.5):.2f} ms, longest "
                f"{1e3 * max(gaps):.1f} ms, over {len(gaps)} gaps")
        return out

    def context(self, w: Window) -> Dict[str, Any]:
        """What the per-layer readers read."""
        recs = self.window_recs(w)
        return {"trace": w.trace, "work": w.work, "peaks": self.peaks,
                "occupancy": w.occupancy,
                "queue_wait_s": [r.req.queue_wait_s for r in recs
                                 if r.req.queue_wait_s is not None]}

    def sample(self, w: Window, n: int) -> List[Rec]:
        """``n`` finished requests of the window, drawn from the seed,
        the one with the most served tokens always among them."""
        done = [r for r in self.window_recs(w) if r.req.state == "done"]
        if not done:
            return []
        longest = max(done, key=lambda r: len(r.req.tokens))
        rest = [r for r in done if r is not longest]
        rng = np.random.default_rng(self.seed)
        pick = rng.permutation(len(rest))[:max(n - 1, 0)]
        return [longest] + [rest[i] for i in sorted(pick)]

    def free(self) -> None:
        """Drop the program's state so the reference has the chip."""
        self.engine = None
        gc.collect()


def check_output(run: Run, w: Window) -> Dict[str, Dict[str, float]]:
    """Compare a sample of the window's finished requests with the plain
    reference; returns each number compared beside its limit."""
    e = run.cell.engine
    recs = run.window_recs(w)
    sample = run.sample(w, e["check_requests"])
    seqs = [(r.req.prompt, list(r.req.tokens)) for r in sample]
    failed = sum(1 for r in recs if r.req.state != "done")
    short = sum(1 for r in sample if len(r.req.tokens) != r.req.max_new)
    run.free()
    gap = max(run.cell.family.served_gaps(run.cell.config, run.seed, seqs)) \
        if seqs else None
    return {
        "max_logit_gap": {"value": gap, "limit": e["max_logit_gap"]},
        "checked_tokens": {"value": sum(len(s) for _, s in seqs),
                           "limit": e["min_checked_tokens"]},
        "failed_requests": {"value": failed, "limit": 0},
        "short_answers": {"value": short, "limit": 0},
    }


def passed(checks: Dict[str, Dict[str, float]]) -> bool:
    c = checks
    gap = c["max_logit_gap"]["value"]
    return (gap is not None and gap <= c["max_logit_gap"]["limit"]
            and c["checked_tokens"]["value"] >= c["checked_tokens"]["limit"]
            and c["failed_requests"]["value"] <= 0
            and c["short_answers"]["value"] <= 0)


def memory_peak() -> int:
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", 0))


def execute(cell: spec_lib.Cell, seed: int, seconds: float, trace: bool,
            t_process: float, device: Dict[str, Any],
            peaks: Dict[str, float]) -> Dict[str, Any]:
    """Set-up, window, check; returns the result object."""
    counter = CompileCounter()
    run = Run(cell, seed, peaks=peaks)
    run.setup()
    setup_s = time.monotonic() - t_process
    log(f"set-up {setup_s:.3f} s, {counter.n} compilations or cache loads")
    w = run.window(seconds, counter, trace=trace)
    log(f"window {w.t1 - w.t0:.3f} s: {w.steps} steps, {w.tokens} tokens, "
        f"{w.compiles} compilations inside the window, submit lag "
        f"{w.submit_lag_s * 1e3:.1f} ms")
    run.drain(w)
    run.read_trace(w)
    recs = run.window_recs(w)
    if trace:
        ctx = run.context(w)
        metrics = {}
        for m in cell.per_layer:
            v = cell.reader(m["name"])(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        vals = run.end_to_end(w, setup_s)
        metrics = {m["name"]: {"value": vals[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end}
    dev = dict(device)
    dev["memory_peak_bytes"] = memory_peak()
    if trace and w.trace is not None:
        dev["busy_s"] = w.trace["busy_s"]
        dev["window_s"] = w.trace["window_s"]
    checks = check_output(run, w)
    ok = passed(checks)
    out = {"correct": ok, "attempted": len(recs),
           "failed": checks["failed_requests"]["value"],
           "metrics": metrics, "device": dev}
    if trace and w.trace is not None:
        out["breakdown"] = {"device_ops": w.trace["device_ops"],
                            "idle_gaps": w.trace["idle_gaps"]}
    out["checks"] = checks
    return out
