"""Observability tests (DESIGN.md §15): the fake-able clock, the
ring-buffer tracer and its Perfetto-loadable export, the metrics
registry, the golden metrics-JSON schema (byte-compatibility lock for
``run()``/``collect_metrics``/``run_open_loop``), trace-vs-metrics
TTFT/TPOT agreement, the engine's phase spans in a profiler trace and
their reduction (``obs.xplane``), and the row counters."""
import collections
import json

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.obs import (MetricsRegistry, Tracer, load_trace, percentiles,
                       validate_events)
from repro.obs import clock as obs_clock
from repro.obs.clock import FakeClock, fake_clock
from repro.obs.metrics import Counter, Ewma, Gauge, Histogram, RunningStat
from repro.serving import ContinuousScheduler


def _cfg(**overrides):
    return get_config("ternary-paper", reduced=True, num_layers=2,
                      **overrides)


def _engine(cfg, slots=3, max_len=32, seed=0, **kw):
    eng = ContinuousScheduler(cfg, max_slots=slots, max_len=max_len, **kw)
    eng.load(eng.model.init(jax.random.PRNGKey(seed)))
    return eng


def _workload(cfg, n, prompt_len=16, seed=0, lens=(2, 9)):
    rng = np.random.default_rng(seed)
    prompts = rng.integers(0, cfg.vocab_size,
                           size=(n, prompt_len)).astype(np.int32)
    gens = [int(g) for g in rng.integers(lens[0], lens[1], size=n)]
    return prompts, gens


# ---------------------------------------------------------------------------
# clock
# ---------------------------------------------------------------------------

def test_clock_is_monotonic_and_fakeable():
    a, b = obs_clock.now(), obs_clock.now()
    assert b >= a
    with fake_clock(FakeClock(t0=100.0)) as fc:
        assert obs_clock.now() == 100.0
        fc.advance(2.5)
        assert obs_clock.now() == 102.5
    assert obs_clock.now() < 100.0 or obs_clock.now() != 102.5


def test_fake_clock_tick_advances_per_read():
    """Busy-wait loops (admission backoff, deadline sweeps) must observe
    progress under test — the optional tick adds on every read."""
    with fake_clock(tick=0.5) as fc:
        assert obs_clock.now() == 0.5
        assert obs_clock.now() == 1.0
        fc.advance(10.0)
        assert obs_clock.now() == 11.5


def test_fake_clock_rejects_rewind():
    with pytest.raises(AssertionError):
        FakeClock().advance(-1.0)


def test_set_clock_restores():
    prev = obs_clock.set_clock(lambda: 42.0)
    try:
        assert obs_clock.now() == 42.0
    finally:
        obs_clock.set_clock(prev)
    assert obs_clock.now() != 42.0


# ---------------------------------------------------------------------------
# tracer
# ---------------------------------------------------------------------------

def test_tracer_ring_drops_oldest():
    with fake_clock(tick=0.001) as fc:
        tr = Tracer(capacity=4, clock=fc)
        for i in range(10):
            tr.instant("ev", args={"i": i})
    assert len(tr) == 4
    assert tr.dropped == 6
    kept = [e["args"]["i"] for e in tr.events()]
    assert kept == [6, 7, 8, 9]          # newest survive
    # drop accounting reaches the export
    assert tr.to_dict()["otherData"]["dropped_events"] == 6


def test_tracer_metadata_survives_overflow():
    tr = Tracer(capacity=2)
    pid = tr.new_pid("engine")
    tr.thread_name(pid, 5, "req 4")
    for _ in range(10):
        tr.instant("x", pid=pid)
    meta = [e for e in tr.to_dict()["traceEvents"] if e["ph"] == "M"]
    names = {(e["name"], e.get("args", {}).get("name")) for e in meta}
    assert ("process_name", "engine") in names
    assert ("thread_name", "req 4") in names


def test_tracer_span_and_complete_agree():
    with fake_clock(FakeClock(t0=10.0)) as fc:
        tr = Tracer(clock=fc)
        with tr.span("work", args={"k": 1}):
            fc.advance(0.25)
        tr.complete("retro", 10.0, 10.25)
    evs = tr.events()
    assert [e["name"] for e in evs] == ["work", "retro"]
    for e in evs:
        assert e["ph"] == "X" and e["ts"] == 0 and e["dur"] == 250_000


def test_tracer_export_is_perfetto_loadable(tmp_path):
    with fake_clock(tick=0.001) as fc:
        tr = Tracer(clock=fc)
        pid = tr.new_pid("engine")
        with tr.span("step", pid=pid):
            pass
        tr.instant("mark", pid=pid, args={"rid": 3}, tid=4)
        tr.counter("sched", {"depth": 2.0}, pid=pid)
    path = str(tmp_path / "t.json")
    n = tr.export(path)
    doc = load_trace(path)
    assert isinstance(doc["traceEvents"], list) and len(doc["traceEvents"]) == n
    assert doc["displayTimeUnit"] == "ms"
    validate_events(doc["traceEvents"])
    phases = {e["ph"] for e in doc["traceEvents"]}
    assert {"M", "X", "i", "C"} <= phases


def test_validate_events_catches_track_mismatch():
    with pytest.raises(AssertionError):
        validate_events([{"ph": "i", "name": "x", "cat": "e", "ts": 0,
                          "pid": 0, "tid": 1, "args": {"rid": 5}}])


def test_tracer_counter_copies_values():
    tr = Tracer()
    vals = {"depth": 1.0}
    tr.counter("sched", vals)
    vals["depth"] = 99.0
    assert tr.events()[0]["args"]["depth"] == 1.0


def test_tracer_is_always_truthy():
    assert bool(Tracer()) and len(Tracer()) == 0


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------

def test_percentiles_shape_and_none():
    assert percentiles([]) is None
    assert percentiles([None, None]) is None
    p = percentiles([1.0, None, 3.0, 2.0])
    assert set(p) == {"p50", "p90", "p99", "mean", "max", "n"}
    assert p["n"] == 3 and p["p50"] == 2.0 and p["max"] == 3.0


def test_registry_get_or_create_and_kind_lock():
    reg = MetricsRegistry()
    c = reg.counter("hits")
    assert reg.counter("hits") is c and c.inc() == 1
    with pytest.raises(AssertionError):
        reg.gauge("hits")
    reg.gauge("depth").set(3)
    reg.histogram("lat").observe(0.5)
    reg.ewma("step", alpha=0.3).update(1.0)
    reg.stat("q").push(7)
    assert len(reg) == 5 and "hits" in reg
    snap = reg.snapshot()
    assert snap["hits"] == 1 and snap["depth"] == 3.0
    assert snap["lat"]["n"] == 1 and snap["q"]["peak"] == 7
    reg.reset("q")
    assert "q" not in reg


def test_ewma_seeding_and_update_math():
    e = Ewma("t", alpha=0.3)
    assert e.value is None
    assert e.update(2.0) == 2.0                 # first observation seeds
    assert abs(e.update(4.0) - (0.7 * 2.0 + 0.3 * 4.0)) < 1e-12


def test_histogram_windowed_but_exact_count():
    h = Histogram("lat", cap=4)
    for v in range(10):
        h.observe(float(v))
    p = h.percentiles()
    assert p["n"] == 10                          # exact total
    assert p["max"] <= 9.0 and p["p50"] >= 4.0   # window holds newest


def test_running_stat_exact_mean_peak():
    s = RunningStat("q", cap=2)
    for v in (1, 5, 3):
        s.push(v)
    assert s.n == 3 and s.peak == 5 and abs(s.mean - 3.0) < 1e-12
    assert len(s.ring) == 2                      # bounded detail


# ---------------------------------------------------------------------------
# engine integration: registry-backed counters, golden metrics schema
# ---------------------------------------------------------------------------

TOP_LEVEL_KEYS = {
    "engine", "max_slots", "max_len", "mesh", "cache", "spec",
    "concurrency", "planned_gemms", "per_request", "submitted", "drained",
    "generated_tokens", "wall_s", "tok_per_s", "prefill_steps",
    "decode_steps", "rows", "ttft_s", "latency", "sched", "queue_depth",
    "faults",
}
PER_REQUEST_KEYS = {
    "rid", "prompt_len", "gen_len", "ttft_s", "queue_wait_s", "prefill_s",
    "tpot_s", "latency_s", "state", "fail_reason", "attempts", "chunks",
    "slo",
}
LATENCY_KEYS = {"ttft_s", "queue_wait_s", "prefill_s", "tpot_s", "e2e_s"}
PCT_KEYS = {"p50", "p90", "p99", "mean", "max", "n"}
FAULTS_KEYS = {"injected", "quarantines", "retries", "failed_requests",
               "degradations"}
DEGRADATION_KEYS = {"spec_disabled", "spec_disables", "admission_pauses",
                    "deadline_cancellations"}
TRAFFIC_KEYS = {"n", "time_scale", "offered_rate", "degenerate_schedule",
                "makespan_s", "max_submit_lag_s"}


@pytest.fixture(scope="module")
def drained():
    cfg = _cfg()
    eng = _engine(cfg)
    prompts, gens = _workload(cfg, 5)
    for p, g in zip(prompts, gens):
        eng.submit(p, g)
    return eng, eng.run()


def test_metrics_json_golden_schema(drained):
    """The metrics JSON shape is load-bearing (CI parses it, docs quote
    it): registry-backing the counters must not change a single key."""
    _, m = drained
    assert set(m) == TOP_LEVEL_KEYS
    for r in m["per_request"]:
        assert set(r) == PER_REQUEST_KEYS
    assert set(m["latency"]) == LATENCY_KEYS
    for block in m["latency"].values():
        assert block is None or set(block) == PCT_KEYS
    assert set(m["faults"]) == FAULTS_KEYS
    assert set(m["faults"]["degradations"]) == DEGRADATION_KEYS
    assert set(m["ttft_s"]) == {"mean", "max"}
    assert set(m["queue_depth"]) == {"max", "mean"}
    assert set(m["concurrency"]) == {"peak", "mean"}
    assert m["cache"]["mode"] == "dense" and "nbytes" in m["cache"]
    assert set(m["rows"]) == {"prefill", "chunk", "decode", "verify"}
    for block in m["rows"].values():
        assert set(block) == {"computed", "real"}
    json.dumps(m)                                 # serializable end-to-end


def test_engine_counters_are_registry_backed(drained):
    eng, m = drained
    assert eng.total_drained == 5
    assert eng.metrics.counter("total_drained").value == 5
    snap = eng.metrics.snapshot()
    assert snap["decode_steps"] == eng.decode_steps > 0
    assert snap["step_time_s"] == pytest.approx(eng._step_ema)
    # writable through the attribute (legacy reset idiom)
    eng.deferrals = 7
    assert eng.metrics.counter("deferrals").value == 7
    eng.deferrals = 0


def test_traffic_block_golden_schema_and_degenerate_flag():
    from repro.serving import Arrival, run_open_loop
    cfg = _cfg()
    eng = _engine(cfg)
    rng = np.random.default_rng(0)

    def arrival(t):
        return Arrival(t=t, prompt=rng.integers(
            0, cfg.vocab_size, size=8, dtype=np.int32), max_new=2)

    # n=1: no arrival spacing exists — rate must be numeric 0.0, flagged
    _, m1 = run_open_loop(eng, [arrival(0.0)])
    assert set(m1["traffic"]) == TRAFFIC_KEYS
    assert m1["traffic"]["offered_rate"] == 0.0
    assert m1["traffic"]["degenerate_schedule"] is True

    # time_scale=0 burst: same degeneracy
    _, m0 = run_open_loop(eng, [arrival(0.0), arrival(1.0)], time_scale=0.0)
    assert m0["traffic"]["offered_rate"] == 0.0
    assert m0["traffic"]["degenerate_schedule"] is True

    # real spacing: rate = (n-1)/span, not flagged
    _, m2 = run_open_loop(eng, [arrival(0.0), arrival(0.05)])
    assert m2["traffic"]["offered_rate"] == pytest.approx(20.0)
    assert m2["traffic"]["degenerate_schedule"] is False


def test_queue_submit_stamps_obs_clock():
    from repro.serving.queue import RequestQueue
    with fake_clock(FakeClock(t0=500.0)):
        q = RequestQueue()
        req = q.submit(np.ones(4, np.int32), 2)
    assert req.submit_t == 500.0


# ---------------------------------------------------------------------------
# trace <-> metrics agreement
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def traced_run(tmp_path_factory):
    cfg = _cfg()
    tracer = Tracer(capacity=1 << 16)
    eng = _engine(cfg, tracer=tracer)
    prompts, gens = _workload(cfg, 6)
    reqs = [eng.submit(p, g) for p, g in zip(prompts, gens)]
    metrics = eng.run()
    path = str(tmp_path_factory.mktemp("trace") / "t.json")
    tracer.export(path)
    return reqs, metrics, load_trace(path)


def test_trace_file_is_valid_and_rid_consistent(traced_run):
    reqs, _, doc = traced_run
    evs = doc["traceEvents"]
    validate_events(evs)
    # every request's lifecycle landed on its own track with the full
    # span set: submit -> queue_wait -> prefill -> first_token ->
    # decode -> done
    for r in reqs:
        names = {e["name"] for e in evs
                 if (e.get("args") or {}).get("rid") == r.rid}
        assert {"submit", "queue_wait", "prefill", "first_token",
                "decode", "done"} <= names, (r.rid, names)


def test_trace_reconstructs_ttft_tpot(traced_run):
    """Trace spans are emitted from the same clock stamps the Request
    metrics use — TTFT (queue_wait + prefill) and TPOT (decode / (n-1))
    reconstructed from the file must agree with Request.metrics()."""
    reqs, _, doc = traced_run
    by_rid = {}
    for e in doc["traceEvents"]:
        rid = (e.get("args") or {}).get("rid")
        if rid is not None and e["ph"] == "X":
            by_rid.setdefault(rid, {})[e["name"]] = e
    for r in reqs:
        spans = by_rid[r.rid]
        mm = r.metrics()
        ttft = (spans["queue_wait"]["dur"] + spans["prefill"]["dur"]) / 1e6
        assert ttft == pytest.approx(mm["ttft_s"], abs=5e-3)
        if mm["tpot_s"] is not None and len(r.tokens) > 1:
            tpot = spans["decode"]["dur"] / 1e6 / (len(r.tokens) - 1)
            assert tpot == pytest.approx(mm["tpot_s"], abs=5e-3)


def test_engine_kernel_spans_emitted(traced_run):
    _, metrics, doc = traced_run
    evs = doc["traceEvents"]
    decode_spans = [e for e in evs
                    if e["ph"] == "X" and e["name"] == "engine.decode"]
    assert len(decode_spans) == metrics["decode_steps"]
    assert all(e["tid"] == 0 for e in decode_spans)
    # every phase span nests inside an engine.step span
    steps = [(e["ts"], e["ts"] + e["dur"]) for e in evs
             if e["ph"] == "X" and e["name"] == "engine.step"]
    phases = [e for e in evs if e["ph"] == "X" and e["tid"] == 0
              and e["name"].startswith("engine.")
              and e["name"] != "engine.step"]
    assert phases and all(
        any(a <= e["ts"] and e["ts"] + e["dur"] <= b for a, b in steps)
        for e in phases)
    counters = [e for e in evs if e["ph"] == "C" and e["name"] == "sched"]
    assert counters and all(
        {"queue_depth", "live_slots", "prefilling"} <= set(e["args"])
        for e in counters)


def test_trace_report_end_to_end(traced_run, tmp_path):
    import sys
    sys.path.insert(0, "scripts")
    try:
        import trace_report
    finally:
        sys.path.pop(0)
    _, metrics, doc = traced_run
    path = str(tmp_path / "t.json")
    with open(path, "w") as f:
        json.dump(doc, f)
    rep = trace_report.report(path)
    assert rep["step_breakdown"]["engine.decode"]["n"] == \
        metrics["decode_steps"]
    il = rep["interleave"]
    assert 0.0 < il["busy_frac"] <= 1.0
    assert il["busy_frac"] + il["bubble_frac"] == pytest.approx(1.0)
    assert len(rep["ttft_waterfall"]) == metrics["drained"]
    # waterfall agrees with the engine's own percentile source
    worst = rep["ttft_waterfall"][0]["ttft_s"]
    assert worst == pytest.approx(metrics["latency"]["ttft_s"]["max"],
                                  abs=5e-3)
    json.dumps(rep)


# ---------------------------------------------------------------------------
# engine phases: row counters, profiler spans, tracer parity
# ---------------------------------------------------------------------------

def _phase_engine(mode="chunked", tracer=None):
    """A small paged engine: chunked prefill, or grouped admission with a
    k=2 speculative round."""
    from repro.serving.sched import SchedConfig
    from repro.spec import SpecConfig
    cfg = get_config("ternary-paper", reduced=True, num_layers=4)
    kw = ({"sched": SchedConfig(chunk_tokens=8, admission="fifo")}
          if mode == "chunked" else
          {"spec": SpecConfig(draft="layer_skip", k=2, draft_layers=2)})
    eng = ContinuousScheduler(cfg, max_slots=3, max_len=48, cache="paged",
                              page_size=8, n_pages=24, tracer=tracer, **kw)
    eng.load(eng.model.init(jax.random.PRNGKey(0)))
    return eng


PHASE_PROMPTS = (13, 5, 20, 9)
PHASE_GENS = (3, 6, 2, 4)


def _submit_phase_work(eng):
    return [eng.submit((np.arange(n, dtype=np.int32) * 7 + n) % 50 + 1, g)
            for n, g in zip(PHASE_PROMPTS, PHASE_GENS)]


def test_row_counters_count_window_and_decode_rows():
    eng = _phase_engine()
    windows = []
    pack = eng._chunker.pack_window

    def spy(jobs):
        out = pack(jobs)
        windows.append((out[2].shape, sum(c for _, _, c in jobs)))
        return out

    eng._chunker.pack_window = spy
    reqs = _submit_phase_work(eng)
    m = eng.run()
    rows = m["rows"]
    # a window computes rows x S, pad rows included; its real rows are
    # the chunk lengths, which add up to every prompt once
    assert all(r == eng.max_slots for (r, _), _ in windows)
    assert rows["chunk"]["computed"] == sum(r * s for (r, s), _ in windows)
    assert rows["chunk"]["real"] == sum(c for _, c in windows) \
        == sum(PHASE_PROMPTS)
    # a decode step computes every slot; real rows committed a token (a
    # request's first token comes from its last chunk)
    assert rows["decode"]["computed"] == eng.max_slots * m["decode_steps"]
    assert rows["decode"]["real"] == sum(len(r.tokens) - 1 for r in reqs)
    assert rows["prefill"] == rows["verify"] == {"computed": 0, "real": 0}
    snap = eng.metrics.snapshot()
    assert snap["rows_computed.chunk"] == rows["chunk"]["computed"]
    assert snap["rows_real.decode"] == rows["decode"]["real"]


def test_row_counters_grouped_prefill_and_verify():
    eng = _phase_engine("spec")
    reqs = _submit_phase_work(eng)
    m = eng.run()
    rows, k = m["rows"], eng.spec.k
    assert rows["prefill"] == {"computed": sum(PHASE_PROMPTS),
                               "real": sum(PHASE_PROMPTS)}
    assert rows["verify"]["computed"] == (eng.max_slots * (k + 1)
                                          * m["spec"]["rounds"])
    # every token after the first comes out of a verify window
    assert rows["verify"]["real"] == sum(len(r.tokens) - 1 for r in reqs)
    assert rows["verify"]["real"] < rows["verify"]["computed"]


@pytest.mark.parametrize("mode", ["chunked", "spec"])
def test_tracer_changes_no_tokens_and_no_device_reads(mode, monkeypatch):
    """With a Tracer the engine makes the same tokens with the same
    device-to-host reads and syncs in every step as with tracer=None."""
    counts = {"reads": 0, "syncs": 0}
    to_host, sync = np.asarray, jax.block_until_ready

    def counting_read(x, *a, **kw):
        counts["reads"] += isinstance(x, jax.Array)
        return to_host(x, *a, **kw)

    def counting_sync(x):
        counts["syncs"] += 1
        return sync(x)

    monkeypatch.setattr(np, "asarray", counting_read)
    monkeypatch.setattr(jax, "block_until_ready", counting_sync)
    runs = []
    for tracer in (None, Tracer()):
        eng = _phase_engine(mode, tracer=tracer)
        reqs = _submit_phase_work(eng)
        per_step = []
        while eng.has_work():
            before = dict(counts)
            eng.step()
            per_step.append((counts["reads"] - before["reads"],
                             counts["syncs"] - before["syncs"]))
        runs.append(([list(r.tokens) for r in reqs], per_step))
    assert runs[0][1] and all(r > 0 for r, _ in runs[0][1])
    assert runs[0] == runs[1]
    assert len(tracer) > 0


def test_engine_phase_spans_reach_a_profiler_trace(tmp_path):
    """On the CPU too the engine's spans land in the jax.profiler trace,
    nested in one engine.step per step, and obs.xplane reads them."""
    from repro.obs import xplane
    eng = _phase_engine()
    _submit_phase_work(eng)
    eng.step()                         # outside the trace
    jax.profiler.start_trace(str(tmp_path))
    try:
        steps = 0
        while eng.has_work():
            eng.step()
            steps += 1
    finally:
        jax.profiler.stop_trace()
    prof = xplane.read(str(tmp_path))
    names = collections.Counter(n for _, _, n in prof.host)
    assert names["engine.step"] == steps
    assert names["engine.decode"] == names["engine.decode_readback"] > 0
    assert names["engine.chunk_window"] == names["engine.chunk_readback"] > 0
    for n in ("engine.admit", "engine.plan_chunks", "engine.grow_pages",
              "engine.upload", "engine.commit"):
        assert names[n] > 0, n
    step_spans = [(a, b) for a, b, n in prof.host if n == "engine.step"]
    for a, b, n in prof.host:
        if n != "engine.step":
            assert any(s <= a and b <= e for s, e in step_spans), n
    rep = xplane.summary(prof)
    assert rep["host_spans"]["engine.step"]["n"] == steps
    assert len(xplane.step_self_times(prof.host)) == steps
    assert 0 < rep["host_step_ms"] <= rep["host_spans"]["engine.step"][
        "total_s"] * 1e3


MS = 1_000_000


def _synthetic_profile():
    """Two steps on one chip: a chunk window whose device run has a
    bubble inside it, then a decode whose readback waits on the device,
    then a stretch with the host between steps."""
    from repro.obs import xplane
    chip = "/device:TPU:0"
    modules = {chip: [(0, 40 * MS, "jit_engine_chunk_window(812)"),
                      (50 * MS, 60 * MS, "jit_engine_decode(77)"),
                      (75 * MS, 80 * MS, "jit__insert_impl(5)")]}
    ops = {chip: [(0, 10 * MS, "%fusion.1 = f32[] fusion()"),
                  (20 * MS, 40 * MS, "%paged_decode_attention.2 = x"),
                  (50 * MS, 60 * MS, "%ternary_gemm_dense.3 = x"),
                  (75 * MS, 80 * MS, "%copy.4 = x")]}
    host = [(0, 70 * MS, "engine.step"),
            (0, 2 * MS, "engine.chunk_window"),
            (2 * MS, 41 * MS, "engine.chunk_readback"),
            (41 * MS, 50 * MS, "engine.commit"),
            (50 * MS, 51 * MS, "engine.decode"),
            (51 * MS, 61 * MS, "engine.decode_readback"),
            (61 * MS, 65 * MS, "engine.commit"),
            (70 * MS, 100 * MS, "engine.step"),
            (72 * MS, 74 * MS, "engine.upload")]
    return xplane.Profile(host, modules, ops)


def test_xplane_program_times_by_phase():
    from repro.obs import xplane
    prof = _synthetic_profile()
    times = xplane.program_times(prof.modules["/device:TPU:0"])
    assert times == {"engine_chunk_window": [0.040],
                     "engine_decode": [0.010],
                     "jit__insert_impl": [0.005]}
    rep = xplane.summary(prof)
    assert rep["programs"]["engine_chunk_window"]["median_ms"] == \
        pytest.approx(40.0)
    assert rep["window_s"] == pytest.approx(0.100)
    assert rep["busy_s"] == pytest.approx(0.045)


def test_xplane_step_self_time_leaves_out_readbacks():
    from repro.obs import xplane
    prof = _synthetic_profile()
    # step 1: 70 ms less 39 + 10 ms of readbacks; step 2: 30 ms
    assert xplane.step_self_times(prof.host) == pytest.approx([0.021,
                                                               0.030])
    assert xplane.summary(prof)["host_step_ms"] == pytest.approx(21.0)


def test_xplane_gaps_go_to_the_program_or_the_innermost_span():
    from repro.obs import xplane
    prof = _synthetic_profile()
    gaps = {label.split(" (")[0] if not label.startswith("in-program")
            else label.rsplit(" (", 1)[0]: s
            for label, s in xplane.idle_gaps(
                prof.ops["/device:TPU:0"], prof.modules["/device:TPU:0"],
                prof.host, 0, 100 * MS)}
    # 10-20 ms: a bubble inside the chunk window's program, not host time
    assert gaps["in-program (engine_chunk_window)"] == pytest.approx(0.010)
    # 40-50 ms: the host commits the window; 60-75 and 80-100 ms have
    # their middles in a step but in none of its phases
    assert gaps["engine.commit"] == pytest.approx(0.010)
    assert gaps["engine.step"] == pytest.approx(0.015 + 0.020)


# ---------------------------------------------------------------------------
# straggler watchdog (registry-backed, API preserved)
# ---------------------------------------------------------------------------

def test_watchdog_shares_registry_mechanism():
    from repro.distributed.fault_tolerance import StragglerWatchdog
    reg = MetricsRegistry()
    w = StragglerWatchdog(factor=2.0, alpha=0.5, registry=reg)
    w.observe(0, 1.0)
    assert w.observe(1, 5.0)
    # the same names the serving engine uses — one mechanism, two users
    assert reg.ewma("step_time_s", alpha=0.5) is w._ewma
    assert reg.counter("straggler_steps").value == w.straggler_steps == 1
    # legacy attribute writes still work
    w.ewma = 2.0
    w.straggler_steps = 0
    assert reg.snapshot() == {"step_time_s": 2.0, "straggler_steps": 0}
