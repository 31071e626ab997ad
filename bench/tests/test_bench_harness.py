"""The harness end to end on the CPU at a tiny size: a cell added by files
alone, the output check passing on the program, and failing on a broken
program and on the float8 control."""
import hashlib
import json
import os
import shutil
import time

import numpy as np
import pytest

import _paths
import harness
import reference
import spec

TINY = {"hidden_size": 64, "intermediate_size": 128, "num_hidden_layers": 4,
        "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
        "vocab_size": 256, "rms_norm_eps": 1e-5, "rope_theta": 10000.0,
        "tie_word_embeddings": False,
        "serving": {"quantization": "ternary_packed", "ternary_min_dim": 16,
                    "dtype": "bfloat16", "param_dtype": "float32",
                    "cache_dtype": "bfloat16"}}
MIX = {"kind": "poisson", "rate": 30, "prompt_lens": [8, 16],
       "output_lens": [16, 24], "block": 16, "warmup_steps": 4}
BATCH = {"kind": "backlog", "backlog": 4, "ramp": 4, "prompt_lens": [8],
         "output_lens": [8, 16], "block": 8, "warmup_steps": 3}
LIMIT = 0.05
CELL = {"max_slots": 4, "max_len": 48, "page_size": 8, "n_pages": 25,
        "chunk_tokens": 8, "step_token_budget": 12, "admission": "fifo",
        "check_requests": 4, "min_checked_tokens": 8,
        "max_logit_gap": LIMIT}
PEAKS = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
CPU = {"platform": "cpu", "kind": "cpu", "count": 1}
METRIC = '''import reduce


def read(ctx):
    return reduce.mean_share(ctx["occupancy"])
'''


def _digest(root):
    out = {}
    for d, _, files in os.walk(os.path.join(root, "bench")):
        for f in files:
            p = os.path.join(d, f)
            with open(p, "rb") as fh:
                out[os.path.relpath(p, root)] = hashlib.sha256(
                    fh.read()).hexdigest()
    return out


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """A checkout's benchmark with one cell added by new files and new
    entries only."""
    r = str(tmp_path_factory.mktemp("checkout"))
    shutil.copytree(_paths.BENCH, os.path.join(r, "bench"),
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(os.path.join(_paths.ROOT, "BENCHMARK.json"), r)
    before = _digest(r)
    b = os.path.join(r, "bench")
    for rel, body in (("configs/tiny.json", TINY),
                      ("traffic/tinychat.json", MIX),
                      ("traffic/tinybatch.json", BATCH),
                      ("cells/tiny.chat.json", CELL),
                      ("cells/tiny.batch.json", CELL)):
        with open(os.path.join(b, rel), "w") as f:
            json.dump(body, f)
    with open(os.path.join(b, "metrics", "slot_share.tiny.py"), "w") as f:
        f.write(METRIC)
    with open(os.path.join(r, "BENCHMARK.json")) as f:
        bj = json.load(f)
    bj["configs"].append({"name": "tiny", "source": "test",
                          "file": "bench/configs/tiny.json", "reduced": [],
                          "why": "test"})
    for name, mix in (("tiny.chat", "tinychat"), ("tiny.batch", "tinybatch")):
        bj["workloads"].append({"name": name, "config": "tiny",
                                "traffic": mix, "chips": 1, "why": "test"})
    bj["end_to_end"].append({"name": "ttft_p50_tiny_ms", "unit": "ms",
                             "better": "lower", "bound": 0.1,
                             "source": "host_clock",
                             "workloads": ["tiny.chat"]})
    bj["per_layer"].append({"name": "slot_share.tiny", "unit": "%",
                            "better": "higher", "source": "program_counter",
                            "layer": "engine", "moves": "ttft_p50_tiny_ms",
                            "workloads": ["tiny.chat"]})
    with open(os.path.join(r, "BENCHMARK.json"), "w") as f:
        json.dump(bj, f)
    after = _digest(r)
    assert all(after[k] == v for k, v in before.items())
    return r


def _run(root, seed, fault=None):
    cell = spec.load_cell("tiny.chat", root=root)
    run = harness.Run(cell, seed, peaks=PEAKS)
    run.setup()
    if fault is not None:
        fault(run.engine)
    counter = harness.CompileCounter()
    w = run.window(1.0, counter)
    run.drain(w)
    return run, w


def test_new_cell_found_from_added_files(root):
    cell = spec.load_cell("tiny.chat", root=root)
    assert cell.config == TINY and cell.engine == CELL
    assert [m["name"] for m in cell.per_layer] == ["slot_share.tiny"]
    assert sorted(m["name"] for m in cell.end_to_end) == [
        "setup_s", "ttft_p50_tiny_ms"]
    assert cell.reader("slot_share.tiny")({"occupancy": [0.5]}) == 50.0


@pytest.fixture(scope="module")
def clean(root):
    run, w = _run(root, 2**32 + 11)
    seqs = [(r.req.prompt, list(r.req.tokens)) for r in run.sample(w, 4)]
    checks = harness.check_output(run, w)
    return run, w, seqs, checks


def test_sound_run_is_correct(clean):
    run, w, seqs, checks = clean
    assert harness.passed(checks), checks
    assert w.compiles == 0
    assert w.tokens > 0 and len(run.window_recs(w)) > 5
    vals = run.end_to_end(w, 1.0)
    assert vals["ttft_p95_ms"] > 0 and vals["itl_p95_ms"] > 0


def test_control_fails_the_limit(clean):
    """The float8 control, at each position of the served prompts and
    tokens, picks tokens the reference ranks below the limit."""
    _, _, seqs, checks = clean
    ctl, srv = reference.control_gaps(TINY, 2**32 + 11, seqs)
    assert max(srv) <= LIMIT
    assert max(ctl) > LIMIT, ctl


def _alter_tokens(engine):
    """Every decoded token is replaced by its neighbour where it is made."""
    inner = engine._decode_paged

    def broken(*a):
        layers, pos, nxt, ok = inner(*a)
        return layers, pos, (nxt + 1) % TINY["vocab_size"], ok

    engine._decode_paged = broken


def test_altered_tokens_fail_the_check(root):
    run, w = _run(root, 2**32 + 11, fault=_alter_tokens)
    checks = harness.check_output(run, w)
    assert not harness.passed(checks)
    assert checks["max_logit_gap"]["value"] > LIMIT


def test_traced_run_drains_before_reading_the_trace(root, monkeypatch):
    """Every request of the window is served before the trace is read."""
    import reduce
    order = []
    drain = harness.Run.drain

    def drain_and_note(self, w):
        drain(self, w)
        order.append(("drained", all(r.req.terminal for r in self.recs
                                     if r.in_window)))

    def read(tdir):
        order.append(("read", os.path.isdir(tdir)))
        return {"busy_s": 1.0, "window_s": 1.0, "kernels": {},
                "device_ops": [], "idle_gaps": []}

    monkeypatch.setattr(harness.Run, "drain", drain_and_note)
    monkeypatch.setattr(reduce, "reduce_dir", read)
    cell = spec.load_cell("tiny.chat", root=root)
    out = harness.execute(cell, 2**32 + 13, 1.0, True, time.monotonic(),
                          CPU, PEAKS)
    assert order == [("drained", True), ("read", True)]
    assert out["correct"] and out["failed"] == 0
    assert out["device"]["busy_s"] == 1.0
    assert "slot_share.tiny" in out["metrics"]


def test_drain_grace_runs_from_its_start(root):
    """A pause between the close and the drain (a traced run stopping its
    profiler) leaves the window's requests their whole grace."""
    offset = [0.0]
    cell = spec.load_cell("tiny.chat", root=root)
    run = harness.Run(cell, 2**32 + 17, peaks=PEAKS,
                      clock=lambda: time.monotonic() + offset[0])
    run.setup()
    w = run.window(1.0, harness.CompileCounter())
    run._submit(run.source.take(), w.t1, True)     # one still in flight
    offset[0] += harness.GRACE_S + 1.0
    run.drain(w)
    recs = run.window_recs(w)
    assert recs and all(r.req.state == "done" for r in recs)


def test_backlog_warms_up_by_decode_steps(root):
    """A closed loop opens its window after the same number of decode
    steps on every run, however long they took."""
    cell = spec.load_cell("tiny.batch", root=root)
    run = harness.Run(cell, 2**32 + 19, peaks=PEAKS)
    run.setup()
    assert run.engine.decode_steps == BATCH["warmup_steps"]


def test_open_loop_warms_up_by_decode_steps(root):
    """An open loop counts its warm-up in decode steps too, serving the
    arrivals that come meanwhile."""
    cell = spec.load_cell("tiny.chat", root=root)
    run = harness.Run(cell, 2**32 + 23, peaks=PEAKS)
    run.setup()
    assert run.engine.decode_steps == MIX["warmup_steps"]
    assert run.recs and not any(r.in_window for r in run.recs)
