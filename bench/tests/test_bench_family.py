"""A model family is a file, ``bench/families/<family>.py``, named by the
configuration's ``"family"`` key: a new family joins the benchmark by
files and entries alone, and the harness, the output check and the
control reach it through ``spec.load_family``."""
import copy
import hashlib
import json
import os
import shutil

import numpy as np
import pytest

import _paths
import control
import gen
import harness
import reference
import spec
import work

TOY = {"family": "toy", "hidden_size": 64, "intermediate_size": 128,
       "num_hidden_layers": 2, "num_attention_heads": 4,
       "num_key_value_heads": 2, "head_dim": 16, "vocab_size": 256,
       "rms_norm_eps": 1e-5, "rope_theta": 10000.0,
       "tie_word_embeddings": False,
       "serving": {"quantization": "ternary_packed", "ternary_min_dim": 16,
                   "dtype": "bfloat16", "param_dtype": "float32",
                   "cache_dtype": "bfloat16"}}
DENSE = {k: v for k, v in TOY.items() if k != "family"}
MIX = {"kind": "poisson", "rate": 30, "prompt_lens": [8, 16],
       "output_lens": [8, 12], "block": 16, "warmup_steps": 4}
CELL = {"max_slots": 4, "max_len": 32, "page_size": 8, "n_pages": 17,
        "chunk_tokens": 8, "step_token_budget": 12, "admission": "fifo",
        "check_requests": 3, "min_checked_tokens": 8,
        "max_logit_gap": 0.05}
PEAKS = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
SEED = 2**32 + 29
# the toy family: the dense decoder's arithmetic under another name, with
# every call the harness makes into it recorded
FAMILY = '''import os

import spec
import work as work_lib

DENSE = spec.load_family(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "dense")
CALLS = []


def model_config(c):
    CALLS.append("model_config")
    return DENSE.model_config(c)


def leaf(root, name, shape, layer, k_in):
    CALLS.append("leaf")
    return DENSE.leaf(root, name, shape, layer, k_in)


class Work(work_lib.StepWork):
    def counters(self, deltas):
        CALLS.append(("counters", dict(deltas)))


def work(c, peaks):
    CALLS.append("work")
    return Work(work_lib.Shapes.from_config(c), peaks)


def served_gaps(c, seed, seqs):
    CALLS.append("served_gaps")
    return DENSE.served_gaps(c, seed, seqs)


def control_gaps(c, seed, seqs):
    CALLS.append("control_gaps")
    return DENSE.control_gaps(c, seed, seqs)
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
    """A checkout's benchmark with the toy family's cell, and a cell whose
    configuration names a family that has no file, added by new files and
    new entries only."""
    r = str(tmp_path_factory.mktemp("checkout"))
    shutil.copytree(_paths.BENCH, os.path.join(r, "bench"),
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(os.path.join(_paths.ROOT, "BENCHMARK.json"), r)
    before = _digest(r)
    b = os.path.join(r, "bench")
    for rel, body in (("configs/toy.json", TOY),
                      ("configs/ghost.json", dict(TOY, family="ghost")),
                      ("traffic/toychat.json", MIX),
                      ("cells/toy.chat.json", CELL),
                      ("cells/ghost.chat.json", CELL)):
        with open(os.path.join(b, rel), "w") as f:
            json.dump(body, f)
    with open(os.path.join(b, "families", "toy.py"), "w") as f:
        f.write(FAMILY)
    with open(os.path.join(r, "BENCHMARK.json")) as f:
        bj = json.load(f)
    for name in ("toy", "ghost"):
        bj["configs"].append({"name": name, "source": "test",
                              "file": f"bench/configs/{name}.json",
                              "reduced": [], "why": "test"})
        bj["workloads"].append({"name": f"{name}.chat", "config": name,
                                "traffic": "toychat", "chips": 1,
                                "why": "test"})
    with open(os.path.join(r, "BENCHMARK.json"), "w") as f:
        json.dump(bj, f)
    after = _digest(r)
    assert all(after[k] == v for k, v in before.items())
    return r


def test_toy_family_reached_through_its_file(root):
    """Set-up, the window's work and counters, the output check and the
    control all go through the toy module."""
    cell = spec.load_cell("toy.chat", root=root)
    fam = cell.family
    assert fam.__file__ == os.path.join(root, "bench", "families", "toy.py")
    run = harness.Run(cell, SEED, peaks=PEAKS)
    run.setup()
    counter = harness.CompileCounter()
    w = run.window(0.5, counter)
    run.drain(w)
    assert isinstance(w.work, fam.Work) and w.work.gemm.ops > 0
    deltas = [c[1] for c in fam.CALLS if isinstance(c, tuple)]
    assert len(deltas) == w.steps > 0
    assert all(isinstance(v, (int, np.integer)) for d in deltas
               for v in d.values())
    assert sum(d["rows_computed.decode"] for d in deltas) >= sum(
        d["rows_real.decode"] for d in deltas) > 0
    checks = harness.check_output(run, w)
    assert harness.passed(checks), checks
    got = control.readings(cell, SEED, PEAKS, 0.5, counter)
    assert got["checked_tokens"] >= CELL["min_checked_tokens"]
    assert got["program_gap"] <= CELL["max_logit_gap"]
    names = [c for c in fam.CALLS if isinstance(c, str)]
    for step in ("model_config", "leaf", "work", "served_gaps",
                 "control_gaps"):
        assert step in names, step


@pytest.mark.parametrize("cell", [w["name"] for w in spec._read_json(
    os.path.join(_paths.ROOT, "BENCHMARK.json"))["workloads"]])
def test_config_without_family_key_is_dense(cell):
    c = spec.load_cell(cell, root=_paths.ROOT)
    assert "family" not in c.config
    assert c.family.__file__ == os.path.join(_paths.BENCH, "families",
                                             "dense.py")


@pytest.mark.parametrize("config", sorted(
    f[:-len(".json")]
    for f in os.listdir(os.path.join(_paths.BENCH, "configs"))
    if f.endswith(".json")))
def test_every_config_file_has_its_family_module(config):
    """Each configuration file under ``bench/configs/``, in a cell or not
    yet, resolves to a family module that exports what the harness calls."""
    c = spec._read_json(os.path.join(_paths.BENCH, "configs",
                                     config + ".json"))
    fam = spec.load_family(_paths.BENCH, c.get("family", "dense"))
    for name in ("model_config", "leaf", "work", "served_gaps",
                 "control_gaps"):
        assert callable(getattr(fam, name)), name
    assert fam.model_config(c).num_layers == c["num_hidden_layers"]


def test_missing_family_file_names_the_path(root):
    path = os.path.join(root, "bench", "families", "ghost.py")
    with pytest.raises(FileNotFoundError, match=path):
        spec.load_cell("ghost.chat", root=root)


def test_dense_family_is_the_reference_and_the_work_counts():
    """The dense family is ``gen.leaf``, ``reference`` and ``work`` as they
    are: the same functions, and the accumulator over the configuration's
    shapes, on which the counter deltas change nothing."""
    dense = spec.load_family(_paths.BENCH, "dense")
    assert dense.leaf is gen.leaf
    assert dense.served_gaps is reference.served_gaps
    assert dense.control_gaps is reference.control_gaps
    acc = dense.work(DENSE, PEAKS)
    assert type(acc) is work.StepWork
    assert acc.s == work.Shapes.from_config(DENSE)
    acc.forward(4, 60)
    before = copy.deepcopy((acc.gemm, acc.attn, acc.useful_ops))
    acc.counters({"rows_computed.decode": 4, "rows_real.decode": 3})
    assert (acc.gemm, acc.attn, acc.useful_ops) == before
