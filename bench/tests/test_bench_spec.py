"""BENCHMARK.json and the files it names hang together."""
import json
import os
import re
import types

import pytest

import _paths
import arrivals
import spec
import work

with open(os.path.join(_paths.ROOT, "BENCHMARK.json")) as _f:
    B = json.load(_f)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
CELLS = [w["name"] for w in B["workloads"]]


def test_top_level_keys():
    assert set(B) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert 1 <= B["run_seconds"] <= 51
    for p in B["paths"]:
        assert os.path.isdir(os.path.join(_paths.ROOT, p))
    assert B["command"][1].startswith(B["paths"][0] + "/")


def test_names_units_and_keys():
    seen = set()
    for group, keys in (("configs", {"name", "source", "file", "reduced",
                                     "why"}),
                        ("workloads", {"name", "config", "traffic", "chips",
                                       "why"})):
        for e in B[group]:
            assert set(e) == keys and NAME.match(e["name"])
            assert e["name"] not in seen
            seen.add(e["name"])
    for m in B["end_to_end"] + B["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert m["name"] not in seen
        seen.add(m["name"])
    assert "setup_s" in {m["name"] for m in B["end_to_end"]}


@pytest.mark.parametrize("cell", CELLS)
def test_cell_files_found_by_name(cell):
    c = spec.load_cell(cell, root=_paths.ROOT)
    mix = arrivals.Mix.from_dict(c.traffic)
    assert mix.longest <= c.engine["max_len"]
    e = c.engine
    pages = -(-e["max_len"] // e["page_size"])
    assert e["n_pages"] >= pages + 1
    assert {m["name"] for m in c.end_to_end} >= {"setup_s"}
    assert len(c.end_to_end) >= 2 and c.per_layer
    for m in c.per_layer:
        assert m["moves"] in {x["name"] for x in c.end_to_end}
        assert callable(c.reader(m["name"]))


def test_per_layer_cells_report_what_they_move():
    e2e = {m["name"]: m for m in B["end_to_end"]}
    for m in B["per_layer"]:
        moved = e2e[m["moves"]]
        for w in m.get("workloads", CELLS):
            assert w in CELLS
            assert spec.applies(moved, w)


def test_peaks_table_names_its_source():
    with open(os.path.join(_paths.BENCH, "peaks.json")) as f:
        p = json.load(f)
    assert "TPU v5e" in p["source"]
    assert p["devices"]["TPU v5 lite"]["bf16_flops"] == 197e12
    assert p["devices"]["TPU v5 lite"]["hbm_bytes_per_s"] == 819e9


@pytest.mark.parametrize("metric", ["mfu.chat", "gemm_roofline.chat",
                                    "attn_roofline.chat", "idle_share.chat"])
def test_split_metric_falls_back_to_its_family_reader(metric):
    """``mfu.chat`` has no file of its own and reads with ``mfu.py``;
    without a trace it finds nothing to read."""
    read = spec.load_reader(_paths.BENCH, metric)
    assert not os.path.exists(
        os.path.join(_paths.BENCH, "metrics", metric + ".py"))
    idle = types.SimpleNamespace(gemm=work.Work(), attn=work.Work(),
                                 useful_ops=0.0)
    assert read({"trace": None, "work": idle, "peaks": {}}) is None
