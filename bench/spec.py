"""Finds a cell's files by name.

``BENCHMARK.json`` at the checkout root lists the configurations, cells and
metrics. Everything that belongs to one of them is a file of its own,
found by its name, so a new cell, mix or metric is added by adding files:

* ``bench/configs/<config>.json`` (the path ``BENCHMARK.json`` gives):
  the model's sizes as run;
* ``bench/traffic/<traffic>.json``: one traffic mix, read by
  ``arrivals.Mix``;
* ``bench/cells/<cell>.json``: the cell's engine settings and the limit of
  its output check;
* ``bench/metrics/<metric>.py``: one per-layer metric's reader, a function
  ``read(ctx)`` that returns a number or ``None``. A metric split by the
  end-to-end metric it moves (``mfu.batch``, ``mfu.chat``) falls back to
  its family's reader (``mfu.py``) when it has no file of its own;
* ``bench/families/<family>.py``: what is particular to one model family,
  named by the configuration's top-level ``"family"`` key (``"dense"``
  without it). The module exports ``model_config(c)``, the program's
  ``ModelConfig``; ``leaf(root, name, shape, layer, k_in)``, one seeded
  weight; ``work(c, peaks)``, the window's work accumulator (``.gemm``,
  ``.attn``, ``.useful_ops``, ``forward(m, attended, keys)``,
  ``useful(tokens, attended)``, ``counters(deltas)``); and the plain
  reference, ``served_gaps(c, seed, seqs)`` and
  ``control_gaps(c, seed, seqs)``.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
from typing import Any, Callable, Dict, List, Optional

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    config: Dict[str, Any]
    traffic_name: str
    traffic: Dict[str, Any]
    engine: Dict[str, Any]
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]
    bench_dir: str
    family: Any

    def reader(self, metric: str) -> Callable[[Dict[str, Any]], Optional[float]]:
        return load_reader(self.bench_dir, metric)


def _read_json(path: str) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def applies(metric: Dict[str, Any], cell: str,
            e2e_names: Optional[List[str]] = None) -> bool:
    """Whether ``metric`` is reported in ``cell``: the cells its
    ``workloads`` key lists, or, without the key, every cell (a per-layer
    metric: every cell that reports the end-to-end metric it moves)."""
    if "workloads" in metric:
        return cell in metric["workloads"]
    if e2e_names is not None and "moves" in metric:
        return metric["moves"] in e2e_names
    return True


def load_cell(name: str, root: str = ROOT,
              bench_dir: Optional[str] = None) -> Cell:
    bench_dir = bench_dir or os.path.join(root, "bench")
    spec = _read_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; "
                       f"have {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in spec["configs"]}
    cfg_entry = configs[w["config"]]
    e2e = [m for m in spec["end_to_end"] if applies(m, name)]
    names = [m["name"] for m in e2e]
    per_layer = [m for m in spec["per_layer"] if applies(m, name, names)]
    config = _read_json(os.path.join(root, cfg_entry["file"]))
    return Cell(
        name=name, chips=int(w["chips"]), config_name=w["config"],
        config=config,
        traffic_name=w["traffic"],
        traffic=_read_json(os.path.join(bench_dir, "traffic",
                                        w["traffic"] + ".json")),
        engine=_read_json(os.path.join(bench_dir, "cells", name + ".json")),
        end_to_end=e2e, per_layer=per_layer, bench_dir=bench_dir,
        family=load_family(bench_dir, config.get("family", "dense")))


def _load_module(path: str, prefix: str, name: str):
    mod_name = prefix + name.replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(mod_name, path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_family(bench_dir: str, family: str):
    """The module ``bench/families/<family>.py``."""
    path = os.path.join(bench_dir, "families", family + ".py")
    if not os.path.exists(path):
        raise FileNotFoundError(f"no module for model family {family!r}: "
                                f"{path} does not exist")
    return _load_module(path, "bench_family_", family)


def load_reader(bench_dir: str, metric: str):
    path = os.path.join(bench_dir, "metrics", metric + ".py")
    family = os.path.join(bench_dir, "metrics",
                          metric.rsplit(".", 1)[0] + ".py")
    if not os.path.exists(path) and os.path.exists(family):
        path = family
    return _load_module(path, "bench_metric_", metric).read
