"""Mesh-sharded serving benchmarks (DESIGN.md §13).

On the CPU the bench process keeps its single host device, so the sharded
workloads run in a subprocess with an 8-way forced host mesh (the same
pattern as tests/test_distributed.py) and report back as JSON. On an
accelerator they run in the bench's own process, which holds the devices:
tp=2 x dp=2 on a four-chip host. Two gated ``ratio=`` entries:

* ``sharding/tp_vs_single`` — a tp-way engine must produce bitwise the
  single-device engine's tokens; the gated ratio is 1.0-if-exact (host
  "devices" are threads fighting over the same cores, so the measured
  speedup is recorded as an ungated ``tp_speedup=`` field — on real
  accelerators it is the scaling figure of merit).
* ``sharding/router_affinity`` — fraction of repeated-prefix requests the
  dp=2 router lands on the replica already holding their prefix pages
  (>= 0.8 hard-asserted: placement that forgets affinity re-prefills
  shared prefixes from scratch and silently loses the prefix-cache win).
"""
from __future__ import annotations

import json
import os
import subprocess
import sys

from benchmarks.common import record

def _mesh_workload(quick: bool, tp: int, dp: int) -> dict:
    """The sharded workloads, in the calling process: a tp-way engine
    against the single-device one, then a dp x tp router."""
    import dataclasses

    import jax
    import numpy as np

    from repro.configs import get_config
    from repro.distributed import tp as tp_lib
    from repro.distributed.router import Router
    from repro.models import LM
    from repro.models.layers import pack_params
    from repro.serving.engine import ContinuousScheduler

    cfg = get_config("ternary-paper", reduced=True)
    cfg = dataclasses.replace(cfg, ternary_min_dim=64)
    model = LM(cfg)
    params = model.init(jax.random.PRNGKey(0))
    packed = pack_params(params, cfg)
    pcfg = dataclasses.replace(cfg, quantization="ternary_packed")
    rng = np.random.default_rng(0)

    requests = 4 if quick else 8
    gen = 6 if quick else 12
    max_len = 16 + gen + 8

    def build(mesh):
        eng = ContinuousScheduler(pcfg, 2, max_len, cache="paged",
                                  page_size=4, mesh=mesh)
        eng.load(packed)
        return eng

    def serve(eng, prompts, gens):
        reqs = [eng.submit(p, g) for p, g in zip(prompts, gens)]
        m = eng.run()
        return [[int(t) for t in r.tokens] for r in reqs], m

    # --- tp-way vs single device: token exactness + throughput ----------
    prompts = [rng.integers(1, cfg.vocab_size, size=12).astype(np.int32)
               for _ in range(requests)]
    gens = [gen] * requests
    single = build(None)
    serve(single, prompts, gens)                     # compile warmup
    ref, m_single = serve(single, prompts, gens)
    tp_eng = build(tp_lib.replica_meshes(1, tp)[0])
    serve(tp_eng, prompts, gens)                     # compile warmup
    got, m_tp = serve(tp_eng, prompts, gens)

    # --- dp x tp router: prefix affinity ---------------------------------
    def make_prompt(prefix, seed):
        tail = np.random.default_rng(seed).integers(
            1, cfg.vocab_size, size=4).astype(np.int32)
        return np.concatenate([prefix, tail])

    pa = rng.integers(1, cfg.vocab_size, size=8).astype(np.int32)
    pb = rng.integers(1, cfg.vocab_size, size=8).astype(np.int32)
    router = Router([build(m) for m in tp_lib.replica_meshes(dp, tp)])
    for p in (make_prompt(pa, 100), make_prompt(pb, 101)):   # warm both
        router.submit(p, gen)
    router.run()
    hot = 10 if quick else 20
    for i in range(hot):
        router.submit(make_prompt(pa if i % 2 == 0 else pb, i), gen)
    m_router = router.run()

    return {
        "tp_size": tp,
        "exact": got == ref,
        "single": {"wall_s": m_single["wall_s"],
                   "tok_per_s": m_single["tok_per_s"]},
        "tp": {"wall_s": m_tp["wall_s"], "tok_per_s": m_tp["tok_per_s"],
               "mesh": m_tp["mesh"]},
        "router": {"wall_s": m_router["wall_s"],
                   "tok_per_s": m_router["tok_per_s"],
                   "affinity": m_router["affinity"],
                   "spills": m_router["spills"],
                   "drained": [r["drained"]
                               for r in m_router["per_replica"]]},
    }


def _run_mesh_subprocess(quick: bool) -> dict:
    """The workload on an 8-device forced host mesh. XLA reads XLA_FLAGS
    when its CPU backend starts, so this needs a fresh process."""
    env = dict(os.environ, PYTHONPATH=os.pathsep.join(["src", "."]),
               XLA_FLAGS="--xla_force_host_platform_device_count=8")
    code = ("import json; from benchmarks.sharding_bench import "
            f"_mesh_workload; print(json.dumps(_mesh_workload({quick}, "
            "tp=4, dp=2)))")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=1800, env=env)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def sharded_serving(quick: bool = False):
    import jax
    if jax.default_backend() == "cpu":
        res = _run_mesh_subprocess(quick)
    else:
        # an accelerator belongs to one process: this one holds it, so the
        # mesh workload runs here, over the attached devices
        n = len(jax.devices())
        assert n >= 4, f"sharded serving needs >= 4 devices, found {n}"
        res = _mesh_workload(quick, tp=min(4, n // 2), dp=2)

    exact = res["exact"]
    speedup = res["tp"]["tok_per_s"] / res["single"]["tok_per_s"]
    record("sharding/tp_serve", res["tp"]["wall_s"],
           f"tok_per_s={res['tp']['tok_per_s']},"
           f"collective_plans={res['tp']['mesh']['collective_plans']}")
    record("sharding/single_for_tp", res["single"]["wall_s"],
           f"tok_per_s={res['single']['tok_per_s']}")
    record("sharding/tp_vs_single", 0.0,
           f"ratio={1.0 if exact else 0.0:.2f},token_exact={exact},"
           f"tp_speedup={speedup:.2f}")
    assert exact, (f"tp={res['tp_size']} tokens diverged from the "
                   f"single-device engine")

    aff = res["router"]["affinity"]
    rate = aff["rate"] or 0.0
    record("sharding/router_affinity", res["router"]["wall_s"],
           f"ratio={rate:.2f},hits={aff['hits']},"
           f"candidates={aff['candidates']},spills={res['router']['spills']},"
           f"tok_per_s={res['router']['tok_per_s']}")
    assert rate >= 0.8, (
        f"router prefix affinity collapsed: {aff['hits']}/"
        f"{aff['candidates']} repeated-prefix requests routed to the "
        f"replica holding their pages (rate {rate:.2f} < 0.8)")
    assert all(d > 0 for d in res["router"]["drained"]), (
        "a replica sat idle through the routed workload")


ALL = [sharded_serving]
