"""End-to-end training driver.

Runs on anything from 1 CPU (reduced configs, tests, examples) to the
production mesh (same code path — the mesh shape is the only difference).
Integrates: synthetic data pipeline, AdamW, checkpoint/restart via the
fault-tolerance supervisor, straggler watchdog, optional ternary QAT
(the paper's technique) and optional ternary gradient compression on the
data-parallel axes (shard_map DP trainer).

Usage:
  PYTHONPATH=src python -m repro.launch.train --arch ternary-paper \
      --reduced --steps 200 --batch 8 --seq 128 --ckpt-dir /tmp/ckpt
"""
from __future__ import annotations

import argparse
import json
import logging
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.configs.base import ModelConfig
from repro.data import SyntheticLM
from repro.distributed import sharding as shlib
from repro.distributed.fault_tolerance import StragglerWatchdog, TrainSupervisor
from repro.launch import steps as steps_lib
from repro.launch.mesh import make_local_mesh
from repro.obs import clock as obs_clock
from repro.models import LM, set_mesh
from repro.optim import warmup_cosine

log = logging.getLogger("repro.train")


def make_compressed_dp_step(model: LM, cfg: ModelConfig, mesh, lr_fn):
    """Pure-DP trainer with TernGrad-style ternary gradient sync (§DESIGN 8):
    the whole step runs under shard_map over the data axes; each shard
    computes local grads on its batch slice, gradients cross the wire as
    ternary codes + scales with error feedback, the optimizer update is
    replicated. The paper's {-1,0,+1} value system applied to the comm
    layer."""
    from jax.sharding import PartitionSpec as P

    from repro.distributed import compression
    from repro.optim import adamw, clip_by_global_norm

    opt_init, opt_update = adamw(state_dtype=cfg.opt_state_dtype)
    axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)

    def local_step(params, opt_state, err, batch):
        def loss_fn(p):
            return model.loss(p, batch)

        (loss, metrics), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params)
        synced, err = compression.compressed_psum(grads, err, axes[-1])
        synced, gnorm = clip_by_global_norm(synced, 1.0)
        lr = lr_fn(opt_state["step"] + 1)
        params, opt_state = opt_update(synced, opt_state, params, lr)
        metrics = dict(metrics, grad_norm=gnorm, lr=lr,
                       loss=jax.lax.pmean(metrics["loss"], axes[-1]))
        return params, opt_state, err, metrics

    rep = P()
    bspec = jax.tree.map(lambda _: P(axes[-1]), {"tokens": 0, "targets": 0})

    def step(params, opt_state, err, batch):
        bs = {k: P(axes[-1]) for k in batch}
        f = jax.shard_map(local_step, mesh=mesh,
                          in_specs=(rep, rep, rep, bs),
                          out_specs=(rep, rep, rep, rep),
                          check_vma=False)
        return f(params, opt_state, err, batch)

    return step, opt_init


def build(cfg: ModelConfig, batch: int, seq: int, mesh=None, lr: float = 3e-4,
          total_steps: int = 1000):
    model = LM(cfg)
    data = SyntheticLM(cfg, batch, seq)
    lr_fn = warmup_cosine(lr, min(100, total_steps // 10 + 1), total_steps)
    train_step, opt_init = steps_lib.make_train_step(model, cfg, lr_fn)

    if mesh is not None:
        set_mesh(mesh)
        p_shapes, p_shardings = steps_lib.model_shardings(model, cfg, mesh)
        opt_shapes = jax.eval_shape(opt_init, p_shapes)
        opt_sh = shlib.opt_state_shardings(p_shardings, opt_shapes, mesh)
        batch_sh = shlib.batch_sharding(
            jax.eval_shape(lambda: data.sharded_batch(0)), mesh)
        jitted = jax.jit(train_step,
                         in_shardings=(p_shardings, opt_sh, batch_sh),
                         donate_argnums=(0, 1))
    else:
        p_shardings = None
        jitted = jax.jit(train_step, donate_argnums=(0, 1))

    def init_state(key):
        params = model.init(key)
        if mesh is not None:
            params = jax.tree.map(
                lambda x, s: jax.device_put(x, s), params, p_shardings)
        return {"params": params, "opt": opt_init(params)}

    return model, data, jitted, init_state, p_shardings


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="ternary-paper")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=100)
    ap.add_argument("--data-parallel", type=int, default=1)
    ap.add_argument("--model-parallel", type=int, default=1)
    ap.add_argument("--compress-grads", action="store_true",
                    help="sync gradients as ternary codes + scales with "
                         "error feedback (TernGrad-style shard_map DP "
                         "trainer; needs --data-parallel > 1 and "
                         "--model-parallel 1)")
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--set", action="append", default=[])
    args = ap.parse_args(argv)
    logging.basicConfig(level=logging.INFO)

    overrides: Dict[str, Any] = {}
    for kv in args.set:
        k, v = kv.split("=", 1)
        f = ModelConfig.__dataclass_fields__[k]
        typ = f.type if isinstance(f.type, type) else eval(f.type)  # noqa: S307
        overrides[k] = (v.lower() in ("1", "true")) if typ is bool else typ(v)
    cfg = get_config(args.arch, reduced=args.reduced, **overrides)

    mesh = None
    if args.data_parallel * args.model_parallel > 1:
        mesh = make_local_mesh(args.data_parallel, args.model_parallel)

    if args.compress_grads:
        # the pure-DP shard_map trainer: replicated params/opt/error state,
        # batch split on the data axis, ternary codes on the wire
        if mesh is None or "data" not in mesh.axis_names \
                or args.model_parallel > 1:
            raise SystemExit("--compress-grads needs a pure data-parallel "
                             "mesh: --data-parallel > 1 --model-parallel 1")
        from repro.distributed import compression
        model = LM(cfg)
        data = SyntheticLM(cfg, args.batch, args.seq)
        lr_fn = warmup_cosine(args.lr, min(100, args.steps // 10 + 1),
                              args.steps)
        cstep, c_opt_init = make_compressed_dp_step(model, cfg, mesh, lr_fn)
        jitted = jax.jit(cstep, donate_argnums=(0, 1, 2))

        def init_state(key):
            params = model.init(key)
            return {"params": params, "opt": c_opt_init(params),
                    "err": compression.init_error_state(params)}
    else:
        model, data, jitted, init_state, _ = build(
            cfg, args.batch, args.seq, mesh, args.lr, args.steps)

    def make_state(resume_step: Optional[int]):
        if resume_step is None:
            return 0, init_state(jax.random.PRNGKey(args.seed))
        from repro import checkpoint as ckpt
        target = jax.eval_shape(init_state, jax.ShapeDtypeStruct((2,), jnp.uint32))
        step, state = ckpt.restore(args.ckpt_dir, resume_step, target)
        log.info("restored step %d from %s", step, args.ckpt_dir)
        return step, state

    t_hist = []

    def step_fn(step: int, state):
        t0 = obs_clock.now()
        if args.compress_grads:
            # shard_map splits the global batch on the data axis itself
            batch = {k: jnp.asarray(v)
                     for k, v in data.global_batch(step).items()}
            params, opt, err, metrics = jitted(
                state["params"], state["opt"], state["err"], batch)
            state = {"params": params, "opt": opt, "err": err}
        else:
            batch = (data.sharded_batch(step, mesh)
                     if mesh is not None else data.sharded_batch(step))
            params, opt, metrics = jitted(state["params"], state["opt"],
                                          batch)
            state = {"params": params, "opt": opt}
        metrics = {k: float(v) for k, v in metrics.items()}
        dt = obs_clock.now() - t0
        t_hist.append(dt)
        if step % args.log_every == 0:
            log.info("step %d loss %.4f (%.3fs)", step, metrics["loss"], dt)
        return state, metrics

    sup = TrainSupervisor(args.ckpt_dir, make_state, step_fn,
                          ckpt_every=args.ckpt_every,
                          watchdog=StragglerWatchdog())
    state, history = sup.run(args.steps)
    losses = [m["loss"] for _, m in history]
    print(json.dumps({
        "steps": len(history),
        "first_loss": losses[0] if losses else None,
        "last_loss": losses[-1] if losses else None,
        "mean_step_s": float(np.mean(t_hist[1:])) if len(t_hist) > 1 else None,
        "stragglers": sup.watchdog.straggler_steps,
    }))


if __name__ == "__main__":
    main()
