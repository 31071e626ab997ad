"""Tensor-parallel serving placement (DESIGN.md §13).

The model's logical PartitionSpecs already encode the Megatron-style TP
layout: QKV / up / gate projections are ``P("fsdp", "model")`` (N-dim
column split — each device computes its own output columns, no collective)
and down / o projections are ``P("model", "fsdp")`` (K-dim row split —
each device holds a K-slice and the partial products need a ``psum``).
``shard_params`` makes those specs real at serve time: it validates every
packed ``TernaryWeight`` spec twin against the mesh (shard boundaries must
land on 2-bit pack-word / tile multiples — ``weights.validate_spec_twin``),
resolves logical names through ``distributed.sharding.resolve_specs``,
``device_put``s the tree and records each placed container's split in its
static ``tp_dim``. Execution follows the data under GSPMD, except that a
Pallas kernel has no partitioning rule: model code traced under
``kernels.ops.tensor_parallel(mesh)`` runs each Pallas lowering of a
TP-placed weight per shard under ``jax.shard_map`` (column split local,
row split local then ``psum``), and paged attention over head-sharded
pages likewise. The XLA ``"ref"`` lowering stays on GSPMD.

Serving topology is dp x tp: ``replica_meshes`` carves ``dp`` disjoint
tp-sized single-axis ``("model",)`` meshes out of the device list, one per
engine replica (``serving.ContinuousScheduler(mesh=...)``); the
data-parallel layer on top is ``distributed.router.Router``. Develop on a
forced host mesh: ``XLA_FLAGS=--xla_force_host_platform_device_count=8``.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import weights
from repro.distributed import sharding

__all__ = ["parse_mesh", "replica_meshes", "validate_param_specs",
           "shard_params", "cache_sharding", "replicated_sharding",
           "device_put_cache", "mesh_axis_sizes", "gemm_shard_fn"]


def parse_mesh(arg: str) -> Tuple[int, int]:
    """``"dp,tp"`` -> (dp, tp). A bare ``"tp"`` means dp=1."""
    parts = [p.strip() for p in str(arg).split(",") if p.strip()]
    if len(parts) == 1:
        parts = ["1"] + parts
    if len(parts) != 2:
        raise ValueError(f"--mesh expects 'dp,tp', got {arg!r}")
    dp, tp = (int(p) for p in parts)
    if dp < 1 or tp < 1:
        raise ValueError(f"--mesh sizes must be >= 1, got dp={dp} tp={tp}")
    return dp, tp


def replica_meshes(dp: int, tp: int, devices=None) -> List[Mesh]:
    """``dp`` disjoint single-axis ``("model",)`` meshes of ``tp`` devices
    each — one per data-parallel engine replica. Replica r owns devices
    ``[r*tp, (r+1)*tp)`` of ``devices`` (default ``jax.devices()``)."""
    devices = list(jax.devices() if devices is None else devices)
    need = dp * tp
    if len(devices) < need:
        raise ValueError(
            f"mesh dp={dp} x tp={tp} needs {need} devices, have "
            f"{len(devices)} — on CPU force a host mesh with "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={need}")
    return [Mesh(np.asarray(devices[r * tp:(r + 1) * tp]), ("model",))
            for r in range(dp)]


def validate_param_specs(params, specs, mesh, *, fsdp: bool = False) -> int:
    """Walk the param/spec-twin trees together, validating every packed
    ``TernaryWeight`` container's twin against the mesh (pack-word / tile
    shard boundaries — ``weights.validate_spec_twin``). Returns the number
    of containers checked; raises ``ValueError`` on the first bad twin."""
    checked = 0

    def check(spec, p):
        nonlocal checked
        if isinstance(p, weights.TernaryWeight):
            weights.validate_spec_twin(p, spec, mesh, fsdp=fsdp)
            checked += 1
        return spec

    jax.tree.map(
        check, specs, params,
        is_leaf=lambda x: isinstance(x, (weights.TernaryWeight, P)))
    return checked


def shard_params(params, specs, mesh: Mesh, *, fsdp: bool = False,
                 validate: bool = True):
    """Place a param tree on ``mesh`` according to its logical spec tree
    (``LM.init_with_specs_abstract`` structure). Packed containers are
    validated first unless ``validate=False``."""
    if validate:
        validate_param_specs(params, specs, mesh, fsdp=fsdp)
    shardings = sharding.resolve_specs(specs, params, mesh, fsdp)
    placed = jax.device_put(params, shardings)
    split = gemm_shard_fn(mesh)

    def mark(w):
        part, _ = split((), w)
        return w if part is None else w.replace(tp_dim=part)

    return jax.tree.map(
        lambda v: mark(v) if isinstance(v, weights.TernaryWeight) else v,
        placed, is_leaf=lambda v: isinstance(v, weights.TernaryWeight))


def cache_sharding(layers, cfg, mesh: Mesh):
    """NamedSharding tree for a serving cache layer tree (dense slot rows
    or paged page arrays): the KV-head axis is sharded over ``"model"`` —
    matching the column-split K/V projections, so TP attention reads and
    writes only its local heads — wherever the head count divides the axis;
    everything else (SSM rows, int8 page scales with indivisible heads,
    the flat/opt layouts) replicates. Replication is always *correct*
    under GSPMD — this is a memory/locality optimization, never a
    numerics switch."""
    kv, hd = cfg.num_kv_heads, cfg.head_dim
    tp = dict(mesh.shape).get("model", 1)
    shardable = tp > 1 and kv % tp == 0

    def spec(x):
        shp = tuple(getattr(x, "shape", ()))
        if shardable and len(shp) >= 2 and shp[-1] == hd and shp[-2] == kv:
            return NamedSharding(
                mesh, P(*([None] * (len(shp) - 2)), "model", None))
        if shardable and len(shp) >= 1 and shp[-1] == kv:
            # int8 page scales: (..., page_size, KV) rides with its page
            return NamedSharding(mesh, P(*([None] * (len(shp) - 1)),
                                         "model"))
        return NamedSharding(mesh, P())

    return jax.tree.map(spec, layers)


def replicated_sharding(tree, mesh: Mesh):
    """Fully-replicated NamedSharding tree (small device mirrors: position
    and token vectors, block tables, masks)."""
    return jax.tree.map(lambda _: NamedSharding(mesh, P()), tree)


def device_put_cache(layers, cfg, mesh: Optional[Mesh]):
    """Shard-place a cache layer tree (no-op without a mesh)."""
    if mesh is None:
        return layers
    return jax.device_put(layers, cache_sharding(layers, cfg, mesh))


def mesh_axis_sizes(mesh) -> Dict[str, int]:
    return dict(getattr(mesh, "shape", mesh))


def gemm_shard_fn(mesh: Mesh):
    """``shard(path, w) -> (partition, tp)`` for ``ops.precompute_plans``:
    reads the *placed* packed array's sharding spec (set by
    ``shard_params``), so the collective recorded in each ``GemmPlan`` is
    derived from where the bits actually live, not re-declared. Packed
    words are (K-packed, N)-shaped: ``"model"`` on the trailing axis is
    the Megatron column split (no collective), on the leading axis the row
    split whose partial products need the ``psum``."""
    tp = mesh_axis_sizes(mesh).get("model", 1)

    def has_model(entry) -> bool:
        return entry == "model" or (isinstance(entry, tuple)
                                    and "model" in entry)

    def shard(path, w):
        arr = getattr(w, "packed", None)
        if arr is None:
            arr = getattr(w, "plus", None)
        spec = getattr(getattr(arr, "sharding", None), "spec", None)
        ndim = getattr(arr, "ndim", 0)
        if spec is None or tp <= 1 or ndim < 2:
            return None, 1
        # placed specs drop trailing Nones: pad back to ndim so the last
        # two entries really are the (K-packed, N) axes
        entries = tuple(spec) + (None,) * (ndim - len(spec))
        if has_model(entries[-1]):
            return "n", tp
        if has_model(entries[-2]):
            return "k", tp
        return None, 1

    return shard
