"""jit'd public wrappers + registry-dispatched planning for the ternary
GEMM kernels.

``ternary_gemm(x, w)`` is the user-facing op; ``w`` is a
``repro.core.weights.TernaryWeight`` container (``Dense2Bit`` / ``Tiled`` /
``Bitplane`` / ``Base3``). Dispatch is two-stage:

1. **plan** — ``ternary_gemm_plan`` consults the kernel registry: each
   lowering registers ``(format, impl)`` with a priority and a capability
   predicate (shape / serving phase / pack-time occupancy), and the planner
   picks the best admissible impl for ``impl="auto"`` (e.g. the skipping
   kernel only below ``SKIP_OCCUPANCY_CUTOFF`` tile occupancy). Block
   shapes left ``None`` are resolved by the autotuner
   (``kernels.autotune``), keyed on (M, K, N, occupancy, impl, phase). The
   resulting ``GemmPlan`` is an inspectable value object (tests and
   benchmarks assert on it directly).
2. **lower** — the registered lowering for ``(plan.format, plan.impl)``
   runs the Pallas kernel (interpret mode off-TPU) or the XLA reference.

Registered impls:

* ``dense2bit``: ``dense`` (Pallas dense-decode), ``ref``;
* ``tiled``:     ``skip_db`` (double-buffered-DMA tile skipping,
                 DESIGN.md §12), ``skip`` (scalar-prefetch tile skipping,
                 DESIGN.md §3), ``dense`` fallback, ``ref``;
* ``bitplane``:  ``bitplane``, ``bitplane_factorized`` (MXU
                 ``Y=(X@P)-(X@M)``, DESIGN.md §4), ``ref``;
* ``base3``:     ``ref`` (LUT-gather decode — the paper's dropped format,
                 kept dispatchable for the benchmark record).

New formats/kernels plug in via ``weights.register_format`` +
``register_kernel`` without touching any call site.

A third registry fuses whole MLP blocks: ``fused_mlp(x, w_in, w_out,
w_gate)`` runs ``GEMM -> bias -> activation -> GEMM`` as one kernel with
the hidden activation resident in VMEM (``impl="pallas"``), falling back
to the literal unfused chain (``impl="chain"``) for formats the fused
kernel does not cover. Both are pinned bitwise-equal, so adoption in
``models.layers.mlp_apply`` is a pure performance decision.

**Removed shim**: the pre-container operand union (raw ``(K/16, N)``
uint32 code matrix, ``formats.TiledTernary``, ``(plus, minus)`` tuple)
went through its two deprecation cycles (PR 3 warned, this PR errors) —
``ternary_gemm`` now raises ``TypeError`` pointing at ``weights.pack`` /
``kernels.pack_weights*``.

Every path defines a custom VJP (dY/dX = g @ T^T; packed weights are
non-differentiable — training uses the QAT/STE latent-weight path in
``core.quantize``).
"""
from __future__ import annotations

import contextlib
import contextvars
import dataclasses
import functools
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core import formats, weights
from repro.kernels import ref
from repro.kernels import autotune as autotune_lib
from repro.kernels.fused_mlp import ACTIVATIONS, _act, fused_mlp_pallas
from repro.kernels.ternary_gemm import (K_PER_WORD, ternary_gemm_pallas,
                                        ternary_gemm_skip_db_pallas,
                                        ternary_gemm_skip_pallas)
from repro.kernels.ternary_gemm_bitplane import (K_PER_BYTE,
                                                 ternary_gemm_bitplane)

__all__ = ["ternary_gemm", "ternary_gemm_plan", "GemmPlan", "KernelImpl",
           "register_kernel", "kernel_registry", "precompute_plans",
           "fused_mlp", "fused_mlp_plan", "FusedMlpPlan",
           "register_fused", "fused_registry", "precompute_fused_plans",
           "pack_weights", "pack_weights_tiled",
           "serving_phase", "current_phase", "SERVING_PHASES",
           "tensor_parallel", "current_tp_mesh",
           "SKIP_OCCUPANCY_CUTOFF",
           "paged_decode_attention", "paged_window_attention",
           "register_paged_attn", "paged_attention_registry",
           "resolve_paged_attn"]

# Serving-phase tag consumed at trace time: prefill GEMMs are M=B·L
# GEMM-shaped, decode GEMMs are M=slots GEMV-shaped, verify GEMMs
# (speculative decoding, DESIGN.md §10) are M=slots·(k+1) small-GEMM
# shaped, and chunk GEMMs (chunked prefill, DESIGN.md §14) are
# M=P·chunk_tokens mid-size — no two of them may share (and thrash) one
# autotune entry even when their bucketed M collides.
SERVING_PHASES = ("prefill", "decode", "verify", "chunk")

_SERVING_PHASE: contextvars.ContextVar[Optional[str]] = \
    contextvars.ContextVar("repro_serving_phase", default=None)


@contextlib.contextmanager
def serving_phase(phase: Optional[str]):
    """Tag ``ternary_gemm`` dispatches traced inside this scope with one
    of ``SERVING_PHASES`` so the autotuner keys them separately (the
    serving engine wraps its phase jit calls in this)."""
    assert phase is None or phase in SERVING_PHASES, phase
    token = _SERVING_PHASE.set(phase)
    try:
        yield
    finally:
        _SERVING_PHASE.reset(token)


def current_phase() -> Optional[str]:
    return _SERVING_PHASE.get()


# Tensor-parallel mesh consumed at trace time (DESIGN.md §13). A Pallas
# kernel has no GSPMD partitioning rule, so model code traced for a mesh
# runs its Pallas lowerings per shard under ``jax.shard_map``: GEMMs of
# TP-placed weights (``TernaryWeight.tp_dim``) and paged attention over
# head-sharded pages. XLA lowerings ("ref", the "jax" paged gather) stay
# on GSPMD, which partitions them itself.
_TP_MESH: contextvars.ContextVar[Optional[Any]] = \
    contextvars.ContextVar("repro_tp_mesh", default=None)


@contextlib.contextmanager
def tensor_parallel(mesh):
    """Trace model code in this scope for ``mesh``'s ``"model"`` axis (the
    serving engine wraps its steps in this). ``mesh=None`` or a mesh
    without a ``"model"`` axis wider than 1 leaves dispatch unchanged."""
    token = _TP_MESH.set(mesh)
    try:
        yield
    finally:
        _TP_MESH.reset(token)


def current_tp_mesh():
    """The ambient TP mesh, or None when its ``"model"`` axis is 1-wide."""
    mesh = _TP_MESH.get()
    if mesh is None or dict(mesh.shape).get("model", 1) <= 1:
        return None
    return mesh


# Above this occupied-tile fraction the skipping grid saves too little to
# justify the scalar-prefetch indirection; "auto" falls back to dense.
SKIP_OCCUPANCY_CUTOFF = 0.875


def _auto_interpret() -> bool:
    return jax.default_backend() != "tpu"


def pack_weights(t: np.ndarray, scale=None, bias=None) -> weights.Dense2Bit:
    """Host-side: (K, N) {-1,0,1} -> ``Dense2Bit`` container (16 weights per
    uint32 word, the dense kernel format)."""
    return weights.Dense2Bit.from_dense(np.asarray(t), scale=scale,
                                        bias=bias)


def pack_weights_tiled(t: np.ndarray, tile_k: int = 256,
                       tile_n: int = 128, scale=None,
                       bias=None) -> weights.Tiled:
    """Host-side: (K, N) {-1,0,1} -> ``Tiled`` container (packed words +
    per-tile occupancy metadata) for the skipping kernel."""
    return weights.Tiled.from_dense(np.asarray(t), tile_k=tile_k,
                                    tile_n=tile_n, scale=scale, bias=bias)


def _pad_to(x: jnp.ndarray, axis: int, mult: int) -> jnp.ndarray:
    size = x.shape[axis]
    pad = (-size) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


# ---------------------------------------------------------------------------
# 2-bit-code family (dense + skipping share the packed format and the VJP)
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp,
                   nondiff_argnums=(6, 7, 8, 9, 10, 11, 12, 13))
def _gemm_2bit(x, w_packed, scale, bias, kt_idx, kt_cnt,
               n, block_m, block_n, block_k, fuse_prelu, prelu_alpha,
               interpret, db):
    """Forward: dense kernel when kt_idx is None, else one of the skipping
    kernels (``db`` selects the double-buffered-DMA variant). Returns the
    (m, n)-sliced logical output."""
    m = x.shape[0]
    bm = min(block_m, max(8, 1 << (m - 1).bit_length()))
    sp = None if scale is None else _pad_to(scale.reshape(-1), 0, block_n)
    bp = None if bias is None else _pad_to(bias.reshape(-1), 0, block_n)
    # x's K must first match the packed operand's (possibly padded) K — the
    # word rows can exceed ceil(k/block_k)*block_k when the pack used a
    # larger tile_k than the resolved block_k.
    kp = w_packed.shape[0] * K_PER_WORD
    xp = _pad_to(_pad_to(x, 1, kp), 0, bm)
    if kt_idx is None:
        xp = _pad_to(xp, 1, block_k)
        wp = _pad_to(_pad_to(w_packed, 0, block_k // K_PER_WORD), 1, block_n)
        y = ternary_gemm_pallas(
            xp, wp, sp, bp, block_m=bm, block_n=block_n, block_k=block_k,
            fuse_prelu=fuse_prelu, prelu_alpha=prelu_alpha,
            interpret=interpret)
    else:
        skip_kernel = (ternary_gemm_skip_db_pallas if db
                       else ternary_gemm_skip_pallas)
        y = skip_kernel(
            xp, w_packed, kt_idx, kt_cnt, sp, bp,
            block_m=bm, block_n=block_n, block_k=block_k,
            fuse_prelu=fuse_prelu, prelu_alpha=prelu_alpha,
            interpret=interpret)
    return y[:m, :n]


def _gemm_2bit_fwd(x, w_packed, scale, bias, kt_idx, kt_cnt, *static):
    y = _gemm_2bit(x, w_packed, scale, bias, kt_idx, kt_cnt, *static)
    fuse_prelu = static[4]
    return y, (x, w_packed, scale, bias, kt_idx, kt_cnt,
               y if fuse_prelu else None)


def _gemm_2bit_bwd(n, bm, bn, bk, fuse_prelu, prelu_alpha, interpret, db,
                   res, g):
    x, w_packed, scale, bias, kt_idx, kt_cnt, y = res
    kk = x.shape[1]  # logical K is x's trailing dim (x is unpadded)
    if fuse_prelu:
        g = jnp.where(y >= 0, g, prelu_alpha * g)
    # Bias grad exists only when a bias operand exists (scale is irrelevant).
    gb = (None if bias is None
          else jnp.sum(g, axis=0).astype(bias.dtype).reshape(bias.shape))
    t = formats.decode_2bit(w_packed, kk, dtype=x.dtype)[:, :n]
    if scale is not None:
        # dL/dscale = sum_m g * (x @ T): exact, costs one decode+matmul.
        ylin = jnp.dot(x, t, preferred_element_type=jnp.float32)
        gscale = jnp.sum(g.astype(jnp.float32) * ylin, axis=0).astype(
            scale.dtype).reshape(scale.shape)
        g = g * scale.reshape(1, -1).astype(g.dtype)
    else:
        gscale = None
    gx = jnp.dot(g, t.T, preferred_element_type=jnp.float32).astype(x.dtype)
    return (gx, jnp.zeros_like(w_packed), gscale, gb,
            None if kt_idx is None else jnp.zeros_like(kt_idx),
            None if kt_cnt is None else jnp.zeros_like(kt_cnt))


_gemm_2bit.defvjp(_gemm_2bit_fwd, _gemm_2bit_bwd)


# ---------------------------------------------------------------------------
# Bitplane family (combined decode / plane-factorized)
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8))
def _gemm_bitplane(x, plus, minus, scale, block_m, block_n, block_k,
                   factorized, interpret):
    return ternary_gemm_bitplane(
        x, plus, minus, scale, block_m=block_m, block_n=block_n,
        block_k=block_k, factorized=factorized, interpret=interpret)


def _gemm_bitplane_fwd(x, plus, minus, scale, *static):
    y = _gemm_bitplane(x, plus, minus, scale, *static)
    return y, (x, plus, minus, scale)


def _gemm_bitplane_bwd(bm, bn, bk, factorized, interpret, res, g):
    x, plus, minus, scale = res
    kk = x.shape[1]
    t = formats.decode_bitplanes(plus, minus, kk, dtype=x.dtype)
    t = t[:, :g.shape[1]]
    if scale is not None:
        ylin = jnp.dot(x, t, preferred_element_type=jnp.float32)
        gscale = jnp.sum(g.astype(jnp.float32) * ylin, axis=0).astype(
            scale.dtype).reshape(scale.shape)
        g = g * scale.reshape(1, -1).astype(g.dtype)
    else:
        gscale = None
    gx = jnp.dot(g, t.T, preferred_element_type=jnp.float32).astype(x.dtype)
    return gx, jnp.zeros_like(plus), jnp.zeros_like(minus), gscale


_gemm_bitplane.defvjp(_gemm_bitplane_fwd, _gemm_bitplane_bwd)


# ---------------------------------------------------------------------------
# The kernel registry
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class GemmPlan:
    """Inspectable dispatch decision for one ternary GEMM.

    Produced by ``ternary_gemm_plan``; consumed by the registered lowering.
    ``block_*`` are ``None`` for reference (non-Pallas) impls.

    Example (doctest-runnable)::

        >>> import numpy as np
        >>> from repro.core import weights
        >>> from repro.kernels import ops
        >>> w = weights.pack(np.sign(np.random.randn(512, 256)), "dense2bit")
        >>> plan = ops.ternary_gemm_plan(w, m=128)
        >>> (plan.format, plan.impl, plan.m, plan.k, plan.n)
        ('dense2bit', 'dense', 128, 512, 256)
        >>> sorted(plan.roofline())     # doctest: +NORMALIZE_WHITESPACE
        ['achieved_flops', 'arithmetic_intensity', 'bound', 'bytes',
         'ceiling_flops', 'collective', 'collective_bytes', 'flops',
         'headroom', 'model_time_s', 'peak_flops', 'tp']

    Under tensor parallelism (``ternary_gemm_plan(..., partition=, tp=)``)
    ``m``/``k``/``n`` are the *per-shard* problem — ``partition="k"`` row
    splits K and carries an explicit ``collective="psum"`` (the all-reduce
    over partial products); ``partition="n"`` column splits N with no
    collective (the next row-split layer consumes the sharded activation).
    """

    format: str
    impl: str
    m: int
    k: int
    n: int
    block_m: Optional[int]
    block_n: Optional[int]
    block_k: Optional[int]
    phase: Optional[str]
    occupancy: float
    interpret: bool
    fuse_prelu: bool = False
    prelu_alpha: float = 0.25
    partition: Optional[str] = None      # None | "k" | "n"
    collective: Optional[str] = None     # None | "psum"
    tp: int = 1

    def traffic(self) -> Dict[str, float]:
        """Modeled FLOPs and HBM bytes for one pass, from the plan's block
        shapes and the pack-time occupancy metadata. Skip-family impls
        scale the K axis by the occupied-tile fraction — the same model
        the autotuner scores with, so plan and tune never disagree."""
        skipping = self.impl in ("skip", "skip_db")
        occ = self.occupancy if skipping else 1.0
        bm = self.block_m or min(128, max(8, 1 << (self.m - 1).bit_length()))
        bn = self.block_n or 128
        bk = self.block_k or 256
        mp = -(-self.m // bm) * bm
        npad = -(-self.n // bn) * bn
        kp = -(-self.k // bk) * bk
        m_tiles, n_tiles = mp // bm, npad // bn
        k_steps = max(1, round((kp // bk) * occ))
        flops = 2.0 * mp * npad * (k_steps * bk)
        x_bytes = m_tiles * n_tiles * k_steps * bm * bk * 2
        w_bytes = m_tiles * n_tiles * k_steps * (bk // K_PER_WORD) * bn * 4
        out_bytes = mp * npad * 2
        # ring all-reduce over the K-split partial products: each shard
        # sends/receives 2*(tp-1)/tp of the (m, n) f32 partial output
        coll = (2.0 * (self.tp - 1) / self.tp * self.m * self.n * 4
                if self.collective == "psum" and self.tp > 1 else 0.0)
        return {"flops": flops,
                "bytes": float(x_bytes + w_bytes + out_bytes),
                "collective_bytes": coll}

    def roofline(self) -> Dict[str, float]:
        """Roofline position of this plan on the modeled machine
        (``autotune.HBM_BW`` / ``autotune.PEAK_FLOPS``): achieved vs
        ceiling FLOP/s, arithmetic intensity, and remaining headroom.
        Emitted per registered kernel by ``benchmarks/roofline.py``.
        Raises on a TPU the modeled peaks do not describe."""
        autotune_lib.check_roofline_device()
        t = self.traffic()
        ai = t["flops"] / max(t["bytes"], 1.0)
        ceiling = min(autotune_lib.PEAK_FLOPS, ai * autotune_lib.HBM_BW)
        # achieved = modeled time for this plan's tile traffic (the same
        # score the tuner minimized, incl. grid + VMEM-pressure overheads)
        cfg = autotune_lib.BlockConfig(
            self.block_m or 128, self.block_n or 128, self.block_k or 256)
        t_model = autotune_lib.Autotuner()._model_score(
            cfg, self.m, self.k, self.n,
            self.occupancy if self.impl in ("skip", "skip_db") else 1.0)
        achieved = t["flops"] / max(t_model, 1e-12)
        return {"flops": t["flops"], "bytes": t["bytes"],
                "arithmetic_intensity": ai,
                "ceiling_flops": ceiling,
                "achieved_flops": achieved,
                "peak_flops": autotune_lib.PEAK_FLOPS,
                "model_time_s": t_model,
                "headroom": max(0.0, 1.0 - achieved / max(ceiling, 1.0)),
                "bound": ("memory" if ceiling < autotune_lib.PEAK_FLOPS
                          else "compute"),
                "collective": self.collective,
                "collective_bytes": t["collective_bytes"],
                "tp": self.tp}


@dataclasses.dataclass(frozen=True)
class KernelImpl:
    """One registered lowering: ``(format, impl)`` -> kernel.

    ``predicate(w, m, phase)`` gates ``impl="auto"`` selection (highest
    admissible ``priority`` wins); ``plan_blocks(w, m, phase, bm, bn, bk)``
    resolves block shapes (consulting the autotuner for ``None`` entries);
    ``lower(plan, x, w, scale, bias)`` executes."""

    format: str
    impl: str
    priority: int
    predicate: Callable[[weights.TernaryWeight, int, Optional[str]], bool]
    plan_blocks: Callable
    lower: Callable


_KERNELS: Dict[Tuple[str, str], KernelImpl] = {}


def register_kernel(fmt: str, impl: str, *, priority: int = 0,
                    predicate: Optional[Callable] = None,
                    plan_blocks: Optional[Callable] = None):
    """Decorator registering a lowering for ``(format, impl)``. The single
    extension point for new kernels — dispatch, ``impl="auto"`` selection
    and ``ternary_gemm_plan`` pick it up with no call-site changes.

    ``predicate(w, m, phase)`` gates ``impl="auto"`` (highest admissible
    ``priority`` wins); ``plan_blocks(w, m, phase, bm, bn, bk)`` resolves
    block shapes (``None`` entries usually consult the autotuner);
    the decorated ``fn(plan, x, w, scale, bias)`` executes.

    Example (doctest-runnable) — a reference lowering that only admits
    GEMV-shaped dispatches::

        >>> import numpy as np
        >>> from repro.core import weights
        >>> from repro.kernels import ops, ref
        >>> @ops.register_kernel("dense2bit", "gemv_ref", priority=1,
        ...                      predicate=lambda w, m, phase: m == 1)
        ... def _lower_gemv(plan, x, w, scale, bias):
        ...     return ref.packed2bit_matmul(x, w.packed, w.k)[:, :w.n]
        >>> w = weights.pack(np.sign(np.random.randn(64, 32)), "dense2bit")
        >>> ops.ternary_gemm_plan(w, m=1, impl="gemv_ref").impl
        'gemv_ref'
        >>> del ops._KERNELS[("dense2bit", "gemv_ref")]   # leave no trace
    """

    def deco(fn):
        _KERNELS[(fmt, impl)] = KernelImpl(
            format=fmt, impl=impl, priority=priority,
            predicate=predicate or (lambda w, m, phase: True),
            plan_blocks=plan_blocks or (lambda w, m, phase, bm, bn, bk:
                                        (bm, bn, bk)),
            lower=fn)
        return fn

    return deco


def kernel_registry() -> Dict[Tuple[str, str], KernelImpl]:
    """Snapshot of the registered ``(format, impl) -> KernelImpl`` table."""
    return dict(_KERNELS)


# --- block planning helpers -------------------------------------------------

def _blocks_dense(w, m, phase, bm, bn, bk):
    # Dense-decode traffic is occupancy-independent: tune under the dense
    # key (sparsity=1.0) so plans do not depend on pack-time nnz metadata
    # (keeps a restored checkpoint's plan identical to the packing boot's).
    if bm is None or bn is None or bk is None:
        cfg = autotune_lib.get_tuner().lookup(
            m, w.k, w.n, sparsity=1.0, impl="dense", phase=phase)
        bm = bm if bm is not None else cfg.block_m
        bn = bn if bn is not None else cfg.block_n
        bk = bk if bk is not None else cfg.block_k
    return bm, bn, bk


def _blocks_skip_impl(impl):
    def plan(w, m, phase, bm, bn, bk):
        # Pack-time tile shapes dictate the kernel's K/N blocks.
        if bn is not None and bn != w.tile_n:
            raise ValueError(f"impl={impl!r}: block_n={bn} must equal the "
                             f"pack's tile_n={w.tile_n}")
        if bk is not None and bk != w.tile_k:
            raise ValueError(f"impl={impl!r}: block_k={bk} must equal the "
                             f"pack's tile_k={w.tile_k}")
        if bm is None:
            bm = autotune_lib.get_tuner().lookup(
                m, w.k, w.n, sparsity=w.occupancy(), impl=impl,
                fixed_n=w.tile_n, fixed_k=w.tile_k, phase=phase).block_m
        return bm, w.tile_n, w.tile_k
    return plan


_blocks_skip = _blocks_skip_impl("skip")
_blocks_skip_db = _blocks_skip_impl("skip_db")


def _blocks_bitplane(impl):
    def plan(w, m, phase, bm, bn, bk):
        if bm is None or bn is None or bk is None:
            cfg = autotune_lib.get_tuner().lookup(
                m, w.k, w.n, impl=impl, phase=phase)
            bm = bm if bm is not None else cfg.block_m
            bn = bn if bn is not None else cfg.block_n
            bk = bk if bk is not None else cfg.block_k
        return bm, bn, bk
    return plan


def _no_blocks(w, m, phase, bm, bn, bk):
    return None, None, None


def _require_2d(w, *leaves):
    for leaf in leaves:
        if getattr(leaf, "ndim", 2) != 2:
            raise ValueError(
                f"{w.format_name} weight has stacked leaves "
                f"{tuple(leaf.shape)}; slice the stack (scan/vmap) down to "
                f"2-D before ternary_gemm")


# --- dense2bit lowerings ----------------------------------------------------

@register_kernel("dense2bit", "dense", priority=10,
                 plan_blocks=_blocks_dense)
def _lower_dense(plan, x, w, scale, bias):
    wp = jnp.asarray(w.packed)
    _require_2d(w, wp)
    return _gemm_2bit(x, wp[:, :w.n], scale, bias, None, None,
                      w.n, plan.block_m, plan.block_n, plan.block_k,
                      plan.fuse_prelu, plan.prelu_alpha, plan.interpret,
                      False)


@register_kernel("dense2bit", "ref", plan_blocks=_no_blocks)
def _lower_dense_ref(plan, x, w, scale, bias):
    wp = jnp.asarray(w.packed)
    _require_2d(w, wp)
    return ref.packed2bit_matmul(
        x, wp, w.k, alpha=scale, bias=bias,
        prelu_alpha=plan.prelu_alpha if plan.fuse_prelu else None)[:, :w.n]


# --- tiled lowerings --------------------------------------------------------

@register_kernel("tiled", "skip_db", priority=12,
                 predicate=lambda w, m, phase:
                     w.occupancy() <= SKIP_OCCUPANCY_CUTOFF,
                 plan_blocks=_blocks_skip_db)
def _lower_skip_db(plan, x, w, scale, bias):
    # Same occupied-tile walk as "skip", but the kernel stages each tile
    # through explicit double-buffered make_async_copy pipelines so the
    # next tile's DMA overlaps the current tile's MXU work (DESIGN.md §12).
    # Bitwise identical to "skip"/"dense" (same ascending-K accumulation).
    return _gemm_2bit(x, jnp.asarray(w.packed), scale, bias,
                      jnp.asarray(w.kt_indices), jnp.asarray(w.kt_counts),
                      w.n, plan.block_m, plan.block_n, plan.block_k,
                      plan.fuse_prelu, plan.prelu_alpha, plan.interpret,
                      True)


@register_kernel("tiled", "skip", priority=10,
                 predicate=lambda w, m, phase:
                     w.occupancy() <= SKIP_OCCUPANCY_CUTOFF,
                 plan_blocks=_blocks_skip)
def _lower_skip(plan, x, w, scale, bias):
    return _gemm_2bit(x, jnp.asarray(w.packed), scale, bias,
                      jnp.asarray(w.kt_indices), jnp.asarray(w.kt_counts),
                      w.n, plan.block_m, plan.block_n, plan.block_k,
                      plan.fuse_prelu, plan.prelu_alpha, plan.interpret,
                      False)


@register_kernel("tiled", "dense", priority=5, plan_blocks=_blocks_dense)
def _lower_tiled_dense(plan, x, w, scale, bias):
    # packed word columns map 1:1 to W columns -> drop the N padding
    return _gemm_2bit(x, jnp.asarray(w.packed)[:, :w.n], scale, bias,
                      None, None, w.n, plan.block_m, plan.block_n,
                      plan.block_k, plan.fuse_prelu, plan.prelu_alpha,
                      plan.interpret, False)


@register_kernel("tiled", "ref", plan_blocks=_no_blocks)
def _lower_tiled_ref(plan, x, w, scale, bias):
    return ref.packed2bit_matmul(
        x, jnp.asarray(w.packed)[:, :w.n], w.k, alpha=scale, bias=bias,
        prelu_alpha=plan.prelu_alpha if plan.fuse_prelu else None)


# --- bitplane lowerings -----------------------------------------------------

def _lower_bitplane_common(plan, x, w, scale, bias, factorized):
    plus, minus = jnp.asarray(w.plus), jnp.asarray(w.minus)
    _require_2d(w, plus)
    bm, bn, bk = plan.block_m, plan.block_n, plan.block_k
    xp = _pad_to(x, 1, plus.shape[0] * K_PER_BYTE)
    y = _gemm_bitplane(xp, plus, minus, scale, bm, bn, bk, factorized,
                       plan.interpret)
    if bias is not None:
        y = y + bias.reshape(1, -1).astype(y.dtype)
    if plan.fuse_prelu:
        y = jnp.where(y >= 0, y, jnp.asarray(plan.prelu_alpha, y.dtype) * y)
    return y


@register_kernel("bitplane", "bitplane", priority=10,
                 plan_blocks=_blocks_bitplane("bitplane"))
def _lower_bitplane(plan, x, w, scale, bias):
    return _lower_bitplane_common(plan, x, w, scale, bias, factorized=False)


@register_kernel("bitplane", "bitplane_factorized", priority=5,
                 plan_blocks=_blocks_bitplane("bitplane_factorized"))
def _lower_bitplane_fact(plan, x, w, scale, bias):
    return _lower_bitplane_common(plan, x, w, scale, bias, factorized=True)


@register_kernel("bitplane", "ref", plan_blocks=_no_blocks)
def _lower_bitplane_ref(plan, x, w, scale, bias):
    return ref.bitplane_matmul(
        x, jnp.asarray(w.plus), jnp.asarray(w.minus), w.k, alpha=scale,
        bias=bias,
        prelu_alpha=plan.prelu_alpha if plan.fuse_prelu else None)[:, :w.n]


# --- base3 lowering (the paper's value-compression format, ref-backed) ------

@register_kernel("base3", "ref", priority=10, plan_blocks=_no_blocks)
def _lower_base3_ref(plan, x, w, scale, bias):
    return ref.base3_matmul(
        x, jnp.asarray(w.packed), w.k, alpha=scale, bias=bias,
        prelu_alpha=plan.prelu_alpha if plan.fuse_prelu else None)[:, :w.n]


# ---------------------------------------------------------------------------
# Paged-attention kernel registry (DESIGN.md §9)
# ---------------------------------------------------------------------------
#
# Same registry discipline as the GEMM table above, for the paged KV-cache
# decode-attention lowerings: each impl registers a name, a priority and an
# admissibility predicate, and ``impl="auto"`` picks the best admissible one
# (the Pallas kernel on TPU backends, the gather + dense-identical JAX path
# elsewhere — the latter is what the paged-vs-dense token-exactness
# guarantee rests on). Lowerings live in ``repro.paging.kernels`` and
# register themselves on import.

@dataclasses.dataclass(frozen=True)
class PagedAttnImpl:
    """One registered paged decode-attention lowering."""

    impl: str
    priority: int
    predicate: Callable[..., bool]
    fn: Callable
    window_fn: Optional[Callable] = None


_PAGED_ATTN: Dict[str, PagedAttnImpl] = {}


def register_paged_attn(impl: str, *, priority: int = 0,
                        predicate: Optional[Callable] = None,
                        window: Optional[Callable] = None):
    """Decorator registering a paged decode-attention lowering under
    ``impl``. ``predicate()`` says whether the lowering is admissible on
    the current backend; it gates ``impl="auto"`` selection (highest
    admissible priority wins). ``window``, where given, is the lowering's
    multi-token window form (``paged_window_attention``); without it a
    window flattens into single-query rows of ``fn``."""

    def deco(fn):
        _PAGED_ATTN[impl] = PagedAttnImpl(
            impl=impl, priority=priority,
            predicate=predicate or (lambda: True), fn=fn, window_fn=window)
        return fn

    return deco


def paged_attention_registry() -> Dict[str, "PagedAttnImpl"]:
    """Snapshot of the registered paged-attention impl table.

    Example (doctest-runnable) — the two stock lowerings are always
    present, and each entry carries its selection metadata::

        >>> from repro.kernels import ops
        >>> table = ops.paged_attention_registry()
        >>> sorted(table)
        ['jax', 'pallas']
        >>> table["jax"].priority <= table["pallas"].priority
        True
    """
    _ensure_paged_impls()
    return dict(_PAGED_ATTN)


def _ensure_paged_impls() -> None:
    # the lowerings self-register on import; imported lazily so kernels.ops
    # stays importable without pulling the paging subsystem in
    import repro.paging.kernels  # noqa: F401


def resolve_paged_attn(impl: str = "auto") -> str:
    """The registered paged-attention lowering ``impl`` names: itself when
    registered, or for ``"auto"`` the highest-priority one admissible on
    this backend (the Pallas kernel on TPU, the jax gather elsewhere)."""
    _ensure_paged_impls()
    if impl == "auto":
        cands = sorted(_PAGED_ATTN.values(), key=lambda pi: -pi.priority)
        return next((pi for pi in cands if pi.predicate()), cands[-1]).impl
    if impl not in _PAGED_ATTN:
        raise ValueError(f"no paged-attention impl {impl!r} registered; "
                         f"available: {sorted(_PAGED_ATTN)}")
    return impl


def paged_decode_attention(q, k_pages, v_pages, block_table, lengths, *,
                           window: int = 0, impl: str = "auto",
                           interpret: Optional[bool] = None):
    """Decode attention over block-table-indexed KV pages.

    q (B, H, hd); k_pages/v_pages (P, ps, KV, hd) arrays or
    ``paging.quant.Int8Pages``; block_table (B, T) int32; lengths (B,)
    int32 valid-token counts (including the current token). ``impl`` picks
    a registered lowering (``resolve_paged_attn``)."""
    chosen = _PAGED_ATTN[resolve_paged_attn(impl)]
    mesh = current_tp_mesh()
    if mesh is not None and chosen.impl != "jax":
        return _tp_paged_attention(mesh, chosen.fn, q, k_pages, v_pages,
                                   block_table, lengths, window=window,
                                   interpret=interpret)
    return chosen.fn(q, k_pages, v_pages, block_table, lengths,
                     window=window, interpret=interpret)


def paged_window_attention(q, k_pages, v_pages, block_table, lengths, *,
                           window: int = 0, impl: str = "auto",
                           interpret: Optional[bool] = None):
    """Attention of an S-token window per row over block-table-indexed KV
    pages (chunked prefill, speculative verify).

    q (B, S, H, hd); pages and block_table (B, T) as in
    ``paged_decode_attention``; lengths (B,) int32 valid-token count of
    each row's first window token, whose K/V is already in the pages:
    token ``j`` attends the keys at positions ``< lengths + j``. Returns
    (B, S, H, hd). A lowering with a window form (the Pallas kernel) walks
    each row's pages once for all S tokens; one without (the ``jax``
    gather) runs the window as (B·S) single-query rows, each row's block
    table repeated."""
    chosen = _PAGED_ATTN[resolve_paged_attn(impl)]
    if chosen.window_fn is None:
        b, s = q.shape[:2]
        o = paged_decode_attention(
            q.reshape(b * s, *q.shape[2:]), k_pages, v_pages,
            jnp.repeat(block_table, s, axis=0),
            (lengths[:, None] + jnp.arange(s)).reshape(-1), window=window,
            impl=chosen.impl, interpret=interpret)
        return o.reshape(q.shape)
    mesh = current_tp_mesh()
    if mesh is not None:
        return _tp_paged_attention(mesh, chosen.window_fn, q, k_pages,
                                   v_pages, block_table, lengths,
                                   window=window, interpret=interpret)
    return chosen.window_fn(q, k_pages, v_pages, block_table, lengths,
                            window=window, interpret=interpret)


def _tp_paged_attention(mesh, fn, q, k_pages, v_pages, block_table, lengths,
                        *, window, interpret):
    """A Pallas paged lowering per shard: q heads (the axis before
    ``head_dim``, for decode rows and windows alike) and the pages'
    KV-head axis split over ``"model"`` (the layout ``distributed.tp.
    cache_sharding`` places), block table and lengths replicated."""
    ntp = dict(mesh.shape)["model"]
    kv = jax.tree_util.tree_leaves(k_pages)[0].shape[2]
    if q.shape[-2] % ntp or kv % ntp:
        raise ValueError(
            f"paged attention: {q.shape[-2]} query / {kv} KV heads do not "
            f"split {ntp} ways over the mesh's 'model' axis")

    def page_spec(a):
        # (P, ps, KV, hd) pages and (P, ps, KV) int8 scales
        return P(None, None, "model", None) if a.ndim == 4 \
            else P(None, None, "model")

    heads = P(*[None] * (q.ndim - 2), "model", None)
    return jax.shard_map(
        lambda qq, kk, vv, bt, ln: fn(qq, kk, vv, bt, ln, window=window,
                                      interpret=interpret),
        mesh=mesh,
        in_specs=(heads, jax.tree.map(page_spec, k_pages),
                  jax.tree.map(page_spec, v_pages), P(), P()),
        out_specs=heads, check_vma=False)(
            q, k_pages, v_pages, block_table, lengths)


# ---------------------------------------------------------------------------
# Grouped expert GEMM (MoE): rows sorted by held expert
# ---------------------------------------------------------------------------
# Two lowerings of one product (``repro.kernels.moe_gemm``): the Pallas
# kernel (``pallas_call`` name ``moe_expert_gemm``), and the XLA product over
# the same sorted layout for backends without it; the caller picks, as the
# packed linears do (``models.layers._use_pallas_gemm``).

def _moe_gemm_impls():
    from repro.kernels import moe_gemm
    return {"pallas": moe_gemm.moe_expert_gemm_pallas,
            "xla": moe_gemm.moe_expert_gemm_xla}


def moe_expert_gemm(x, w: weights.Dense2Bit, tile_group, tile_row,
                    n_active, *, block_m: int, impl: str,
                    interpret: Optional[bool] = None):
    """Y = X @ (bank of each row's expert) * scale over expert-sorted rows.

    x (M, K) with M a multiple of ``block_m``, each tile of ``block_m``
    rows one expert's; ``w`` a stacked ``Dense2Bit`` bank (E, K/16, N);
    tile_group / tile_row (M / block_m,) int32 and n_active (1,) int32
    describe the tiles (``models.moe`` builds them). ``impl``: ``pallas``
    or ``xla``."""
    impls = _moe_gemm_impls()
    if impl not in impls:
        raise ValueError(f"no moe_expert_gemm impl {impl!r}; "
                         f"available: {sorted(impls)}")
    words = w.packed
    kp = words.shape[-2] * 16              # K padded to whole words
    if x.shape[1] != kp:
        x = jnp.pad(x, ((0, 0), (0, kp - x.shape[1])))
    kw = {}
    if impl == "pallas":
        kw["interpret"] = (_auto_interpret() if interpret is None
                           else interpret)
    return impls[impl](x, words, w.scale, tile_group, tile_row, n_active,
                       block_m=block_m, **kw)


# ---------------------------------------------------------------------------
# The planner
# ---------------------------------------------------------------------------

def _coerce_weight(w: Any, k: Optional[int],
                   xk: Optional[int]) -> weights.TernaryWeight:
    """Accept only typed containers. The PR-3-era raw-operand union (raw
    packed word matrix / ``formats.TiledTernary`` / ``(plus, minus)``
    tuple) finished its deprecation cycle — name the migration target in
    the error instead of silently wrapping."""
    if isinstance(w, weights.TernaryWeight):
        return w
    if isinstance(w, formats.TiledTernary):
        hint = "weights.Tiled.from_tiled(w) or re-pack via weights.pack"
    elif isinstance(w, (tuple, list)) and len(w) == 2:
        hint = "weights.Bitplane.from_planes(plus, minus, k=K)"
    elif getattr(w, "ndim", 0) == 2:
        hint = ("weights.Dense2Bit.from_packed(w, k=K) or "
                "kernels.pack_weights(ternary)")
    else:
        hint = "repro.core.weights.pack(w, format)"
    raise TypeError(
        f"ternary_gemm no longer accepts raw weight operands "
        f"(got {type(w).__name__}); the DeprecationWarning shim was "
        f"removed after two release cycles. Pack into a typed container: "
        f"{hint}.")


def _shard_view(w: weights.TernaryWeight, partition: Optional[str],
                tp: int) -> weights.TernaryWeight:
    """Static view of one ``tp``-way shard of ``w`` (leaves untouched): the
    per-shard logical shape the shard's kernel plans and runs against."""
    if partition is None:
        return w
    k, n = w.shape
    return w.replace(shape=(k // tp, n) if partition == "k"
                     else (k, n // tp))


def _validate_k(w: weights.TernaryWeight, xk: int, k: Optional[int]) -> None:
    """One K check for every format (the old dispatcher inferred K from x on
    the dense path but asserted on the operand for skip)."""
    if k is not None and k != w.k:
        raise ValueError(
            f"k={k} does not match the {w.format_name} weight's logical "
            f"K={w.k} (shape {w.shape})")
    if xk != w.k:
        raise ValueError(
            f"x has K={xk} columns but the {w.format_name} weight encodes "
            f"K={w.k} (shape {w.shape}); reshape x or repack the weight")


def ternary_gemm_plan(
    w: Any,
    m: int,
    *,
    k: Optional[int] = None,
    impl: str = "auto",
    phase: Optional[str] = "__current__",
    block_m: Optional[int] = None,
    block_n: Optional[int] = None,
    block_k: Optional[int] = None,
    fuse_prelu: bool = False,
    prelu_alpha: float = 0.25,
    interpret: Optional[bool] = None,
    partition: Optional[str] = None,
    tp: int = 1,
) -> GemmPlan:
    """Plan (but do not run) a ternary GEMM: registry + autotuner -> an
    inspectable ``GemmPlan``. ``phase`` defaults to the ambient
    ``serving_phase`` scope; ``k``, if given, is validated against the
    container. Planning uses only static container metadata, so it is
    trace-safe and cheap to precompute (the serving engine warms
    phase-keyed plans for every packed weight at build time).

    ``partition``/``tp`` plan one *shard* of a tensor-parallel GEMM
    (DESIGN.md §13): ``"k"`` row splits K ``tp`` ways and records the
    ``psum`` collective the partial products need; ``"n"`` column splits N
    with no collective. Shard boundaries must land on the container's pack
    multiples (``TernaryWeight.shard_constraints``) — the same rule
    ``weights.validate_spec_twin`` enforces on the spec twins.

    Example (doctest-runnable) — a sparse tiled pack below the occupancy
    cutoff selects the double-buffered skipping kernel, and the same
    weight plans independently per serving phase::

        >>> import numpy as np
        >>> from repro.core import weights
        >>> from repro.kernels import ops
        >>> t = np.sign(np.random.randn(512, 256))
        >>> t[:256] = 0                       # half the K tiles are empty
        >>> w = weights.pack(t, "tiled", tile_k=256, tile_n=128)
        >>> plan = ops.ternary_gemm_plan(w, m=64)
        >>> (plan.impl, plan.block_n, plan.block_k)
        ('skip_db', 128, 256)
        >>> ops.ternary_gemm_plan(w, m=8, phase="decode").phase
        'decode'
    """
    w = _coerce_weight(w, k, None)
    if phase == "__current__":
        phase = current_phase()
    interpret = _auto_interpret() if interpret is None else interpret
    if partition not in (None, "k", "n"):
        raise ValueError(f"partition must be 'k', 'n' or None, "
                         f"got {partition!r}")
    if tp < 1:
        raise ValueError(f"tp must be >= 1, got {tp}")
    if tp == 1:
        partition = None
    if partition is not None:
        extent, multiple = w.shard_constraints()[partition]
        if extent % (tp * multiple) != 0:
            raise ValueError(
                f"{w.format_name} GEMM: {partition.upper()}-partitioning "
                f"{tp}-way puts shard boundaries every {extent / tp:g} of "
                f"{extent} values — off the {multiple}-value pack multiple; "
                f"repack or choose tp dividing {extent // multiple}")
    k_shard = w.k // tp if partition == "k" else w.k
    n_shard = w.n // tp if partition == "n" else w.n
    fmt = w.format_name
    if impl == "auto":
        cands = sorted((ki for ki in _KERNELS.values() if ki.format == fmt),
                       key=lambda ki: -ki.priority)
        if not cands:
            raise ValueError(f"no kernel registered for format {fmt!r}")
        chosen = next((ki for ki in cands if ki.predicate(w, m, phase)),
                      cands[-1])
    else:
        chosen = _KERNELS.get((fmt, impl))
        if chosen is None:
            avail = sorted(i for f, i in _KERNELS if f == fmt)
            raise ValueError(f"no impl {impl!r} registered for format "
                             f"{fmt!r}; available: {avail}")
    bm, bn, bk = chosen.plan_blocks(_shard_view(w, partition, tp), m, phase,
                                    block_m, block_n, block_k)
    if partition is not None:
        # per-shard tiles: clamp the global autotune blocks to the shard's
        # axis extent so the plan's tiling matches what one device runs
        bk = min(bk, k_shard) if bk else bk
        bn = min(bn, n_shard) if bn else bn
    return GemmPlan(format=fmt, impl=chosen.impl, m=m, k=k_shard, n=n_shard,
                    block_m=bm, block_n=bn, block_k=bk, phase=phase,
                    occupancy=w.occupancy(), interpret=interpret,
                    fuse_prelu=fuse_prelu, prelu_alpha=prelu_alpha,
                    partition=partition,
                    collective="psum" if partition == "k" else None,
                    tp=tp)


def precompute_plans(params, *, prefill_ms=(), decode_ms=(), verify_ms=(),
                     chunk_ms=(),
                     select: Optional[Callable] = None, impl: str = "auto",
                     shard: Optional[Callable] = None,
                     ) -> Dict[Tuple[int, ...], GemmPlan]:
    """Warm phase-keyed plans for ``TernaryWeight``s in a param tree.

    Called once at serving-engine build: every (weight, M-bucket, phase)
    combination the hot loop will dispatch gets its autotune entry resolved
    (and persisted) up front, so no serving step pays a first-call tune.
    ``select(path, w) -> bool`` filters which containers to plan — the
    engine selects only those that actually dispatch through
    ``ternary_gemm`` (packed linears), not containers a model materializes
    instead (MoE expert banks) — and ``impl`` should be the impl the apply
    path will dispatch (planning ``"ref"`` touches no autotune state).
    ``shard(path, w) -> (partition, tp)`` makes plans collective-aware
    under TP serving (``distributed.tp.gemm_shard_fn`` derives it from the
    placed arrays' shardings). Returns the plans keyed by
    (leaf index, m, phase) for introspection."""
    flat = jax.tree_util.tree_flatten_with_path(
        params, is_leaf=lambda v: isinstance(v, weights.TernaryWeight))[0]
    ws = [(path, w) for path, w in flat
          if isinstance(w, weights.TernaryWeight)
          and (select is None or select(path, w))]
    plans: Dict[Tuple[int, ...], GemmPlan] = {}
    for i, (path, w) in enumerate(ws):
        part, ntp = shard(path, w) if shard is not None else (None, 1)
        for phase, ms in (("prefill", prefill_ms), ("decode", decode_ms),
                          ("verify", verify_ms), ("chunk", chunk_ms)):
            for m in ms:
                plans[(i, m, phase)] = ternary_gemm_plan(
                    w, m, impl=impl, phase=phase, partition=part, tp=ntp)
    return plans


# ---------------------------------------------------------------------------
# Fused MLP registry (DESIGN.md §12)
# ---------------------------------------------------------------------------
#
# Third registry, same discipline: ``fused_mlp`` runs the whole
# ``GEMM -> bias -> activation -> GEMM`` block through one registered
# lowering. ``"pallas"`` is the fused kernel (hidden activation resident in
# VMEM, weights streamed with double-buffered DMA); ``"chain"`` is the
# literal unfused call chain and covers every format the fused kernel does
# not. The two are pinned bitwise-equal (tests/test_fused_mlp.py), which
# is what lets ``models.layers.mlp_apply`` adopt the fusion transparently.

_FUSED_FORMATS = ("dense2bit", "tiled")


@dataclasses.dataclass(frozen=True)
class FusedMlpPlan:
    """Dispatch decision for one fused MLP block.

    ``block_n1/block_k1`` tile the up/gate projection, ``block_n2/
    block_k2`` the down projection; all are taken from the *chain's* own
    ``GemmPlan``s (via the fused autotune key), so the fused kernel tiles
    K identically to the unfused chain — the bitwise-equality contract.

    Under TP (``fused_mlp_plan(..., tp=)``) ``ff`` is the *per-shard*
    hidden width: up/gate column split the hidden dim, down row splits it
    back, and the single trailing ``psum`` (``collective``) reduces the
    partial outputs — the Megatron MLP layout (DESIGN.md §13)."""

    impl: str
    format_up: str
    format_down: str
    m: int
    k: int
    ff: int
    n: int
    gated: bool
    activation: str
    block_m: Optional[int]
    block_n1: Optional[int]
    block_k1: Optional[int]
    block_n2: Optional[int]
    block_k2: Optional[int]
    phase: Optional[str]
    occupancy_up: float
    occupancy_down: float
    interpret: bool
    collective: Optional[str] = None     # None | "psum"
    tp: int = 1

    def sub_plans(self) -> Tuple[GemmPlan, GemmPlan]:
        """The two chained ``GemmPlan``s this fusion replaces (gate shares
        the up plan) — the roofline baseline."""
        mk = dict(phase=self.phase, interpret=self.interpret, tp=self.tp)
        sharded = self.tp > 1
        up = GemmPlan(format=self.format_up, impl="dense", m=self.m,
                      k=self.k, n=self.ff, block_m=self.block_m,
                      block_n=self.block_n1, block_k=self.block_k1,
                      occupancy=self.occupancy_up,
                      partition="n" if sharded else None, **mk)
        down = GemmPlan(format=self.format_down, impl="dense", m=self.m,
                        k=self.ff, n=self.n, block_m=self.block_m,
                        block_n=self.block_n2, block_k=self.block_k2,
                        occupancy=self.occupancy_down,
                        partition="k" if sharded else None,
                        collective=self.collective, **mk)
        return up, down

    def roofline(self) -> Dict[str, float]:
        """Fused vs unfused roofline: the chain's HBM traffic (both GEMMs,
        plus the hidden activation's write + per-N-tile re-reads), the
        fused kernel's (x and each weight once per M tile, h never leaves
        VMEM), and the modeled speedup ratio the CI bench gates on. Raises
        on a TPU the modeled peaks do not describe."""
        autotune_lib.check_roofline_device()
        up, down = self.sub_plans()
        n_up = 2 if self.gated else 1
        unfused_bytes = n_up * up.traffic()["bytes"] \
            + down.traffic()["bytes"]
        bm = self.block_m or 128
        mp = -(-self.m // bm) * bm
        m_tiles = mp // bm
        k1p = -(-self.k // (self.block_k1 or 256)) * (self.block_k1 or 256)
        ff1 = -(-self.ff // (self.block_n1 or 128)) * (self.block_n1 or 128)
        k2p = -(-self.ff // (self.block_k2 or 256)) * (self.block_k2 or 256)
        n2p = -(-self.n // (self.block_n2 or 128)) * (self.block_n2 or 128)
        w_up = (k1p // K_PER_WORD) * ff1 * 4
        w_down = (k2p // K_PER_WORD) * n2p * 4
        fused_bytes = float(
            mp * k1p * 2                      # x: once per M tile
            + m_tiles * (n_up * w_up + w_down)  # weights streamed per tile
            + mp * n2p * 2)                   # final output write
        nf1 = ff1 // (self.block_n1 or 128)
        nf2 = n2p // (self.block_n2 or 128)
        t_fused = (fused_bytes / autotune_lib.HBM_BW
                   + m_tiles * (nf1 + nf2) * 1e-6)
        tuner = autotune_lib.Autotuner()
        t_unfused = n_up * tuner._model_score(
            autotune_lib.BlockConfig(bm, self.block_n1 or 128,
                                     self.block_k1 or 256),
            self.m, self.k, self.ff, 1.0) \
            + tuner._model_score(
                autotune_lib.BlockConfig(bm, self.block_n2 or 128,
                                         self.block_k2 or 256),
                self.m, self.ff, self.n, 1.0)
        flops = 2.0 * self.m * self.ff * (n_up * self.k + self.n)
        ai = flops / max(fused_bytes, 1.0)
        ceiling = min(autotune_lib.PEAK_FLOPS, ai * autotune_lib.HBM_BW)
        achieved = flops / max(t_fused, 1e-12)
        coll = (2.0 * (self.tp - 1) / self.tp * self.m * self.n * 4
                if self.collective == "psum" and self.tp > 1 else 0.0)
        return {"flops": flops,
                "bytes": fused_bytes,
                "unfused_bytes": float(unfused_bytes),
                "collective": self.collective,
                "collective_bytes": coll,
                "tp": self.tp,
                "arithmetic_intensity": ai,
                "ceiling_flops": ceiling,
                "achieved_flops": achieved,
                "peak_flops": autotune_lib.PEAK_FLOPS,
                "model_time_s": t_fused,
                "unfused_model_time_s": t_unfused,
                "fused_speedup": t_unfused / max(t_fused, 1e-12),
                "headroom": max(0.0, 1.0 - achieved / max(ceiling, 1.0)),
                "bound": ("memory" if ceiling < autotune_lib.PEAK_FLOPS
                          else "compute")}


@dataclasses.dataclass(frozen=True)
class FusedImpl:
    """One registered fused-MLP lowering."""

    impl: str
    priority: int
    predicate: Callable[..., bool]
    fn: Callable


_FUSED: Dict[str, FusedImpl] = {}


def register_fused(impl: str, *, priority: int = 0,
                   predicate: Optional[Callable] = None):
    """Decorator registering a fused-MLP lowering under ``impl``.
    ``predicate(w_in, w_out, w_gate, m, phase)`` gates ``impl="auto"``
    selection (highest admissible priority wins)."""

    def deco(fn):
        _FUSED[impl] = FusedImpl(
            impl=impl, priority=priority,
            predicate=predicate or (lambda *a: True), fn=fn)
        return fn

    return deco


def fused_registry() -> Dict[str, FusedImpl]:
    """Snapshot of the registered fused-MLP impl table."""
    return dict(_FUSED)


def _fusable(w_in, w_out, w_gate, m, phase) -> bool:
    for w in (w_in, w_out) + (() if w_gate is None else (w_gate,)):
        if w.format_name not in _FUSED_FORMATS:
            return False
        if getattr(jnp.asarray(w.packed), "ndim", 2) != 2:
            return False
    if w_gate is not None:
        # the gate rides the up projection's strips: same shape required,
        # and its own chain plan must resolve the same K/N tiles
        if (w_gate.k, w_gate.n) != (w_in.k, w_in.n):
            return False
        up = ternary_gemm_plan(w_in, m, phase=phase)
        gate = ternary_gemm_plan(w_gate, m, phase=phase)
        if (up.block_n, up.block_k) != (gate.block_n, gate.block_k):
            return False
    return True


def fused_mlp_plan(w_in: Any, w_out: Any, w_gate: Any = None, *,
                   m: int, impl: str = "auto", activation: str = "silu",
                   phase: Optional[str] = "__current__",
                   interpret: Optional[bool] = None,
                   tp: int = 1) -> FusedMlpPlan:
    """Plan (but do not run) a fused MLP block; the fused analogue of
    ``ternary_gemm_plan``. Blocks resolve through the autotuner's fused
    key (``autotune.fused_cache_key``) pinned to the chain sub-plans'
    tiles, so fused and unfused tiling always agree. ``tp > 1`` plans one
    Megatron-MLP shard: the hidden dim is column split on the way up, row
    split on the way down, with an explicit trailing ``psum``."""
    w_in = _coerce_weight(w_in, None, None)
    w_out = _coerce_weight(w_out, None, None)
    if w_gate is not None:
        w_gate = _coerce_weight(w_gate, None, None)
    if w_out.k != w_in.n:
        raise ValueError(
            f"fused_mlp: down projection expects K={w_in.n} (the up "
            f"projection's N) but encodes K={w_out.k}")
    if w_gate is not None and (w_gate.k, w_gate.n) != (w_in.k, w_in.n):
        raise ValueError(
            f"fused_mlp: gate shape {(w_gate.k, w_gate.n)} must match the "
            f"up projection's {(w_in.k, w_in.n)}")
    assert activation in ACTIVATIONS, activation
    if tp < 1:
        raise ValueError(f"tp must be >= 1, got {tp}")
    if tp > 1:
        for which, wgt, dim in (("up N", w_in, "n"), ("down K", w_out, "k")):
            extent, multiple = wgt.shard_constraints()[dim]
            if extent % (tp * multiple) != 0:
                raise ValueError(
                    f"fused_mlp: {tp}-way TP splits the {which} axis every "
                    f"{extent / tp:g} of {extent} values — off the "
                    f"{multiple}-value pack multiple of {wgt.format_name}")
    ff_shard = w_in.n // tp
    if phase == "__current__":
        phase = current_phase()
    interpret = _auto_interpret() if interpret is None else interpret

    if impl == "auto":
        cands = sorted(_FUSED.values(), key=lambda fi: -fi.priority)
        if not cands:
            raise ValueError("no fused-MLP lowerings registered")
        chosen = next((fi for fi in cands
                       if fi.predicate(w_in, w_out, w_gate, m, phase)),
                      cands[-1])
    else:
        chosen = _FUSED.get(impl)
        if chosen is None:
            raise ValueError(f"no fused-MLP impl {impl!r} registered; "
                             f"available: {sorted(_FUSED)}")

    bm = bn1 = bk1 = bn2 = bk2 = None
    if chosen.impl == "pallas":
        up = ternary_gemm_plan(w_in, m, phase=phase, interpret=interpret,
                               partition="n" if tp > 1 else None, tp=tp)
        down = ternary_gemm_plan(w_out, m, phase=phase, interpret=interpret,
                                 partition="k" if tp > 1 else None, tp=tp)
        cfg = autotune_lib.get_tuner().lookup_fused(
            m, w_in.k, ff_shard, w_out.n,
            sparsity_up=w_in.occupancy(), sparsity_down=w_out.occupancy(),
            fixed_n1=up.block_n, fixed_k1=up.block_k,
            fixed_n2=down.block_n, fixed_k2=down.block_k, phase=phase)
        bm, bn1, bk1 = cfg.block_m, cfg.block_n1, cfg.block_k1
        bn2, bk2 = cfg.block_n2, cfg.block_k2
    return FusedMlpPlan(
        impl=chosen.impl, format_up=w_in.format_name,
        format_down=w_out.format_name, m=m, k=w_in.k, ff=ff_shard,
        n=w_out.n, gated=w_gate is not None, activation=activation,
        block_m=bm, block_n1=bn1, block_k1=bk1, block_n2=bn2,
        block_k2=bk2, phase=phase, occupancy_up=w_in.occupancy(),
        occupancy_down=w_out.occupancy(), interpret=interpret,
        collective="psum" if tp > 1 else None, tp=tp)


@functools.partial(jax.custom_vjp,
                   nondiff_argnums=(10, 11, 12, 13, 14, 15, 16, 17, 18))
def _fused_2bit(x, wi_p, wo_p, wg_p, si, bi, sg, bg, so, bo,
                n, ff, bm, bn1, bk1, bn2, bk2, activation, interpret):
    return fused_mlp_pallas(
        x, wi_p, wo_p, wg_p, scale_i=si, bias_i=bi, scale_g=sg, bias_g=bg,
        scale_o=so, bias_o=bo, n=n, ff=ff, block_m=bm, block_n1=bn1,
        block_k1=bk1, block_n2=bn2, block_k2=bk2, activation=activation,
        interpret=interpret)


def _fused_2bit_fwd(x, wi_p, wo_p, wg_p, si, bi, sg, bg, so, bo, *static):
    y = _fused_2bit(x, wi_p, wo_p, wg_p, si, bi, sg, bg, so, bo, *static)
    return y, (x, wi_p, wo_p, wg_p, si, bi, sg, bg, so, bo)


def _fused_2bit_bwd(n, ff, bm, bn1, bk1, bn2, bk2, activation, interpret,
                    res, g):
    # Differentiate a reference chain over the decoded weights: packed
    # operands are non-differentiable (same contract as _gemm_2bit_bwd),
    # everything else routes through jax.vjp of the float chain.
    x, wi_p, wo_p, wg_p, si, bi, sg, bg, so, bo = res
    k = x.shape[1]
    ti = formats.decode_2bit(wi_p, k, dtype=x.dtype)[:, :ff]
    to = formats.decode_2bit(wo_p, ff, dtype=x.dtype)[:, :n]
    tg = (None if wg_p is None
          else formats.decode_2bit(wg_p, k, dtype=x.dtype)[:, :ff])

    def epi(y, s, b):
        if s is not None:
            y = y * s.reshape(1, -1).astype(y.dtype)
        if b is not None:
            y = y + b.reshape(1, -1).astype(y.dtype)
        return y

    def chain(d):
        yi = epi(jnp.dot(d["x"], ti, preferred_element_type=jnp.float32),
                 d.get("si"), d.get("bi"))
        if tg is not None:
            yg = epi(jnp.dot(d["x"], tg,
                             preferred_element_type=jnp.float32),
                     d.get("sg"), d.get("bg"))
            h = _act(activation, yg) * yi
        else:
            h = _act(activation, yi)
        h = h.astype(x.dtype)
        return epi(jnp.dot(h, to, preferred_element_type=jnp.float32),
                   d.get("so"), d.get("bo")).astype(x.dtype)

    diff = {"x": x}
    for name, v in (("si", si), ("bi", bi), ("sg", sg), ("bg", bg),
                    ("so", so), ("bo", bo)):
        if v is not None:
            diff[name] = v
    _, vjp = jax.vjp(chain, diff)
    (gd,) = vjp(g)
    return (gd["x"], jnp.zeros_like(wi_p), jnp.zeros_like(wo_p),
            None if wg_p is None else jnp.zeros_like(wg_p),
            gd.get("si"), gd.get("bi"), gd.get("sg"), gd.get("bg"),
            gd.get("so"), gd.get("bo"))


_fused_2bit.defvjp(_fused_2bit_fwd, _fused_2bit_bwd)


@register_fused("pallas", priority=10, predicate=_fusable)
def _lower_fused_pallas(plan, x, w_in, w_out, w_gate):
    wi = jnp.asarray(w_in.packed)[:, :w_in.n]
    wo = jnp.asarray(w_out.packed)[:, :w_out.n]
    wg = None if w_gate is None else jnp.asarray(w_gate.packed)[:, :w_gate.n]
    return _fused_2bit(
        x, wi, wo, wg, w_in.scale, w_in.bias,
        None if w_gate is None else w_gate.scale,
        None if w_gate is None else w_gate.bias,
        w_out.scale, w_out.bias,
        plan.n, plan.ff, plan.block_m, plan.block_n1, plan.block_k1,
        plan.block_n2, plan.block_k2, plan.activation, plan.interpret)


@register_fused("chain", priority=0)
def _lower_fused_chain(plan, x, w_in, w_out, w_gate):
    # The literal unfused chain: the bitwise-equality oracle for the fused
    # kernel, and the fallback for formats it does not cover (bitplane,
    # base3, stacked leaves). Each GEMM dispatches through the normal
    # registry, so this is exactly what mlp_apply did before fusion.
    yi = ternary_gemm(x, w_in, interpret=plan.interpret)
    if w_gate is not None:
        yg = ternary_gemm(x, w_gate, interpret=plan.interpret)
        h = _act(plan.activation, yg) * yi
    else:
        h = _act(plan.activation, yi)
    return ternary_gemm(h, w_out, interpret=plan.interpret)


def fused_mlp(x: jnp.ndarray, w_in: Any, w_out: Any, w_gate: Any = None,
              *, activation: str = "silu", impl: str = "auto",
              interpret: Optional[bool] = None) -> jnp.ndarray:
    """Fused ternary MLP block: ``act(x @ Wg) * (x @ Wi) @ Wo`` (gate
    optional; ``act(x @ Wi) @ Wo`` without it), scale/bias taken from each
    container's own metadata.

    ``impl="pallas"`` keeps the hidden activation in VMEM for the whole
    block; ``impl="chain"`` is the unfused call chain; ``"auto"`` picks
    the best admissible lowering — both produce bitwise-identical outputs,
    so the choice is purely a bandwidth decision (see
    ``FusedMlpPlan.roofline``)."""
    if x.ndim != 2:
        raise ValueError(f"fused_mlp expects 2-D x, got {x.shape}; "
                         f"reshape leading dims into M first")
    plan = fused_mlp_plan(w_in, w_out, w_gate, m=x.shape[0], impl=impl,
                          activation=activation, interpret=interpret)
    w_in = _coerce_weight(w_in, None, None)
    w_out = _coerce_weight(w_out, None, None)
    if w_gate is not None:
        w_gate = _coerce_weight(w_gate, None, None)
    if x.shape[1] != w_in.k:
        raise ValueError(f"x has K={x.shape[1]} but the up projection "
                         f"encodes K={w_in.k}")
    mesh = current_tp_mesh()
    if mesh is not None and plan.impl == "pallas" and w_in.tp_dim == "n" \
            and w_out.tp_dim == "k" \
            and (w_gate is None or w_gate.tp_dim == "n"):
        return _tp_fused(mesh, x, w_in, w_out, w_gate,
                         activation=activation, interpret=plan.interpret)
    return _FUSED[plan.impl].fn(plan, x, w_in, w_out, w_gate)


def _tp_fused(mesh, x, w_in, w_out, w_gate, *, activation, interpret):
    """The Megatron MLP shard-locally: up/gate column split and down row
    split over ``"model"`` keep the hidden activation local, so each shard
    runs the fused kernel on its hidden slice and one f32 ``psum`` (plus
    the down bias, once) finishes the block."""
    ntp = dict(mesh.shape)["model"]
    vec = P("model")
    args, in_specs, locals_ = {"x": x}, {"x": P()}, {}
    for name, w in (("in", w_in), ("out", w_out), ("gate", w_gate)):
        if w is None:
            continue
        leaves, specs = _shard_leaves(w, w.tp_dim)
        args[name], in_specs[name] = dict(leaves), dict(specs)
        for v in ("scale", "bias"):
            if getattr(w, v) is not None and not (name == "out"
                                                  and v == "bias"):
                args[name][v] = getattr(w, v)
                in_specs[name][v] = vec if name != "out" else P()
        locals_[name] = _shard_view(w, w.tp_dim, ntp).replace(
            scale=None, bias=None, tp_dim=None,
            nnz=w.nnz // ntp if w.nnz >= 0 else -1)

    def shard(a):
        ws = {name: locals_[name].replace(**a[name]) for name in locals_}
        y = fused_mlp(a["x"], ws["in"], ws["out"], ws.get("gate"),
                      activation=activation, impl="pallas",
                      interpret=interpret)
        return jax.lax.psum(y.astype(jnp.float32), "model").astype(y.dtype)

    y = jax.shard_map(shard, mesh=mesh, in_specs=(in_specs,), out_specs=P(),
                      check_vma=False)(args)
    if w_out.bias is not None:
        y = y + w_out.bias.reshape(1, -1).astype(y.dtype)
    return y


def precompute_fused_plans(params, *, prefill_ms=(), decode_ms=(),
                           verify_ms=(), chunk_ms=(), impl: str = "auto",
                           tp: int = 1,
                           ) -> Dict[Tuple[int, ...], FusedMlpPlan]:
    """Warm phase-keyed *fused* plans for MLP-shaped subtrees: any dict
    with packed ``"in"``/``"out"`` (and optionally ``"gate"``) linears.
    The fused analogue of ``precompute_plans`` — the serving engine calls
    both at build time so no hot-loop dispatch pays a first-call tune.

    Scan-stacked containers ((L, K/16, N) leaves) plan through their
    layer-0 slice: inside the scan each step sees the 2-D per-layer view,
    and that — not the stacked tree — is what dispatch keys on."""
    found = []

    def _container(node):
        if isinstance(node, dict):
            w = node.get("w_packed")
            if isinstance(w, weights.TernaryWeight):
                words = getattr(w, "packed", getattr(w, "plus", None))
                if words is not None and words.ndim == 3:
                    return jax.tree_util.tree_map(lambda a: a[0], w)
                return w
        return None

    def walk(node):
        if isinstance(node, dict):
            wi, wo = _container(node.get("in")), _container(node.get("out"))
            if wi is not None and wo is not None and wo.k == wi.n:
                found.append((wi, wo, _container(node.get("gate"))))
            for v in node.values():
                walk(v)
        elif isinstance(node, (list, tuple)):
            for v in node:
                walk(v)

    walk(params)
    plans: Dict[Tuple[int, ...], FusedMlpPlan] = {}
    for i, (wi, wo, wg) in enumerate(found):
        for phase, ms in (("prefill", prefill_ms), ("decode", decode_ms),
                          ("verify", verify_ms), ("chunk", chunk_ms)):
            for m in ms:
                plans[(i, m, phase)] = fused_mlp_plan(
                    wi, wo, wg, m=m, impl=impl, phase=phase, tp=tp)
    return plans


# ---------------------------------------------------------------------------
# The public op
# ---------------------------------------------------------------------------

def _shard_leaves(w: weights.TernaryWeight, dim: str):
    """(packed leaves, their PartitionSpecs) of a TP-placed container: the
    (K-packed, N) word/plane arrays split on K (``dim="k"``) or N."""
    if w.format_name not in ("dense2bit", "bitplane"):
        raise NotImplementedError(
            f"no per-shard rule for TP-placed {w.format_name} weights")
    names = [f for f in w._leaves if f not in ("scale", "bias")
             and getattr(w, f) is not None]
    spec = P("model", None) if dim == "k" else P(None, "model")
    return {f: getattr(w, f) for f in names}, {f: spec for f in names}


def _tp_gemm(mesh, x, w, scale, bias, *, impl, fuse_prelu, prelu_alpha,
             interpret):
    """One TP-placed GEMM, run shard-locally (DESIGN.md §13). Column split
    (``tp_dim="n"``): replicated x, local columns out, no collective. Row
    split (``"k"``): K-sharded x, f32 ``psum`` of the partial products,
    then bias and PReLU once on the reduced output."""
    ntp = dict(mesh.shape)["model"]
    col = w.tp_dim == "n"
    leaves, specs = _shard_leaves(w, w.tp_dim)
    local = _shard_view(w, w.tp_dim, ntp).replace(
        scale=None, bias=None, tp_dim=None,
        nnz=w.nnz // ntp if w.nnz >= 0 else -1)
    vec = P("model") if col else P()
    args = {"x": x, "w": leaves}
    in_specs = {"x": P() if col else P(None, "model"), "w": specs}
    if scale is not None:
        args["scale"], in_specs["scale"] = scale, vec
    if col and bias is not None:
        args["bias"], in_specs["bias"] = bias, vec

    def shard(a):
        y = ternary_gemm(a["x"], local.replace(**a["w"]), a.get("scale"),
                         a.get("bias"), impl=impl,
                         fuse_prelu=fuse_prelu and col,
                         prelu_alpha=prelu_alpha, interpret=interpret)
        if col:
            return y
        return jax.lax.psum(y.astype(jnp.float32), "model").astype(y.dtype)

    y = jax.shard_map(shard, mesh=mesh, in_specs=(in_specs,),
                      out_specs=P(None, "model") if col else P(),
                      check_vma=False)(args)
    if not col:
        if bias is not None:
            y = y + bias.reshape(1, -1).astype(y.dtype)
        if fuse_prelu:
            y = jnp.where(y >= 0, y, jnp.asarray(prelu_alpha, y.dtype) * y)
    return y


def ternary_gemm(
    x: jnp.ndarray,
    w: Any,
    scale: Optional[jnp.ndarray] = None,
    bias: Optional[jnp.ndarray] = None,
    k: Optional[int] = None,
    block_m: Optional[int] = None,
    block_n: Optional[int] = None,
    block_k: Optional[int] = None,
    fuse_prelu: bool = False,
    prelu_alpha: float = 0.25,
    interpret: Optional[bool] = None,
    impl: str = "auto",
) -> jnp.ndarray:
    """Y = X @ decode(w) * scale + bias (+PReLU). Any (M, K, N).

    ``w`` is a ``repro.core.weights.TernaryWeight`` (raw operands raise
    ``TypeError`` — pack via ``weights.pack``); ``scale``/``bias`` default
    to the container's own metadata. ``impl`` selects a registered
    lowering explicitly ("auto" plans by format/occupancy/phase — see
    module docstring); ``block_*`` left ``None`` consult the autotuner.
    ``k`` is redundant with the container and validated against it.
    """
    w = _coerce_weight(w, k, x.shape[1])
    _validate_k(w, x.shape[1], k)
    scale = w.scale if scale is None else scale
    bias = w.bias if bias is None else bias
    plan = ternary_gemm_plan(
        w, x.shape[0], impl=impl, block_m=block_m, block_n=block_n,
        block_k=block_k, fuse_prelu=fuse_prelu, prelu_alpha=prelu_alpha,
        interpret=interpret)
    mesh = current_tp_mesh()
    if mesh is not None and w.tp_dim is not None and plan.impl != "ref":
        return _tp_gemm(mesh, x, w, scale, bias, impl=plan.impl,
                        fuse_prelu=fuse_prelu, prelu_alpha=prelu_alpha,
                        interpret=plan.interpret)
    return _KERNELS[(plan.format, plan.impl)].lower(plan, x, w, scale, bias)
