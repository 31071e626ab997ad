"""Pallas TPU kernel: packed 2-bit sparse-ternary GEMM with fused epilogue.

TPU adaptation of the paper's kernel (see DESIGN.md §2). The mapping:

* paper's BlockedTCSC B-window  -> BlockSpec K-tiling: each grid step loads a
  (block_k/16, block_n) packed-word tile + a (block_m, block_k) X tile into
  VMEM, so every access the kernel makes is VMEM-resident (the paper's
  "confine irregular accesses to a cache window", except on TPU we remove the
  irregularity altogether and the window is the VMEM tile).
* paper's structural sign encoding -> 2-bit codes (0,+1,-1) decoded with pure
  VPU bit ops: v = (c & 1) - (c >> 1). One pass, no ± branches -- the
  interleaving insight expressed as data-parallel arithmetic.
* paper's multi-accumulator unrolling -> f32 VMEM scratch accumulator carried
  across the K grid dimension, MXU `jnp.dot(..., preferred_element_type=f32)`.
* paper's symmetric SIMD padding -> zero-padding K/N to tile multiples
  (code 0 decodes to 0.0 and contributes exactly nothing).
* paper's fused PReLU (vectorized kernels) -> fused scale+bias+PReLU epilogue
  on the last K step.

Weight bandwidth is 2 bits/element = 16x less than f32 (8x less than bf16):
on a memory-bound GEMM (the paper's own diagnosis of this workload) that is
the roofline lever on TPU.

Mosaic note: every vector op of the decode is 2-D and 32-bit (the TPU
compiler refuses 3-D gathers and int8 arithmetic there), and the kernels
compile at every block shape the autotuner can pick
(tests/test_tpu_compile.py). On a TPU they run compiled; on other backends
`ops.ternary_gemm` runs them in interpret mode, which the CPU tests use.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

WORD_BITS = 32
K_PER_WORD = WORD_BITS // 2  # 16 ternary weights per uint32 word

__all__ = ["ternary_gemm_pallas", "ternary_gemm_skip_pallas",
           "ternary_gemm_skip_db_pallas", "K_PER_WORD"]


def _expand_rows(words: jnp.ndarray, reps: int) -> jnp.ndarray:
    """(q, bn) int32 -> (q * reps, bn): row ``i`` repeated ``reps`` times.

    Built from 2-D sublane broadcasts and one sublane concatenation, so
    Mosaic never sees a 3-D vector or a gather."""
    q, bn = words.shape
    return jnp.concatenate(
        [jnp.broadcast_to(words[i:i + 1], (reps, bn)) for i in range(q)],
        axis=0)


def _decode_tile(words: jnp.ndarray, out_dtype) -> jnp.ndarray:
    """(bk/16, bn) uint32 -> (bk, bn) ±1/0 tile.

    Tile row ``r`` is codeword ``r % 16`` of word row ``r // 16``; code
    ``c`` decodes to ``(c & 1) - (c >> 1)`` (0, +1, -1, 0). Every vector op
    is 2-D and 32-bit; the one cast to the matmul dtype comes last."""
    rep = _expand_rows(jax.lax.bitcast_convert_type(words, jnp.int32),
                       K_PER_WORD)
    shift = 2 * (jax.lax.broadcasted_iota(jnp.int32, rep.shape, 0)
                 % K_PER_WORD)
    c = jax.lax.shift_right_logical(rep, shift) & 3
    return ((c & 1) - (c >> 1)).astype(jnp.float32).astype(out_dtype)


def _kernel(x_ref, w_ref, scale_ref, bias_ref, o_ref, acc_ref, *,
            nk: int, fuse_prelu: bool, prelu_alpha: float):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    t = _decode_tile(w_ref[...], x_ref.dtype)
    acc_ref[...] += jnp.dot(x_ref[...], t,
                            preferred_element_type=jnp.float32)

    @pl.when(k == nk - 1)
    def _epilogue():
        y = acc_ref[...]
        if scale_ref is not None:
            y = y * scale_ref[...].astype(jnp.float32)
        if bias_ref is not None:
            y = y + bias_ref[...].astype(jnp.float32)
        if fuse_prelu:
            y = jnp.where(y >= 0, y, prelu_alpha * y)
        o_ref[...] = y.astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("block_m", "block_n", "block_k", "fuse_prelu",
                     "prelu_alpha", "interpret"),
)
def ternary_gemm_pallas(
    x: jnp.ndarray,                    # (M, K)  f32/bf16, K % block_k == 0
    w_packed: jnp.ndarray,             # (K / 16, N) uint32 2-bit codes
    scale: Optional[jnp.ndarray] = None,   # (N,) per-channel alpha
    bias: Optional[jnp.ndarray] = None,    # (N,)
    *,
    block_m: int = 128,
    block_n: int = 128,
    block_k: int = 512,
    fuse_prelu: bool = False,
    prelu_alpha: float = 0.25,
    interpret: bool = False,
) -> jnp.ndarray:
    """Y = X @ decode(w_packed) * scale + bias (+ PReLU). Shapes must be
    pre-padded to block multiples -- `ops.ternary_gemm` handles padding."""
    m, k = x.shape
    kw, n = w_packed.shape
    assert kw * K_PER_WORD == k, (kw, k)
    assert m % block_m == 0 and n % block_n == 0 and k % block_k == 0, \
        (m, n, k, block_m, block_n, block_k)
    nk = k // block_k
    bkw = block_k // K_PER_WORD

    in_specs = [
        pl.BlockSpec((block_m, block_k), lambda i, j, kk: (i, kk)),
        pl.BlockSpec((bkw, block_n), lambda i, j, kk: (kk, j)),
    ]
    operands = [x, w_packed]
    if scale is not None:
        in_specs.append(pl.BlockSpec((1, block_n), lambda i, j, kk: (0, j)))
        operands.append(scale.reshape(1, n))
    if bias is not None:
        in_specs.append(pl.BlockSpec((1, block_n), lambda i, j, kk: (0, j)))
        operands.append(bias.reshape(1, n))

    def kernel(*refs):
        x_ref, w_ref = refs[0], refs[1]
        idx = 2
        s_ref = b_ref = None
        if scale is not None:
            s_ref = refs[idx]; idx += 1
        if bias is not None:
            b_ref = refs[idx]; idx += 1
        o_ref, acc_ref = refs[idx], refs[idx + 1]
        _kernel(x_ref, w_ref, s_ref, b_ref, o_ref, acc_ref,
                nk=nk, fuse_prelu=fuse_prelu, prelu_alpha=prelu_alpha)

    return pl.pallas_call(
        kernel,
        grid=(m // block_m, n // block_n, nk),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), x.dtype),
        scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        name="ternary_gemm_dense",
        interpret=interpret,
    )(*operands)


# ---------------------------------------------------------------------------
# Sparsity-adaptive path: skip structurally-empty (block_k x block_n) tiles
# ---------------------------------------------------------------------------

def _skip_kernel(idx_ref, cnt_ref, x_ref, w_ref, scale_ref, bias_ref, o_ref,
                 acc_ref, *, max_occ: int, fuse_prelu: bool,
                 prelu_alpha: float):
    j = pl.program_id(1)
    s = pl.program_id(2)

    @pl.when(s == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # Padded steps (s >= kt_counts[j]) re-point the DMA at a known tile and
    # contribute nothing; the guard keeps the accumulation exactly the sum
    # over occupied tiles in ascending K order.
    @pl.when(s < cnt_ref[j])
    def _body():
        t = _decode_tile(w_ref[...], x_ref.dtype)
        acc_ref[...] += jnp.dot(x_ref[...], t,
                                preferred_element_type=jnp.float32)

    @pl.when(s == max_occ - 1)
    def _epilogue():
        y = acc_ref[...]
        if scale_ref is not None:
            y = y * scale_ref[...].astype(jnp.float32)
        if bias_ref is not None:
            y = y + bias_ref[...].astype(jnp.float32)
        if fuse_prelu:
            y = jnp.where(y >= 0, y, prelu_alpha * y)
        o_ref[...] = y.astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("block_m", "block_n", "block_k", "fuse_prelu",
                     "prelu_alpha", "interpret"),
)
def ternary_gemm_skip_pallas(
    x: jnp.ndarray,                    # (M, K) f32/bf16, pre-padded
    w_packed: jnp.ndarray,             # (K / 16, N) uint32 2-bit codes
    kt_indices: jnp.ndarray,           # (N/block_n, max_occ) int32
    kt_counts: jnp.ndarray,            # (N/block_n,) int32
    scale: Optional[jnp.ndarray] = None,
    bias: Optional[jnp.ndarray] = None,
    *,
    block_m: int = 128,
    block_n: int = 128,
    block_k: int = 256,
    fuse_prelu: bool = False,
    prelu_alpha: float = 0.25,
    interpret: bool = False,
) -> jnp.ndarray:
    """Tile-skipping ternary GEMM (DESIGN.md §3).

    ``kt_indices``/``kt_counts`` are the ``TiledTernary`` occupancy metadata
    (pack-time tile shapes must equal ``block_k``/``block_n``). They ride in
    as scalar-prefetch operands, so the BlockSpec index maps can steer the
    K grid dimension through *occupied* K-tiles only: the grid is
    (M/bm, N/bn, max_occ) instead of (M/bm, N/bn, K/bk) — empty tiles are
    never DMA'd, decoded, or matmul'd. Semantics are exactly the dense
    kernel's (zero tiles contribute exact f32 zeros there).
    """
    m, k = x.shape
    kw, n = w_packed.shape
    assert kw * K_PER_WORD == k, (kw, k)
    assert m % block_m == 0 and n % block_n == 0 and k % block_k == 0, \
        (m, n, k, block_m, block_n, block_k)
    nn = n // block_n
    assert kt_indices.shape[0] == nn and kt_counts.shape == (nn,), \
        (kt_indices.shape, kt_counts.shape, nn)
    max_occ = kt_indices.shape[1]
    bkw = block_k // K_PER_WORD

    in_specs = [
        pl.BlockSpec((block_m, block_k),
                     lambda i, j, s, idx, cnt: (i, idx[j, s])),
        pl.BlockSpec((bkw, block_n),
                     lambda i, j, s, idx, cnt: (idx[j, s], j)),
    ]
    operands = [x, w_packed]
    if scale is not None:
        in_specs.append(pl.BlockSpec((1, block_n),
                                     lambda i, j, s, idx, cnt: (0, j)))
        operands.append(scale.reshape(1, n))
    if bias is not None:
        in_specs.append(pl.BlockSpec((1, block_n),
                                     lambda i, j, s, idx, cnt: (0, j)))
        operands.append(bias.reshape(1, n))

    def kernel(idx_ref, cnt_ref, *refs):
        x_ref, w_ref = refs[0], refs[1]
        pos = 2
        s_ref = b_ref = None
        if scale is not None:
            s_ref = refs[pos]; pos += 1
        if bias is not None:
            b_ref = refs[pos]; pos += 1
        o_ref, acc_ref = refs[pos], refs[pos + 1]
        _skip_kernel(idx_ref, cnt_ref, x_ref, w_ref, s_ref, b_ref, o_ref,
                     acc_ref, max_occ=max_occ, fuse_prelu=fuse_prelu,
                     prelu_alpha=prelu_alpha)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(m // block_m, nn, max_occ),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((block_m, block_n),
                               lambda i, j, s, idx, cnt: (i, j)),
        scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.float32)],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m, n), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        name="ternary_gemm_skip",
        interpret=interpret,
    )(kt_indices, kt_counts, *operands)


# ---------------------------------------------------------------------------
# Double-buffered DMA variant: overlap the next occupied tile's HBM->VMEM
# copy with the current tile's MXU work
# ---------------------------------------------------------------------------

def _skip_db_kernel(idx_ref, cnt_ref, x_hbm, w_hbm, scale_ref, bias_ref,
                    o_ref, xs, ws, sem, acc_ref, *, block_m: int,
                    block_n: int, block_k: int, fuse_prelu: bool,
                    prelu_alpha: float):
    """Grid is (M-tiles, N-tiles); the occupied-K-tile walk happens *inside*
    the kernel as an explicit two-slot ``make_async_copy`` pipeline: while
    tile ``s`` is decoded and matmul'd out of slot ``s % 2``, tile ``s + 1``
    is already in flight into the other slot. x and the packed words stay in
    HBM (``memory_space=ANY``); the kernel only ever touches the VMEM
    staging slots."""
    i = pl.program_id(0)
    j = pl.program_id(1)
    bkw = block_k // K_PER_WORD
    cnt = cnt_ref[j]

    acc_ref[...] = jnp.zeros_like(acc_ref)

    def tile_dma(slot, s):
        """Async copies for occupied tile ``s`` into staging ``slot``:
        the (bm, bk) X window and the (bk/16, bn) packed-word tile."""
        kt = idx_ref[j, s]
        x_dma = pltpu.make_async_copy(
            x_hbm.at[pl.ds(i * block_m, block_m),
                     pl.ds(kt * block_k, block_k)],
            xs.at[slot], sem.at[slot, 0])
        w_dma = pltpu.make_async_copy(
            w_hbm.at[pl.ds(kt * bkw, bkw), pl.ds(j * block_n, block_n)],
            ws.at[slot], sem.at[slot, 1])
        return x_dma, w_dma

    def start(slot, s):
        for dma in tile_dma(slot, s):
            dma.start()

    def wait(slot, s):
        for dma in tile_dma(slot, s):
            dma.wait()

    @pl.when(cnt > 0)
    def _pipeline():
        start(0, 0)                              # warm-up: first tile

        def body(s, _):
            cur = jax.lax.rem(s, 2)

            @pl.when(s + 1 < cnt)
            def _prefetch():                     # overlap: next tile's DMA
                start(jax.lax.rem(s + 1, 2), s + 1)

            wait(cur, s)
            t = _decode_tile(ws[cur], xs.dtype)
            acc_ref[...] += jnp.dot(xs[cur], t,
                                    preferred_element_type=jnp.float32)
            return 0

        jax.lax.fori_loop(0, cnt, body, 0)

    y = acc_ref[...]
    if scale_ref is not None:
        y = y * scale_ref[...].astype(jnp.float32)
    if bias_ref is not None:
        y = y + bias_ref[...].astype(jnp.float32)
    if fuse_prelu:
        y = jnp.where(y >= 0, y, prelu_alpha * y)
    o_ref[...] = y.astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("block_m", "block_n", "block_k", "fuse_prelu",
                     "prelu_alpha", "interpret"),
)
def ternary_gemm_skip_db_pallas(
    x: jnp.ndarray,                    # (M, K) f32/bf16, pre-padded
    w_packed: jnp.ndarray,             # (K / 16, N) uint32 2-bit codes
    kt_indices: jnp.ndarray,           # (N/block_n, max_occ) int32
    kt_counts: jnp.ndarray,            # (N/block_n,) int32
    scale: Optional[jnp.ndarray] = None,
    bias: Optional[jnp.ndarray] = None,
    *,
    block_m: int = 128,
    block_n: int = 128,
    block_k: int = 256,
    fuse_prelu: bool = False,
    prelu_alpha: float = 0.25,
    interpret: bool = False,
) -> jnp.ndarray:
    """Tile-skipping ternary GEMM with an explicit double-buffered DMA
    pipeline (DESIGN.md §12).

    Same operands and semantics as ``ternary_gemm_skip_pallas`` — the
    occupied-tile metadata rides in as scalar prefetch — but instead of the
    implicit per-grid-step BlockSpec pipeline, the grid is only
    (M/bm, N/bn) and each kernel invocation walks its occupied K-tiles with
    two VMEM staging slots: tile ``s+1``'s HBM->VMEM ``make_async_copy``
    issues *before* tile ``s``'s decode + matmul, so DMA overlaps MXU work
    within a single output tile. Accumulation visits occupied tiles in the
    same ascending-K order as the skip kernel, so results are bitwise
    identical to both the skip and dense kernels.
    """
    m, k = x.shape
    kw, n = w_packed.shape
    assert kw * K_PER_WORD == k, (kw, k)
    assert m % block_m == 0 and n % block_n == 0 and k % block_k == 0, \
        (m, n, k, block_m, block_n, block_k)
    nn = n // block_n
    assert kt_indices.shape[0] == nn and kt_counts.shape == (nn,), \
        (kt_indices.shape, kt_counts.shape, nn)
    bkw = block_k // K_PER_WORD

    # x / packed words stay in HBM; only scale/bias (tiny) are block-fed.
    in_specs = [
        pl.BlockSpec(memory_space=pl.ANY),
        pl.BlockSpec(memory_space=pl.ANY),
    ]
    operands = [x, w_packed]
    if scale is not None:
        in_specs.append(pl.BlockSpec((1, block_n),
                                     lambda i, j, idx, cnt: (0, j)))
        operands.append(scale.reshape(1, n))
    if bias is not None:
        in_specs.append(pl.BlockSpec((1, block_n),
                                     lambda i, j, idx, cnt: (0, j)))
        operands.append(bias.reshape(1, n))

    def kernel(idx_ref, cnt_ref, *refs):
        x_hbm, w_hbm = refs[0], refs[1]
        pos = 2
        s_ref = b_ref = None
        if scale is not None:
            s_ref = refs[pos]; pos += 1
        if bias is not None:
            b_ref = refs[pos]; pos += 1
        o_ref = refs[pos]
        xs, ws, sem, acc_ref = refs[pos + 1:pos + 5]
        _skip_db_kernel(idx_ref, cnt_ref, x_hbm, w_hbm, s_ref, b_ref, o_ref,
                        xs, ws, sem, acc_ref, block_m=block_m,
                        block_n=block_n, block_k=block_k,
                        fuse_prelu=fuse_prelu, prelu_alpha=prelu_alpha)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(m // block_m, nn),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((block_m, block_n),
                               lambda i, j, idx, cnt: (i, j)),
        scratch_shapes=[
            pltpu.VMEM((2, block_m, block_k), x.dtype),    # X staging slots
            pltpu.VMEM((2, bkw, block_n), jnp.uint32),     # word staging
            pltpu.SemaphoreType.DMA((2, 2)),               # (slot, x|w)
            pltpu.VMEM((block_m, block_n), jnp.float32),   # accumulator
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m, n), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
        ),
        name="ternary_gemm_skip_db",
        interpret=interpret,
    )(kt_indices, kt_counts, *operands)
