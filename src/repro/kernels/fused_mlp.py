"""Fused ternary MLP block: GEMM -> bias -> activation -> GEMM, one kernel.

The unfused chain (``models.layers.mlp_apply``) round-trips the hidden
activation ``h`` through HBM between the up- and down-projection — at 2-bit
weight density that (m, ff) tensor is the *dominant* memory traffic of the
block ("Above the Inner Loop", PAPERS.md: on bandwidth-bound hardware the
win above the inner loop is keeping operands resident across chained
GEMMs). This kernel keeps ``h`` in a VMEM scratch buffer for the lifetime
of one M-tile:

    grid = (M / block_m,)                       # one program per row tile
    x tile     : (block_m, K)   VMEM block      # reused by gate AND up proj
    weights    : HBM (memory_space=ANY), streamed per N-strip with
                 double-buffered ``make_async_copy`` (next strip's DMA
                 overlaps the current strip's decode + MXU work)
    h scratch  : (block_m, FF)  VMEM, never leaves the chip
    output     : (block_m, N)   written strip by strip

Bitwise equality with the unfused chain is a hard invariant (pinned in
tests/test_fused_mlp.py). It holds because every float op matches the
chain exactly: the same (block_k x block_n) decode tiles in the same
ascending-K order feed the same f32-accumulating ``jnp.dot``s, the
epilogue (scale -> bias, f32) and the cast to x.dtype happen per strip
exactly as the dense kernel's epilogue does, and the activation is the
same ``jax.nn.silu`` applied to the same x.dtype value. M-tiling is free:
XLA's dot is row-stable bitwise, so the fused block_m need not match the
chain's (K-tiling is NOT free, hence the matched block_k).

Gated (SwiGLU, ``h = silu(x@Wg) * (x@Wi)``) and ungated
(``h = act(x@Wi)``) variants share the kernel; the gate weight is simply
a second streamed operand.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.ternary_gemm import K_PER_WORD, _decode_tile

__all__ = ["fused_mlp_pallas", "ACTIVATIONS"]

ACTIVATIONS = ("silu", "relu", "none")


def _act(name: str, y: jnp.ndarray) -> jnp.ndarray:
    if name == "silu":
        # the sigmoid runs in f32 and rounds once to y's dtype, as XLA
        # computes a bf16 ``jax.nn.silu`` (Mosaic has no bf16 logistic)
        return y * jax.nn.sigmoid(y.astype(jnp.float32)).astype(y.dtype)
    if name == "relu":
        return jax.nn.relu(y)
    assert name == "none", name
    return y


def _round_up(v: int, mult: int) -> int:
    return -(-v // mult) * mult


def _pad2(a: jnp.ndarray, rows: int, cols: int) -> jnp.ndarray:
    return jnp.pad(a, ((0, rows - a.shape[0]), (0, cols - a.shape[1])))


def _fused_body(x_ref, wg_hbm, wi_hbm, wo_hbm, sg_ref, bg_ref, si_ref,
                bi_ref, so_ref, bo_ref, o_ref, wg_s, wi_s, wo_s, sem1,
                sem2, h_ref, *, bm, bn1, bk1, bn2, bk2, nf1, nk1, nf2, nk2,
                activation):
    """One M-tile: up (+gate) projection strip pipeline into ``h_ref``,
    activation, then down projection strip pipeline into ``o_ref``."""
    bkw1 = bk1 // K_PER_WORD
    bkw2 = bk2 // K_PER_WORD
    gated = wg_hbm is not None
    dt = x_ref.dtype

    # Columns the up-projection strips never touch (bn1/bk2 misalignment
    # padding) must read as the chain's zero padding in the down proj.
    h_ref[...] = jnp.zeros_like(h_ref)

    # --- stage 1: h[:, j*bn1:(j+1)*bn1] strips, double-buffered weights ---

    def up_dmas(slot, j):
        dmas = [pltpu.make_async_copy(
            wi_hbm.at[:, pl.ds(j * bn1, bn1)], wi_s.at[slot],
            sem1.at[slot, 0])]
        if gated:
            dmas.append(pltpu.make_async_copy(
                wg_hbm.at[:, pl.ds(j * bn1, bn1)], wg_s.at[slot],
                sem1.at[slot, 1]))
        return dmas

    for dma in up_dmas(0, 0):
        dma.start()

    def up_strip(j, _):
        cur = jax.lax.rem(j, 2)

        @pl.when(j + 1 < nf1)
        def _prefetch():
            for dma in up_dmas(jax.lax.rem(j + 1, 2), j + 1):
                dma.start()

        for dma in up_dmas(cur, j):
            dma.wait()

        def ktile(t, accs):
            xt = x_ref[:, pl.ds(t * bk1, bk1)]
            acc_i, acc_g = accs
            ti = _decode_tile(wi_s[cur, pl.ds(t * bkw1, bkw1)], dt)
            acc_i = acc_i + jnp.dot(xt, ti,
                                    preferred_element_type=jnp.float32)
            if gated:
                tg = _decode_tile(wg_s[cur, pl.ds(t * bkw1, bkw1)], dt)
                acc_g = acc_g + jnp.dot(xt, tg,
                                        preferred_element_type=jnp.float32)
            return acc_i, acc_g

        zero = jnp.zeros((bm, bn1), jnp.float32)
        acc_i, acc_g = jax.lax.fori_loop(0, nk1, ktile, (zero, zero))

        def epilogue(acc, s_ref, b_ref):
            y = acc
            if s_ref is not None:
                y = y * s_ref[:, pl.ds(j * bn1, bn1)].astype(jnp.float32)
            if b_ref is not None:
                y = y + b_ref[:, pl.ds(j * bn1, bn1)].astype(jnp.float32)
            return y.astype(dt)

        yi = epilogue(acc_i, si_ref, bi_ref)
        if gated:
            h = _act(activation, epilogue(acc_g, sg_ref, bg_ref)) * yi
        else:
            h = _act(activation, yi)
        h_ref[:, pl.ds(j * bn1, bn1)] = h
        return 0

    jax.lax.fori_loop(0, nf1, up_strip, 0)

    # --- stage 2: o[:, j*bn2:(j+1)*bn2] strips over the resident h ---

    def down_dma(slot, j):
        return pltpu.make_async_copy(
            wo_hbm.at[:, pl.ds(j * bn2, bn2)], wo_s.at[slot],
            sem2.at[slot])

    down_dma(0, 0).start()

    def down_strip(j, _):
        cur = jax.lax.rem(j, 2)

        @pl.when(j + 1 < nf2)
        def _prefetch():
            down_dma(jax.lax.rem(j + 1, 2), j + 1).start()

        down_dma(cur, j).wait()

        def ktile(t, acc):
            ht = h_ref[:, pl.ds(t * bk2, bk2)]
            to = _decode_tile(wo_s[cur, pl.ds(t * bkw2, bkw2)], dt)
            return acc + jnp.dot(ht, to,
                                 preferred_element_type=jnp.float32)

        acc = jax.lax.fori_loop(0, nk2, ktile,
                                jnp.zeros((bm, bn2), jnp.float32))
        y = acc
        if so_ref is not None:
            y = y * so_ref[:, pl.ds(j * bn2, bn2)].astype(jnp.float32)
        if bo_ref is not None:
            y = y + bo_ref[:, pl.ds(j * bn2, bn2)].astype(jnp.float32)
        o_ref[:, pl.ds(j * bn2, bn2)] = y.astype(o_ref.dtype)
        return 0

    jax.lax.fori_loop(0, nf2, down_strip, 0)


@functools.partial(
    jax.jit,
    static_argnames=("n", "ff", "block_m", "block_n1", "block_k1",
                     "block_n2", "block_k2", "activation", "interpret"),
)
def fused_mlp_pallas(
    x: jnp.ndarray,                     # (M, K) f32/bf16
    wi_packed: jnp.ndarray,             # (K/16, >=ff) uint32: up proj
    wo_packed: jnp.ndarray,             # (ff/16, >=n) uint32: down proj
    wg_packed: Optional[jnp.ndarray] = None,   # (K/16, >=ff): gate proj
    scale_i: Optional[jnp.ndarray] = None,
    bias_i: Optional[jnp.ndarray] = None,
    scale_g: Optional[jnp.ndarray] = None,
    bias_g: Optional[jnp.ndarray] = None,
    scale_o: Optional[jnp.ndarray] = None,
    bias_o: Optional[jnp.ndarray] = None,
    *,
    n: int,
    ff: int,
    block_m: int = 128,
    block_n1: int = 128,
    block_k1: int = 256,
    block_n2: int = 128,
    block_k2: int = 256,
    activation: str = "silu",
    interpret: bool = False,
) -> jnp.ndarray:
    """Fused ``act(x @ Wg) * (x @ Wi) @ Wo`` (gate optional) — see module
    docstring. Returns the (M, n) logical output; ``h`` never leaves VMEM.

    ``block_n1/block_k1`` tile the up/gate projections, ``block_n2/
    block_k2`` the down projection — pass the same blocks the unfused
    chain's plans resolve to and the result is bitwise identical to the
    two/three-call chain.
    """
    assert activation in ACTIVATIONS, activation
    m, k = x.shape
    assert wi_packed.shape[0] * K_PER_WORD >= k
    if wg_packed is not None:
        assert wg_packed.shape == wi_packed.shape, \
            (wg_packed.shape, wi_packed.shape)

    bm = min(block_m, max(8, 1 << (m - 1).bit_length()))
    mp = _round_up(m, bm)

    # Stage-1 K: the packed operand's word rows, padded to the K tile.
    k1p = _round_up(wi_packed.shape[0] * K_PER_WORD, block_k1)
    ff1 = _round_up(ff, block_n1)
    # Stage-2 K: ff padded exactly as the chain pads h (words, then tile) —
    # matching tile counts keeps the accumulation order identical.
    k2p = _round_up(_round_up(ff, K_PER_WORD), block_k2)
    n2p = _round_up(n, block_n2)
    hw = max(ff1, k2p)                  # h scratch width covers both views

    xp = _pad2(x, mp, k1p)
    wi_p = _pad2(wi_packed[:, :ff], k1p // K_PER_WORD, ff1)
    wo_p = _pad2(wo_packed[:, :n], k2p // K_PER_WORD, n2p)

    operands = [wi_p, wo_p]
    in_specs = [pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY)]
    if wg_packed is not None:
        operands.append(_pad2(wg_packed[:, :ff], k1p // K_PER_WORD, ff1))
        in_specs.append(pl.BlockSpec(memory_space=pl.ANY))

    def vec(v, width):
        return _pad2(v.reshape(1, -1), 1, width)

    epilogues = []                      # (present, width) in kernel order
    for v, width in ((scale_g, ff1), (bias_g, ff1), (scale_i, ff1),
                     (bias_i, ff1), (scale_o, n2p), (bias_o, n2p)):
        epilogues.append(v is not None)
        if v is not None:
            operands.append(vec(v, width))
            in_specs.append(pl.BlockSpec((1, width), lambda i: (0, 0)))

    nf1, nk1 = ff1 // block_n1, k1p // block_k1
    nf2, nk2 = n2p // block_n2, k2p // block_k2
    gated = wg_packed is not None

    def kernel(*refs):
        it = iter(refs)
        x_ref = next(it)
        wi_hbm, wo_hbm = next(it), next(it)
        wg_hbm = next(it) if gated else None
        eps = [next(it) if present else None for present in epilogues]
        o_ref = next(it)
        wg_s = next(it) if gated else None
        wi_s, wo_s, sem1, sem2, h_ref = it
        _fused_body(x_ref, wg_hbm, wi_hbm, wo_hbm, eps[0], eps[1], eps[2],
                    eps[3], eps[4], eps[5], o_ref, wg_s, wi_s, wo_s, sem1,
                    sem2, h_ref, bm=bm, bn1=block_n1, bk1=block_k1,
                    bn2=block_n2, bk2=block_k2, nf1=nf1, nk1=nk1, nf2=nf2,
                    nk2=nk2, activation=activation)

    scratch = []
    if gated:
        scratch.append(pltpu.VMEM((2, k1p // K_PER_WORD, block_n1),
                                  jnp.uint32))
    scratch += [
        pltpu.VMEM((2, k1p // K_PER_WORD, block_n1), jnp.uint32),  # wi
        pltpu.VMEM((2, k2p // K_PER_WORD, block_n2), jnp.uint32),  # wo
        pltpu.SemaphoreType.DMA((2, 2 if gated else 1)),
        pltpu.SemaphoreType.DMA((2,)),
        pltpu.VMEM((bm, hw), x.dtype),                             # h
    ]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=0,
        grid=(mp // bm,),
        in_specs=[pl.BlockSpec((bm, k1p), lambda i: (i, 0))] + in_specs,
        out_specs=pl.BlockSpec((bm, n2p), lambda i: (i, 0)),
        scratch_shapes=scratch,
    )
    y = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((mp, n2p), x.dtype),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel",)),
        name="fused_mlp",
        interpret=interpret,
    )(xp, *operands)
    return y[:m, :n]
