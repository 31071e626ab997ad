"""Pallas ternary GEMM consuming the *bitplane* format — the most literal
TPU translation of the paper's TCSC structural-sign encoding: the sign of a
weight is *which plane* its bit lives in (plus/minus), exactly as TCSC
encodes sign by *which index array* a row id lives in (DESIGN.md §2).

Same grid/accumulation structure as the 2-bit kernel; decode is
``(plus_bit - minus_bit)`` — one subtract per weight, no sign branches (the
paper's interleaving insight as pure data-parallel arithmetic). 2 bits/weight
like the 2-bit codes, but the two planes can also be streamed independently
(e.g. plus-plane-only for unsigned masks).

``factorized=True`` switches to the matmul factorization
``Y = (X @ P) - (X @ M)`` (DESIGN.md §4): each 0/1 plane is bit-expanded and
fed to the MXU as its own binary matmul, and the ternary combine happens
once on the (bm, bn) accumulator instead of per-element on the (bk, bn)
decode — no signed ternary tile is ever materialized.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.ternary_gemm import _expand_rows

K_PER_BYTE = 8

__all__ = ["ternary_gemm_bitplane"]


def _unpack_plane(plane, out_dtype):
    """(bk/8, bn) uint8 plane -> (bk, bn) 0/1 tile (no sign combine).

    Tile row ``r`` is bit ``r % 8`` of byte row ``r // 8``; the bytes widen
    to int32 first, so every vector op is 2-D and 32-bit."""
    rep = _expand_rows(plane.astype(jnp.int32), K_PER_BYTE)
    shift = jax.lax.broadcasted_iota(jnp.int32, rep.shape, 0) % K_PER_BYTE
    return (jax.lax.shift_right_logical(rep, shift) & 1).astype(
        jnp.float32).astype(out_dtype)


def _decode_planes(plus, minus, out_dtype):
    """(bk/8, bn) uint8 planes -> (bk, bn) ±1/0 tile."""
    return (_unpack_plane(plus, jnp.float32)
            - _unpack_plane(minus, jnp.float32)).astype(out_dtype)


def _kernel(x_ref, p_ref, m_ref, scale_ref, o_ref, acc_ref, *, nk: int,
            factorized: bool = False):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    if factorized:
        # Y = (X @ P) - (X @ M): two binary-plane MXU passes, ternary
        # combine deferred to the accumulator (DESIGN.md §4).
        p = _unpack_plane(p_ref[...], x_ref.dtype)
        m = _unpack_plane(m_ref[...], x_ref.dtype)
        acc_ref[...] += (
            jnp.dot(x_ref[...], p, preferred_element_type=jnp.float32)
            - jnp.dot(x_ref[...], m, preferred_element_type=jnp.float32))
    else:
        t = _decode_planes(p_ref[...], m_ref[...], x_ref.dtype)
        acc_ref[...] += jnp.dot(x_ref[...], t,
                                preferred_element_type=jnp.float32)

    @pl.when(k == nk - 1)
    def _epilogue():
        y = acc_ref[...]
        if scale_ref is not None:
            y = y * scale_ref[...].astype(jnp.float32)
        o_ref[...] = y.astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("block_m", "block_n", "block_k", "factorized",
                              "interpret"))
def ternary_gemm_bitplane(
    x: jnp.ndarray,                 # (M, K)
    plus: jnp.ndarray,              # (K/8, N) uint8
    minus: jnp.ndarray,             # (K/8, N) uint8
    scale: Optional[jnp.ndarray] = None,
    *,
    block_m: int = 128,
    block_n: int = 128,
    block_k: int = 512,
    factorized: bool = False,
    interpret: bool = False,
) -> jnp.ndarray:
    m, k = x.shape
    kb, n = plus.shape
    assert kb * K_PER_BYTE == k

    bm = min(block_m, max(8, 1 << (m - 1).bit_length()))
    bn = min(block_n, n)
    bk = min(block_k, k)
    pad = lambda a, i, mult: jnp.pad(
        a, [(0, (-a.shape[d]) % (mult if d == i else 1))
            for d in range(a.ndim)])
    xp = pad(pad(x, 0, bm), 1, bk)
    pp = pad(pad(plus, 0, bk // K_PER_BYTE), 1, bn)
    mp = pad(pad(minus, 0, bk // K_PER_BYTE), 1, bn)
    sp = None if scale is None else pad(scale.reshape(1, -1), 1, bn)
    mm, kk = xp.shape
    nn = pp.shape[1]
    nkk = kk // bk

    in_specs = [
        pl.BlockSpec((bm, bk), lambda i, j, s: (i, s)),
        pl.BlockSpec((bk // K_PER_BYTE, bn), lambda i, j, s: (s, j)),
        pl.BlockSpec((bk // K_PER_BYTE, bn), lambda i, j, s: (s, j)),
    ]
    operands = [xp, pp, mp]
    if sp is not None:
        in_specs.append(pl.BlockSpec((1, bn), lambda i, j, s: (0, j)))
        operands.append(sp)

    def kernel(*refs):
        s_ref = refs[3] if sp is not None else None
        o_ref, acc_ref = refs[-2], refs[-1]
        _kernel(refs[0], refs[1], refs[2], s_ref, o_ref, acc_ref, nk=nkk,
                factorized=factorized)

    y = pl.pallas_call(
        kernel,
        grid=(mm // bm, nn // bn, nkk),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, s: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mm, nn), x.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        name="ternary_gemm_bitplane",
        interpret=interpret,
    )(*operands)
    return y[:m, :n]
