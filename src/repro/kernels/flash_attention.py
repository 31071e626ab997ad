"""Pallas TPU flash attention kernel.

§Perf cell B showed the XLA-level blockwise attention pays
O(B·H·S²/bkv) HBM bytes in accumulator/score round-trips; this kernel keeps
the running (m, l, acc) statistics in VMEM scratch across the KV grid steps,
so HBM traffic is just Q/K/V reads + O writes — the memory-roofline floor.

Layout: q/k/v as (BH, S, hd) (batch*heads flattened; GQA callers repeat or
reshape K/V). Grid (BH, nq, nkv) with the KV dimension innermost
("arbitrary" semantics → sequential accumulation). Causal masking skips
fully-masked KV blocks via @pl.when (no dot issued for them).

Checked in interpret mode against the naive oracle on CPU
(tests/test_flash_kernel.py); it runs compiled only on a TPU, and only
where ``attn_impl="pallas"`` selects it. `ops`-style jit wrapper below.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30

__all__ = ["flash_attention_pallas"]


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            scale: float, causal: bool, nkv: int, block_q: int,
            block_kv: int, seq_kv: int):
    iq = pl.program_id(1)
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # causal: skip KV blocks strictly after this Q block's last row
    run = True
    if causal:
        run = ik * block_kv <= (iq + 1) * block_q - 1

    @pl.when(run)
    def _step():
        q = q_ref[0]                                   # (bq, hd)
        k = k_ref[0]                                   # (bkv, hd)
        v = v_ref[0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # (bq, bkv)
        q_pos = iq * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_kv), 0)
        k_pos = ik * block_kv + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_kv), 1)
        mask = k_pos < seq_kv
        if causal:
            mask &= q_pos >= k_pos
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(ik == nkv - 1)
    def _epilogue():
        o_ref[0] = (acc_ref[...]
                    / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "block_q", "block_kv", "interpret"))
def flash_attention_pallas(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                           *, causal: bool = True, block_q: int = 512,
                           block_kv: int = 512,
                           interpret: bool = False) -> jnp.ndarray:
    """q, k, v: (BH, S, hd) -> (BH, S, hd). S padded internally."""
    bh, sq, hd = q.shape
    _, skv, _ = k.shape
    bq = min(block_q, sq)
    bkv = min(block_kv, skv)
    pq, pkv = (-sq) % bq, (-skv) % bkv
    if pq:
        q = jnp.pad(q, ((0, 0), (0, pq), (0, 0)))
    if pkv:
        k = jnp.pad(k, ((0, 0), (0, pkv), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pkv), (0, 0)))
    nq = (sq + pq) // bq
    nkv = (skv + pkv) // bkv
    scale = 1.0 / (hd ** 0.5)

    out = pl.pallas_call(
        functools.partial(_kernel, scale=scale, causal=causal, nkv=nkv,
                          block_q=bq, block_kv=bkv, seq_kv=skv),
        grid=(bh, nq, nkv),
        in_specs=[
            pl.BlockSpec((1, bq, hd), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bkv, hd), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bkv, hd), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, hd), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sq + pq, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, hd), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        name="flash_attention",
        interpret=interpret,
    )(q, k, v)
    return out[:, :sq]
