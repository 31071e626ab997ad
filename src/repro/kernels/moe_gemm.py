"""Grouped ternary expert GEMM: rows sorted by held expert, one expert's
2-bit bank per group (the MoE layer's gate, up and down projections).

Layout (built by ``models.moe``): the (row, held expert) pairs a layer
computes are sorted by expert, and each expert's group is padded to a
whole number of ``block_m``-row tiles, so no tile holds two experts. Tile
``t`` of the padded rows belongs to expert ``tile_group[t]``; the first
``n_active`` tiles hold rows, the rest of the static bound are spare.

The Pallas kernel walks a (N tile, row tile) grid. The row tiles of one
expert are consecutive, so an expert's (K/16, block_n) word block is
fetched once per N tile and only if the expert has rows: an expert with no
rows owns no tile. Spare tiles skip their compute and point every block at
the last active tile's, so they fetch nothing and write nothing. Inside a
step the K axis is walked in ``block_k`` chunks, each decoded from its
words in VMEM (``ternary_gemm._decode_tile``) and multiplied on the MXU
into an f32 accumulator; the per-channel scale is applied once at the end.

``moe_expert_gemm_xla`` is the same product in plain XLA over the same
layout (every held bank decoded, one masked matmul per expert), for the
CPU. The ``pallas_call`` is named ``moe_expert_gemm``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.ternary_gemm import K_PER_WORD, _decode_tile

__all__ = ["moe_expert_gemm_pallas", "moe_expert_gemm_xla", "pick_block_n"]


def pick_block_n(n: int) -> int:
    """The widest N tile of at most 1024 lanes that divides ``n``."""
    for bn in (1024, 512, 256, 128):
        if n % bn == 0:
            return bn
    return n


@functools.partial(jax.jit, static_argnames=("block_m", "block_n",
                                             "block_k", "interpret"))
def moe_expert_gemm_pallas(x, words, scale, tile_group, tile_row, n_active,
                           *, block_m: int, block_n: int = 0,
                           block_k: int = 512, interpret: bool = False):
    """x (M, K) rows in tiles of ``block_m``; words (E, K/16, N) uint32;
    scale (E, N); tile_group, tile_row (M/block_m,) int32: the expert of
    each tile and the tile each grid step reads (spare tiles repeat the
    last active one); n_active (1,) int32. Returns (M, N) in x's dtype,
    rows of spare tiles unwritten."""
    m, k = x.shape
    e, kw, n = words.shape
    assert kw * K_PER_WORD == k and m % block_m == 0, (x.shape, words.shape)
    bn = block_n or pick_block_n(n)
    bk = next((b for b in (block_k, 256, 128) if k % b == 0), k)
    assert n % bn == 0, (n, bn)
    n_tiles = m // block_m
    bkw = bk // K_PER_WORD

    def kernel(group_ref, row_ref, active_ref, x_ref, w_ref, s_ref, o_ref):
        t = pl.program_id(1)

        @pl.when(t < active_ref[0])
        def _body():
            acc = jnp.zeros((block_m, bn), jnp.float32)
            for c in range(k // bk):
                w = _decode_tile(w_ref[0, c * bkw:(c + 1) * bkw, :],
                                 x_ref.dtype)
                acc += jnp.dot(x_ref[:, c * bk:(c + 1) * bk], w,
                               preferred_element_type=jnp.float32)
            o_ref[...] = (acc * s_ref[0].astype(jnp.float32)
                          ).astype(o_ref.dtype)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(n // bn, n_tiles),
        in_specs=[
            pl.BlockSpec((block_m, k), lambda j, t, g, r, a: (r[t], 0)),
            pl.BlockSpec((1, kw, bn), lambda j, t, g, r, a: (g[t], 0, j)),
            pl.BlockSpec((1, 1, bn), lambda j, t, g, r, a: (g[t], 0, j)),
        ],
        out_specs=pl.BlockSpec((block_m, bn),
                               lambda j, t, g, r, a: (r[t], j)),
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m, n), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=64 * 1024 * 1024),
        name="moe_expert_gemm",
        interpret=interpret,
    )(tile_group, tile_row, n_active, x, words,
      scale.reshape(e, 1, n))


def moe_expert_gemm_xla(x, words, scale, tile_group, tile_row, n_active,
                        *, block_m: int):
    """The same product as ``moe_expert_gemm_pallas`` in plain XLA: each
    held bank decoded to ±1/0 and applied to its own tiles' rows (spare
    tiles' rows come out zero)."""
    from repro.core import formats
    del tile_row
    m, k = x.shape
    e = words.shape[0]
    row_group = jnp.repeat(tile_group, block_m)
    live = jnp.repeat(jnp.arange(m // block_m) < n_active[0], block_m)
    out = jnp.zeros((m, words.shape[2]), jnp.float32)
    for g in range(e):
        t = formats.decode_2bit(words[g], k).astype(x.dtype)
        y = jnp.dot(x, t, preferred_element_type=jnp.float32) \
            * scale[g].astype(jnp.float32)
        out = jnp.where(((row_group == g) & live)[:, None], y, out)
    return out.astype(x.dtype)
