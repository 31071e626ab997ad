"""Paged decode attention: Pallas kernels + references (DESIGN.md §9).

Decode attention where K/V live in fixed-size *pages* owned by a global
pool and each batch row reads its own sequence through a block table
(``block_table[b, t]`` = page id of the t-th page of row ``b``).

Two forms: one query token a row (decode), and a window of S tokens a
row (chunked prefill, speculative verify; ``kernels.ops.
paged_window_attention``), where token j of a row attends one more key
than token j-1. Registered lowerings (``kernels.ops.register_paged_attn``):

* ``jax`` — batched page gather + exactly the dense decode's attention
  math (the einsum/mask/softmax lines mirror
  ``models.attention.naive_attention`` with ``causal=False``). Because the
  ops match the dense path line-for-line, a paged serving run is
  **bit-identical** in logits to the dense-cache run — this is what the
  paged-vs-dense token-exactness guarantee rests on, and it is the
  ``impl="auto"`` choice off-TPU.
* ``pallas`` — ``PrefetchScalarGridSpec`` kernel: the block table and
  per-row lengths ride in as scalar-prefetch operands so the page grid
  dimension's BlockSpec index maps DMA exactly the pages the row owns
  (same steering mechanism as the tile-skipping GEMM, DESIGN.md §3).
  Pages stream through an online softmax: running max, sum and output
  accumulator live in ``(heads, hd)`` f32 VMEM scratch, so VMEM use does
  not grow with ``max_len``. Pages past a row's length re-point the DMA
  at its last valid page (no new copy) and skip their compute. Its window
  form (``paged_window_attention_pallas``, ``pallas_call`` name
  ``paged_decode_attention_window``) takes the (B, T) block table as is
  and walks each row's pages once for all S tokens: a grid of (row,
  query tile, page), each KV head's query heads and window tokens one
  (S·g, hd) matrix against a (ps, hd) page. A lowering with no window
  form (``jax``) runs a window as (B·S) single-query rows, the block
  table repeated S times.
* the pure-JAX **reference** (``paged_decode_attention_ref``) runs the
  same ``_page_step`` over the same pages in the same order, so
  kernel-vs-reference comparisons are bitwise, not approximate.

All three accept bf16 page arrays or ``quant.Int8Pages`` containers
(per-page scales dequantized after the gather — inside the kernel for the
Pallas path, so HBM reads stay int8), either one layer's
``(P, ps, KV, hd)`` pages or the model's stacked ``(G, P, ps, KV, hd)``
pool with ``layer`` naming the group to read (``kernels.ops.page_stack``
views one layer's pages as ``G = 1``). The Pallas kernels take the layer
as one more scalar-prefetch operand and their page index maps lead with
it, so a layer's pages are DMA'd straight out of the stacked pool and no
copy of the layer is taken; the XLA lowerings index the layer themselves.
"""
from __future__ import annotations

import functools
import math
from typing import Optional, Union

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.ops import page_stack, register_paged_attn
from repro.paging.quant import Int8Pages, dequantize_rows

NEG_INF = -1e30

__all__ = ["paged_decode_attention_pallas", "paged_decode_attention_ref",
           "paged_decode_attention_jax", "paged_window_attention_pallas"]

Pages = Union[jnp.ndarray, Int8Pages]


def _page_geometry(pages: Pages):
    """(n_pages, page_size, kv_heads, head_dim) of a stacked page operand."""
    shape = pages.codes.shape if isinstance(pages, Int8Pages) else pages.shape
    assert len(shape) == 5, f"expected (G, P, ps, KV, hd) pages, got {shape}"
    return shape[1:]


def _page_step(q, k, v, m, l, acc, *, kv_heads: int, pos0, length,
               window: int):
    """One page of the online-softmax decode attention, f32 throughout.

    q (H, hd); k/v (ps, KV, hd) one page; m/l/acc (H, hd) the running max,
    sum (both lane-replicated over hd) and output accumulator; ``pos0`` is
    the page's first token position and ``length`` the row's valid-token
    count (the current token sits at ``length - 1``). Shared verbatim by
    the Pallas kernel body and the pure-JAX reference so the two are
    bit-exact by construction. Masked keys contribute exact zeros, so a
    page with no valid key leaves (m, l, acc) unchanged."""
    h, hd = q.shape
    ps = k.shape[0]
    g = h // kv_heads
    s = jnp.einsum("kgd,skd->kgs", q.reshape(kv_heads, g, hd), k,
                   preferred_element_type=jnp.float32).reshape(h, ps) \
        * (1.0 / math.sqrt(hd))
    k_pos = pos0 + jax.lax.broadcasted_iota(jnp.int32, (h, ps), 1)
    valid = k_pos < length
    if window:
        valid &= (length - 1 - k_pos) < window
    s = jnp.where(valid, s, NEG_INF)
    m_prev, l_prev = m[:, :1], l[:, :1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
    l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
    pv = jnp.einsum("kgs,skd->kgd", p.reshape(kv_heads, g, ps), v,
                    preferred_element_type=jnp.float32).reshape(h, hd)
    return (jnp.broadcast_to(m_new, (h, hd)),
            jnp.broadcast_to(l_new, (h, hd)), alpha * acc + pv)


def _page_live(t, ps: int, first, last, window: int):
    """Whether page ``t`` of a row holds any key one of its queries
    attends; the queries' valid-token counts run from ``first`` to
    ``last`` (equal for a decode row)."""
    live = t * ps < last
    if window:
        live &= (t + 1) * ps > first - window
    return live


def _load_page(pages, dtype=jnp.float32):
    """One (ps, KV, hd) page (or its Int8 codes + scales) as f32."""
    if isinstance(pages, tuple):
        return dequantize_rows(pages[0], pages[1], dtype)
    return pages.astype(dtype)


def _gather(pages: Pages, layer, block_table: jnp.ndarray,
            dtype) -> jnp.ndarray:
    """(B, T) block table -> (B, T*ps, KV, hd) gathered sequence view of
    group ``layer`` of the stacked pages. int8 pages dequantize to
    ``dtype``; raw pages keep their storage dtype (it already equals the
    dense cache dtype, which the bit-exactness contract with the dense
    path requires)."""
    if isinstance(pages, Int8Pages):
        codes = pages.codes[layer, block_table]   # (B, T, ps, KV, hd)
        scales = pages.scales[layer, block_table]  # (B, T, ps, KV)
        seq = dequantize_rows(codes, scales, dtype)
    else:
        seq = pages[layer, block_table]
    b, t, ps, kv, hd = seq.shape
    return seq.reshape(b, t * ps, kv, hd)


# ---------------------------------------------------------------------------
# Pure-JAX lowerings
# ---------------------------------------------------------------------------

@register_paged_attn("jax", priority=10)
def paged_decode_attention_jax(q, k_pages: Pages, v_pages: Pages,
                               block_table, lengths, *, layer=0,
                               window: int = 0,
                               interpret: Optional[bool] = None):
    """Gather + dense-identical attention (see module docstring).

    q (B, H, hd); returns (B, H, hd). The einsum/mask/softmax sequence
    below MUST stay line-identical to ``models.attention.naive_attention``
    (causal=False) — tests/test_paging.py pins the bitwise equality."""
    del interpret
    b, h, hd = q.shape
    ks = _gather(page_stack(k_pages), layer, block_table, q.dtype)
    vs = _gather(page_stack(v_pages), layer, block_table, q.dtype)
    kvh = ks.shape[2]
    qg = q.reshape(b, 1, kvh, h // kvh, hd)
    s = jnp.einsum("bqkgd,bskd->bkgqs", qg, ks,
                   preferred_element_type=jnp.float32) \
        * (1.0 / math.sqrt(hd))
    lengths = jnp.asarray(lengths)
    k_pos = jnp.arange(ks.shape[1])
    mask = jnp.ones((b, 1, ks.shape[1]), bool)
    if window:
        q_pos = (lengths - 1)[:, None, None]
        mask &= q_pos - k_pos < window
    mask = mask & (k_pos < lengths[:, None, None])
    s = jnp.where(mask[:, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgqs,bskd->bqkgd", p.astype(vs.dtype), vs,
                   preferred_element_type=jnp.float32)
    return o.reshape(b, 1, h, hd)[:, 0].astype(q.dtype)


@functools.partial(jax.jit, static_argnames=("window",))
def paged_decode_attention_ref(q, k_pages: Pages, v_pages: Pages,
                               block_table, lengths, *, layer=0,
                               window: int = 0):
    """Bit-exact mirror of the Pallas kernel: per row, the same
    ``_page_step`` over the row's pages in block-table order, compiled as
    one program like the interpret-mode kernel (op-by-op dispatch rounds
    differently). Reference only — the B·T loop unrolls into the trace."""
    b, h, hd = q.shape
    k_pages, v_pages = page_stack(k_pages), page_stack(v_pages)
    _, ps, kv, _ = _page_geometry(k_pages)

    def page(pages, pid):
        if isinstance(pages, Int8Pages):
            return _load_page((pages.codes[layer, pid],
                               pages.scales[layer, pid]))
        return _load_page(pages[layer, pid])

    outs = []
    for i in range(b):
        qi = q[i].astype(jnp.float32)
        m = jnp.full((h, hd), NEG_INF, jnp.float32)
        l = jnp.zeros((h, hd), jnp.float32)
        acc = jnp.zeros((h, hd), jnp.float32)
        length = jnp.asarray(lengths[i], jnp.int32)
        for t in range(block_table.shape[1]):
            pid = block_table[i, t]
            m, l, acc = _page_step(
                qi, page(k_pages, pid), page(v_pages, pid), m, l, acc,
                kv_heads=kv, pos0=jnp.int32(t * ps), length=length,
                window=window)
        outs.append((acc / l).astype(q.dtype))
    return jnp.stack(outs)


# ---------------------------------------------------------------------------
# Pallas kernel
# ---------------------------------------------------------------------------

def _layer_operand(layer) -> jnp.ndarray:
    """The layer index as the (1,) int32 scalar-prefetch operand that the
    page index maps lead with."""
    return jnp.reshape(jnp.asarray(layer, jnp.int32), (1,))


def _kernel(bt_ref, len_ref, _layer_ref, q_ref, *refs, page_size: int,
            kv_heads: int, window: int, quantized: bool):
    b = pl.program_id(0)
    t = pl.program_id(1)
    if quantized:
        kc_ref, ks_ref, vc_ref, vs_ref = refs[:4]
        o_ref, m_scr, l_scr, acc_scr = refs[4:]
    else:
        k_ref, v_ref = refs[:2]
        o_ref, m_scr, l_scr, acc_scr = refs[2:]
    length = len_ref[b]

    @pl.when(t == 0)
    def _init():
        m_scr[...] = jnp.full(m_scr.shape, NEG_INF, jnp.float32)
        l_scr[...] = jnp.zeros(l_scr.shape, jnp.float32)
        acc_scr[...] = jnp.zeros(acc_scr.shape, jnp.float32)

    @pl.when(_page_live(t, page_size, length, length, window))
    def _step():
        if quantized:
            k = _load_page((kc_ref[0], ks_ref[0]))
            v = _load_page((vc_ref[0], vs_ref[0]))
        else:
            k, v = _load_page(k_ref[0]), _load_page(v_ref[0])
        m, l, acc = _page_step(
            q_ref[0].astype(jnp.float32), k, v, m_scr[...], l_scr[...],
            acc_scr[...], kv_heads=kv_heads, pos0=t * page_size,
            length=length, window=window)
        m_scr[...] = m
        l_scr[...] = l
        acc_scr[...] = acc

    @pl.when(t == pl.num_programs(1) - 1)
    def _finish():
        o_ref[0] = (acc_scr[...] / l_scr[...]).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("window", "interpret"))
def paged_decode_attention_pallas(q, k_pages: Pages, v_pages: Pages,
                                  block_table, lengths, *, layer=0,
                                  window: int = 0,
                                  interpret: Optional[bool] = None):
    """q (B, H, hd); pages (G, P, ps, KV, hd) (or ``Int8Pages``; one
    layer's (P, ps, KV, hd) reads as G = 1), of which group ``layer`` is
    read; block_table (B, T) int32 (pad unused entries with any valid page
    id, e.g. 0 — their keys are masked out by ``lengths``); lengths (B,)
    int32 valid-token counts including the current token. Returns
    (B, H, hd)."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    b, h, hd = q.shape
    k_pages, v_pages = page_stack(k_pages), page_stack(v_pages)
    _, ps, kv, _ = _page_geometry(k_pages)
    t = block_table.shape[1]
    quantized = isinstance(k_pages, Int8Pages)

    def page_index(i, j, bt, ln):
        # pages past the row's last valid one repeat that page's block
        # index, so the pipeline issues no new DMA for them
        return bt[i, jnp.minimum(j, jnp.maximum(ln[i] - 1, 0) // ps)]

    page_spec = pl.BlockSpec(
        (None, 1, ps, kv, hd),
        lambda i, j, bt, ln, ly: (ly[0], page_index(i, j, bt, ln), 0, 0, 0))
    scale_spec = pl.BlockSpec(
        (None, 1, ps, kv),
        lambda i, j, bt, ln, ly: (ly[0], page_index(i, j, bt, ln), 0, 0))
    in_specs = [pl.BlockSpec((1, h, hd),
                             lambda i, j, bt, ln, ly: (i, 0, 0))]
    if quantized:
        in_specs += [page_spec, scale_spec, page_spec, scale_spec]
        operands = [q, k_pages.codes, k_pages.scales,
                    v_pages.codes, v_pages.scales]
    else:
        in_specs += [page_spec, page_spec]
        operands = [q, k_pages, v_pages]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b, t),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, h, hd),
                               lambda i, j, bt, ln, ly: (i, 0, 0)),
        scratch_shapes=[pltpu.VMEM((h, hd), jnp.float32)] * 3,
    )
    return pl.pallas_call(
        functools.partial(_kernel, page_size=ps, kv_heads=kv, window=window,
                          quantized=quantized),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        name="paged_decode_attention",
        interpret=interpret,
    )(jnp.asarray(block_table, jnp.int32), jnp.asarray(lengths, jnp.int32),
      _layer_operand(layer), *operands)


# ---------------------------------------------------------------------------
# Pallas window kernel: S query tokens per row, one grid step per page
# ---------------------------------------------------------------------------

# VMEM a window tile may take for what grows with its query rows: the f32
# running max, sum and accumulator, the double-buffered q and output
# blocks, the scores and the compiler's temporaries. The v5e compiler
# needs about 32 f32 (KV, 128-lane) slabs a row (1,024 rows at
# Mistral-NeMo widths, 8 KV heads of 128, take 130 MB), so the budget
# keeps a tile well inside the chip's 128 MiB of VMEM.
WINDOW_VMEM_BUDGET = 40 * 2 ** 20


def _window_page_step(q, k, v, m, l, acc, *, group: int, pos0, first,
                      window: int):
    """One page of the window's online softmax, f32 throughout.

    q (KV, R, hd): row ``r`` of KV head ``c`` is query head ``c*group +
    r % group`` of window token ``r // group``; k/v (ps, KV, hd) one page;
    m/l/acc (KV, R, hd) as in ``_page_step``. Token ``j`` of the tile has
    ``first + j`` valid tokens (it sits at position ``first + j - 1``).
    The per-row arithmetic is ``_page_step``'s, so a masked page leaves a
    row's (m, l, acc) unchanged."""
    kvh, r, hd = q.shape
    ps = k.shape[0]
    s = jnp.einsum("krd,skd->krs", q, k,
                   preferred_element_type=jnp.float32) \
        * (1.0 / math.sqrt(hd))
    row = jax.lax.broadcasted_iota(jnp.int32, (r, ps), 0)
    k_pos = pos0 + jax.lax.broadcasted_iota(jnp.int32, (r, ps), 1)
    # k_pos < first + row // group, without an integer division
    valid = (k_pos - first + 1) * group <= row
    if window:
        # first + row // group - 1 - k_pos < window
        valid &= row < (window + k_pos - first + 1) * group
    valid = jnp.broadcast_to(valid[None], s.shape)
    s = jnp.where(valid, s, NEG_INF)
    m_prev, l_prev = m[..., :1], l[..., :1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
    l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
    pv = jnp.einsum("krs,skd->krd", p, v,
                    preferred_element_type=jnp.float32)
    return (jnp.broadcast_to(m_new, (kvh, r, hd)),
            jnp.broadcast_to(l_new, (kvh, r, hd)), alpha * acc + pv)


def _window_kernel(bt_ref, len_ref, _layer_ref, q_ref, *refs,
                   page_size: int, group: int, window: int,
                   quantized: bool):
    b = pl.program_id(0)
    t = pl.program_id(2)
    if quantized:
        kc_ref, ks_ref, vc_ref, vs_ref = refs[:4]
        o_ref, m_scr, l_scr, acc_scr = refs[4:]
    else:
        k_ref, v_ref = refs[:2]
        o_ref, m_scr, l_scr, acc_scr = refs[2:]
    tokens = q_ref.shape[2] // group
    first = len_ref[b] + pl.program_id(1) * tokens

    @pl.when(t == 0)
    def _init():
        m_scr[...] = jnp.full(m_scr.shape, NEG_INF, jnp.float32)
        l_scr[...] = jnp.zeros(l_scr.shape, jnp.float32)
        acc_scr[...] = jnp.zeros(acc_scr.shape, jnp.float32)

    @pl.when(_page_live(t, page_size, first, first + tokens - 1, window))
    def _step():
        if quantized:
            k = _load_page((kc_ref[0], ks_ref[0]))
            v = _load_page((vc_ref[0], vs_ref[0]))
        else:
            k, v = _load_page(k_ref[0]), _load_page(v_ref[0])
        m, l, acc = _window_page_step(
            q_ref[0].astype(jnp.float32), k, v, m_scr[...], l_scr[...],
            acc_scr[...], group=group, pos0=t * page_size, first=first,
            window=window)
        m_scr[...] = m
        l_scr[...] = l
        acc_scr[...] = acc

    @pl.when(t == pl.num_programs(2) - 1)
    def _finish():
        o_ref[0] = (acc_scr[...] / l_scr[...]).astype(o_ref.dtype)


def _window_tile(s: int, group: int, kv: int, hd: int) -> int:
    """Window tokens per query tile: the most that keep a tile within
    ``WINDOW_VMEM_BUDGET``, among divisors of ``s`` whose rows fill whole
    (16, 128) tiles (or all of ``s``)."""
    per_row = 32 * 4 * kv * max(hd, 128)
    fits = [n for n in range(1, s + 1)
            if s % n == 0 and (n == s or n * group % 16 == 0)]
    within = [n for n in fits if n * group * per_row <= WINDOW_VMEM_BUDGET]
    return max(within) if within else min(fits)


@functools.partial(jax.jit, static_argnames=("window", "interpret"))
def paged_window_attention_pallas(q, k_pages: Pages, v_pages: Pages,
                                  block_table, lengths, *, layer=0,
                                  window: int = 0,
                                  interpret: Optional[bool] = None):
    """Attention of an S-token window per row over its own pages.

    q (B, S, H, hd); pages, ``layer`` and block_table (B, T) as in
    ``paged_decode_attention_pallas``; lengths (B,) int32 valid-token
    count of each row's first window token: token ``j`` attends the keys
    at positions ``< lengths + j``. Returns (B, S, H, hd), equal per token
    to the decode kernel over the (B·S) rows that flattening the window
    would give, with one grid step per (row, query tile, page) instead of
    one per (token, page)."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    b, s, h, hd = q.shape
    k_pages, v_pages = page_stack(k_pages), page_stack(v_pages)
    _, ps, kv, _ = _page_geometry(k_pages)
    g = h // kv
    t = block_table.shape[1]
    quantized = isinstance(k_pages, Int8Pages)
    tile = _window_tile(s, g, kv, hd)
    rows = tile * g
    # (B, KV, S·g, hd): each KV head's query heads and window tokens are
    # the rows of one matrix against a (ps, hd) page
    qw = q.reshape(b, s, kv, g, hd).transpose(0, 2, 1, 3, 4) \
        .reshape(b, kv, s * g, hd)

    def page_index(i, qi, j, bt, ln):
        # pages past the tile's last valid one repeat that page's block
        # index, so the pipeline issues no new DMA for them
        last = jnp.maximum(ln[i] + (qi + 1) * tile - 2, 0) // ps
        return bt[i, jnp.minimum(j, last)]

    page_spec = pl.BlockSpec(
        (None, 1, ps, kv, hd),
        lambda i, qi, j, bt, ln, ly: (ly[0], page_index(i, qi, j, bt, ln),
                                      0, 0, 0))
    scale_spec = pl.BlockSpec(
        (None, 1, ps, kv),
        lambda i, qi, j, bt, ln, ly: (ly[0], page_index(i, qi, j, bt, ln),
                                      0, 0))
    q_spec = pl.BlockSpec((1, kv, rows, hd),
                          lambda i, qi, j, bt, ln, ly: (i, 0, qi, 0))
    if quantized:
        in_specs = [q_spec, page_spec, scale_spec, page_spec, scale_spec]
        operands = [qw, k_pages.codes, k_pages.scales,
                    v_pages.codes, v_pages.scales]
    else:
        in_specs = [q_spec, page_spec, page_spec]
        operands = [qw, k_pages, v_pages]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b, s // tile, t),
        in_specs=in_specs,
        out_specs=q_spec,
        scratch_shapes=[pltpu.VMEM((kv, rows, hd), jnp.float32)] * 3,
    )
    out = pl.pallas_call(
        functools.partial(_window_kernel, page_size=ps, group=g,
                          window=window, quantized=quantized),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(qw.shape, q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        name="paged_decode_attention_window",
        interpret=interpret,
    )(jnp.asarray(block_table, jnp.int32), jnp.asarray(lengths, jnp.int32),
      _layer_operand(layer), *operands)
    return out.reshape(b, kv, s, g, hd).transpose(0, 2, 1, 3, 4) \
        .reshape(b, s, h, hd)


# registered lowering: the kernels want explicit interpret resolution
register_paged_attn(
    "pallas", priority=20,
    predicate=lambda: jax.default_backend() == "tpu",
    window=paged_window_attention_pallas,
)(paged_decode_attention_pallas)
