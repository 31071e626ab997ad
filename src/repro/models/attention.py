"""GQA attention: flash (blockwise, lax-native) + naive paths, RoPE, sliding
window, KV-cache decode, and cross-attention for enc-dec models.

All projections are 2-D ``layers.linear`` layers, so the paper's ternary
weight format applies to QKV/O directly. Flash attention is implemented as a
python-unrolled loop over query blocks with a ``lax.scan`` over key blocks
whose *static trip count is shortened* by causality and the sliding window —
i.e. masked-out blocks are genuinely skipped in the HLO, not just masked
(this is what makes SWA sub-quadratic here, and is a §Perf lever).
"""
from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.kernels import ops as kops
from repro.models.layers import (FSDP, MODEL, linear_apply, linear_init,
                                 norm_apply, norm_init, rope)

NEG_INF = -1e30


def attn_init(key, cfg: ModelConfig, cross: bool = False):
    d, kv, hd = cfg.d_model, cfg.num_kv_heads, cfg.head_dim
    h = cfg.num_heads + cfg.head_pad   # §Perf B1: TP-divisible head padding
    ks = jax.random.split(key, 4)
    wq, sq = linear_init(ks[0], cfg, d, h * hd, FSDP, MODEL)
    wk, sk = linear_init(ks[1], cfg, d, kv * hd, FSDP, MODEL)
    wv, sv = linear_init(ks[2], cfg, d, kv * hd, FSDP, MODEL)
    wo, so = linear_init(ks[3], cfg, h * hd, d, MODEL, FSDP)
    params = {"q": wq, "k": wk, "v": wv, "o": wo}
    specs = {"q": sq, "k": sk, "v": sv, "o": so}
    if cfg.qk_norm:
        for name in ("q_norm", "k_norm"):
            params[name], specs[name] = norm_init(None, cfg, hd)
    return params, specs


def _split_heads(x, n, hd):
    return x.reshape(*x.shape[:-1], n, hd)


def _qk_scale(hd):
    return 1.0 / math.sqrt(hd)


# ---------------------------------------------------------------------------
# Blockwise (flash) attention over full sequences — train / prefill
# ---------------------------------------------------------------------------

def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                    causal: bool, window: int, block_q: int, block_kv: int,
                    q_offset: int = 0) -> jnp.ndarray:
    """q: (B, Sq, H, hd); k, v: (B, Skv, KV, hd) with H % KV == 0.
    Returns (B, Sq, H, hd). f32 softmax accumulation."""
    b, sq, h, hd = q.shape
    _, skv, kvh, _ = k.shape
    g = h // kvh
    bq = min(block_q, sq)
    bkv = min(block_kv, skv)
    pad_q = (-sq) % bq
    pad_kv = (-skv) % bkv
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
    if pad_kv:
        k = jnp.pad(k, ((0, 0), (0, pad_kv), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_kv), (0, 0), (0, 0)))
    sq_p, skv_p = sq + pad_q, skv + pad_kv
    nkv = skv_p // bkv
    # (B, nkv, bkv, KV, hd) blocked K/V for scan
    kb = k.reshape(b, nkv, bkv, kvh, hd)
    vb = v.reshape(b, nkv, bkv, kvh, hd)
    scale = _qk_scale(hd)

    outs = []
    for i in range(sq_p // bq):
        q_blk = q[:, i * bq:(i + 1) * bq]                      # (B,bq,H,hd)
        q_blk = q_blk.reshape(b, bq, kvh, g, hd)
        q_lo = q_offset + i * bq
        q_hi = q_lo + bq
        # static KV range this q block can see
        hi_blk = nkv if not causal else min(nkv, -(-min(q_hi, skv) // bkv))
        lo_blk = 0
        if window:
            lo_blk = max(0, (q_lo - window) // bkv)
        hi_blk = max(hi_blk, lo_blk + 1)
        q_pos = q_lo + jnp.arange(bq)

        def step(carry, blk_idx):
            m_prev, l_prev, acc = carry
            # dynamic-index the block from the full blocked K/V (a sliced
            # xs copy per q-block would materialize O(S^2/bq) bytes)
            kc = jax.lax.dynamic_index_in_dim(kb, blk_idx, axis=1,
                                              keepdims=False)
            vc = jax.lax.dynamic_index_in_dim(vb, blk_idx, axis=1,
                                              keepdims=False)
            k_pos = blk_idx * bkv + jnp.arange(bkv)
            # scores: (B, KV, G, bq, bkv), f32
            s = jnp.einsum("bqkgd,bskd->bkgqs", q_blk, kc,
                           preferred_element_type=jnp.float32) * scale
            mask = jnp.ones((bq, bkv), bool)
            if causal:
                mask &= q_pos[:, None] >= k_pos[None, :]
            if window:
                mask &= q_pos[:, None] - k_pos[None, :] < window
            mask &= (k_pos < skv)[None, :]
            s = jnp.where(mask[None, None, None], s, NEG_INF)
            m_cur = jnp.max(s, axis=-1)
            m_new = jnp.maximum(m_prev, m_cur)
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m_prev - m_new)
            l_new = l_prev * corr + jnp.sum(p, axis=-1)
            pv = jnp.einsum("bkgqs,bskd->bkgqd", p.astype(vc.dtype), vc,
                            preferred_element_type=jnp.float32)
            acc = acc * corr[..., None] + pv
            return (m_new, l_new, acc), None

        init = (jnp.full((b, kvh, g, bq), NEG_INF, jnp.float32),
                jnp.zeros((b, kvh, g, bq), jnp.float32),
                jnp.zeros((b, kvh, g, bq, hd), jnp.float32))
        blk_ids = jnp.arange(lo_blk, hi_blk)
        (m_f, l_f, acc), _ = jax.lax.scan(step, init, blk_ids)
        o = acc / jnp.maximum(l_f, 1e-30)[..., None]
        o = o.reshape(b, kvh * g, bq, hd).transpose(0, 2, 1, 3)
        outs.append(o.astype(q.dtype))
    out = jnp.concatenate(outs, axis=1) if len(outs) > 1 else outs[0]
    return out[:, :sq].reshape(b, sq, h, hd)


def naive_attention(q, k, v, *, causal, window, q_offset=0,
                    kv_valid_len=None):
    """Reference full-materialization attention (and the decode path).
    q: (B, Sq, H, hd); k/v: (B, Skv, KV, hd).

    ``q_offset`` / ``kv_valid_len`` may be scalars (classic decode: every
    row at the same position) or (B,) vectors (continuous batching: each
    slot at its own position/valid length)."""
    b, sq, h, hd = q.shape
    _, skv, kvh, _ = k.shape
    g = h // kvh
    qg = q.reshape(b, sq, kvh, g, hd)
    s = jnp.einsum("bqkgd,bskd->bkgqs", qg, k,
                   preferred_element_type=jnp.float32) * _qk_scale(hd)
    q_off = jnp.asarray(q_offset)
    q_pos = q_off[..., None] + jnp.arange(sq)    # (sq,) or (B, sq)
    if q_pos.ndim == 1:
        q_pos = q_pos[None]                      # (1, sq): shared offsets
    k_pos = jnp.arange(skv)
    mask = jnp.ones((1, sq, skv), bool)
    if causal:
        mask &= q_pos[:, :, None] >= k_pos
    if window:
        mask &= q_pos[:, :, None] - k_pos < window
    if kv_valid_len is not None:
        valid = jnp.asarray(kv_valid_len)
        valid = valid[:, None, None] if valid.ndim else valid
        mask = mask & (k_pos < valid)
    s = jnp.where(mask[:, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgqs,bskd->bqkgd", p.astype(v.dtype), v,
                   preferred_element_type=jnp.float32)
    return o.reshape(b, sq, h, hd).astype(q.dtype)


def opt_decode_attention(q, k_cache, v_cache, *, kv_valid_len, window=0,
                         q_offset=0):
    """Decode attention on the transpose-free layouts:
    q (B,1,H,hd); k_cache (B,KV,S,hd); v_cache (B,KV,hd,S). Both dots have
    their contracting dim minor-most — no relayout traffic (§Perf A6)."""
    b, sq, h, hd = q.shape
    kvh, s = k_cache.shape[1], k_cache.shape[2]
    g = h // kvh
    qg = q.reshape(b, sq, kvh, g, hd)
    scores = jnp.einsum("bqkgd,bksd->bkgqs", qg, k_cache,
                        preferred_element_type=jnp.float32) * _qk_scale(hd)
    k_pos = jnp.arange(s)
    mask = k_pos < kv_valid_len
    if window:
        mask &= (q_offset - k_pos) < window
    scores = jnp.where(mask[None, None, None, None], scores, NEG_INF)
    p = jax.nn.softmax(scores, axis=-1)
    o = jnp.einsum("bkgqs,bkds->bqkgd", p.astype(v_cache.dtype), v_cache,
                   preferred_element_type=jnp.float32)
    return o.reshape(b, sq, h, hd).astype(q.dtype)


def delta_decode_attention(q, k_cache, v_cache, k_tok, v_tok, *, cache_pos,
                           rolling: bool, window=0):
    """Decode attention WITHOUT writing the cache in-loop (§Perf A7): attend
    over the stale cache (current token masked out) plus the fresh token's
    self-attention term, concatenated before the softmax — mathematically
    identical to attending over the updated cache. The layer scan then emits
    only (k_tok, v_tok) and one batched DUS outside the loop commits all
    layers' tokens: per-step cache write drops from L x full-layer-slice to
    L x one token.

    q (B,1,H,hd); k_cache (B,KV,S,hd); v_cache (B,KV,hd,S);
    k_tok (B,1,KV,hd); v_tok (B,1,KV,hd).

    ``cache_pos`` may be a scalar (all rows at one position) or a (B,)
    vector (continuous batching: per-slot positions)."""
    b, sq, h, hd = q.shape
    kvh, s = k_cache.shape[1], k_cache.shape[2]
    g = h // kvh
    qg = q.reshape(b, sq, kvh, g, hd)
    scores = jnp.einsum("bqkgd,bksd->bkgqs", qg, k_cache,
                        preferred_element_type=jnp.float32) * _qk_scale(hd)
    cp = jnp.asarray(cache_pos).reshape(-1, 1)   # (1,1) scalar / (B,1) vector
    idx = jnp.arange(s)[None]                    # (1, S)
    if rolling:
        slot = cp % s
        mask = jnp.where(cp >= s, idx != slot, idx < cp)
    else:
        mask = idx < cp
        if window:
            mask &= (cp - idx) < window
    scores = jnp.where(mask[:, None, None, None], scores, NEG_INF)
    self_score = jnp.einsum("bqkgd,bqkd->bkgq", qg, k_tok,
                            preferred_element_type=jnp.float32) \
        * _qk_scale(hd)
    # two-part softmax without concatenating on the (sharded) S axis —
    # concat on a sharded dim forces a GSPMD full regather
    m = jnp.maximum(jnp.max(scores, axis=-1), self_score)   # (B,KV,G,1)
    p_cache = jnp.exp(scores - m[..., None])
    p_self = jnp.exp(self_score - m)
    denom = jnp.sum(p_cache, axis=-1) + p_self              # (B,KV,G,1)
    o = jnp.einsum("bkgqs,bkds->bqkgd", p_cache.astype(v_cache.dtype),
                   v_cache, preferred_element_type=jnp.float32)
    o = o + jnp.einsum("bkgq,bqkd->bqkgd", p_self.astype(q.dtype),
                       v_tok, preferred_element_type=jnp.float32)
    o = o / denom.transpose(0, 3, 1, 2)[..., None]
    return o.reshape(b, sq, h, hd).astype(q.dtype)


# ---------------------------------------------------------------------------
# Full attention layer (projections + rope + attention + output)
# ---------------------------------------------------------------------------

def attn_apply(params, x: jnp.ndarray, cfg: ModelConfig, *,
               positions: jnp.ndarray, causal: bool = True,
               cache: Optional[dict] = None,
               cache_pos: Optional[jnp.ndarray] = None,
               kv_override: Optional[Tuple[jnp.ndarray, jnp.ndarray]] = None,
               block_table: Optional[jnp.ndarray] = None,
               ) -> Tuple[jnp.ndarray, Optional[dict]]:
    """One attention layer.

    * train/prefill: cache=None (or a cache dict to fill at positions 0..S).
    * decode: cache given + cache_pos scalar; x is (B, 1, d).
    * verify window (speculative decoding, DESIGN.md §10): x is (B, S>1, d)
      with cache_pos scalar or (B,) — the S tokens sit at positions
      cache_pos..cache_pos+S-1, their K/V are scattered before attending,
      and causal masking within the window plus the committed prefix makes
      each window token's logits equal to what sequential decode at its
      position would produce. Non-rolling caches only (the caller —
      ``LM.decode_step`` — unrolls rolling-SWA layouts per token instead).
    * paged decode: cache = {"k_pages", "v_pages"} + block_table (B, T) +
      cache_pos (B,) vector (DESIGN.md §9); prefill never sees a paged
      cache — the page pool scatters prefilled dense rows into pages.
      Multi-token windows attend through ``kops.paged_window_attention``.
    * cross-attention: kv_override = (k, v) precomputed from the encoder.
    """
    kv, hd = cfg.num_kv_heads, cfg.head_dim
    h = cfg.num_heads + cfg.head_pad
    q = _split_heads(linear_apply(params["q"], x, cfg), h, hd)
    if "q_norm" in params:
        q = norm_apply(params["q_norm"], q, cfg)
    if kv_override is None:
        k = _split_heads(linear_apply(params["k"], x, cfg), kv, hd)
        v = _split_heads(linear_apply(params["v"], x, cfg), kv, hd)
        if "k_norm" in params:
            k = norm_apply(params["k_norm"], k, cfg)
        if cfg.rope_theta:
            q = rope(q, positions, cfg.rope_theta)
            k = rope(k, positions, cfg.rope_theta)
    else:
        k, v = kv_override
        causal = False

    if cache is not None and "k_pages" in cache:
        assert cache_pos is not None and block_table is not None, (
            "paged caches are decode-only and need a block table")
        y, new_cache = _paged_decode(params, x, cfg, q, k, v, cache,
                                     cache_pos, block_table)
        return y, new_cache

    new_cache = cache
    opt = cache is not None and cfg.cache_layout == "opt"
    attend_view = False   # prefill-into-cache: attend the stored view
    if cache is not None and kv_override is None:
        flat = cache["k"].ndim == 3
        cache_len = cache["k"].shape[2] if opt else cache["k"].shape[1]
        if cache_pos is not None and k.shape[1] > 1:
            # verify window (DESIGN.md §10): scatter all S tokens' K/V at
            # positions cache_pos..cache_pos+S-1 before attending. Rolling
            # SWA caches never reach here (write-then-attend would let a
            # wrapped write clobber an entry an earlier window token still
            # attends to — decode_step unrolls those per token).
            assert not (cfg.sliding_window and cache_len <= cfg.sliding_window)
            assert not opt, "verify windows need cache_layout='bshd'"
            sq = k.shape[1]
            base = cache_pos[:, None] if jnp.ndim(cache_pos) else cache_pos
            slots2d = jnp.broadcast_to(base + jnp.arange(sq),
                                       (k.shape[0], sq))
            rows = jnp.arange(k.shape[0])[:, None]
            k_c = cache["k"].at[rows, slots2d].set(
                _store_view(k, cfg, flat).astype(cache["k"].dtype))
            v_c = cache["v"].at[rows, slots2d].set(
                _store_view(v, cfg, flat).astype(cache["v"].dtype))
            new_cache = {"k": k_c, "v": v_c}
            k, v = _cache_view(k_c, cfg), _cache_view(v_c, cfg)
        elif cache_pos is not None:  # decode: insert this step's K/V
            if cfg.sliding_window and cache_len <= cfg.sliding_window:
                slot = cache_pos % cache_len            # rolling SWA cache
            else:
                slot = cache_pos
            if opt:
                # delta mode (§Perf A7): the scan emits just this token's
                # K/V; decode_step commits all layers in one batched DUS
                new_cache = {
                    "k_tok": k.transpose(0, 2, 1, 3).astype(cache["k"].dtype),
                    "v_tok": v.transpose(0, 2, 3, 1).astype(cache["v"].dtype),
                }
            elif jnp.ndim(slot):
                # per-slot positions (continuous batching): scatter each
                # row's token K/V at that row's own cache offset
                rows = jnp.arange(k.shape[0])
                k_c = cache["k"].at[rows, slot].set(
                    _store_view(k, cfg, flat)[:, 0].astype(cache["k"].dtype))
                v_c = cache["v"].at[rows, slot].set(
                    _store_view(v, cfg, flat)[:, 0].astype(cache["v"].dtype))
                new_cache = {"k": k_c, "v": v_c}
                k, v = _cache_view(k_c, cfg), _cache_view(v_c, cfg)
            else:
                zeros = (0, 0, 0) if flat else (0, 0, 0, 0)
                k_c = jax.lax.dynamic_update_slice(
                    cache["k"],
                    _store_view(k, cfg, flat).astype(cache["k"].dtype),
                    (0, slot) + zeros[2:])
                v_c = jax.lax.dynamic_update_slice(
                    cache["v"],
                    _store_view(v, cfg, flat).astype(cache["v"].dtype),
                    (0, slot) + zeros[2:])
                new_cache = {"k": k_c, "v": v_c}
                k, v = _cache_view(k_c, cfg), _cache_view(v_c, cfg)
        else:                       # prefill: write whole K/V
            # Attend the same cache-dtype-rounded K/V the cache will hold
            # (a no-op when the cache is full precision). Every other
            # consumer of these positions — sequential decode, spec verify
            # windows, chunked-prefill windows — reads the *stored*
            # values, so rounding at production makes prefill->decode
            # bitwise-consistent with windowed admission (DESIGN.md §14)
            # instead of agreeing only up to greedy near-ties.
            k = k.astype(cache["k"].dtype).astype(k.dtype)
            v = v.astype(cache["v"].dtype).astype(v.dtype)
            s = k.shape[1]
            if opt:
                ks = k.transpose(0, 2, 1, 3)            # (B,KV,S,hd)
                vs = v.transpose(0, 2, 3, 1)            # (B,KV,hd,S)
                if s > cache_len:
                    shift = (s - cache_len) % cache_len
                    k_c = jnp.roll(ks[:, :, -cache_len:], shift, axis=2
                                   ).astype(cache["k"].dtype)
                    v_c = jnp.roll(vs[..., -cache_len:], shift, axis=3
                                   ).astype(cache["v"].dtype)
                else:
                    k_c = jax.lax.dynamic_update_slice(
                        cache["k"], ks.astype(cache["k"].dtype), (0, 0, 0, 0))
                    v_c = jax.lax.dynamic_update_slice(
                        cache["v"], vs.astype(cache["v"].dtype), (0, 0, 0, 0))
            else:
                ks = _store_view(k, cfg, flat)
                vs = _store_view(v, cfg, flat)
                if s > cache_len:
                    # rolling SWA cache: keep last `cache_len` tokens at
                    # their (pos % cache_len) slots
                    shift = (s - cache_len) % cache_len
                    k_c = jnp.roll(ks[:, -cache_len:], shift, axis=1
                                   ).astype(cache["k"].dtype)
                    v_c = jnp.roll(vs[:, -cache_len:], shift, axis=1
                                   ).astype(cache["v"].dtype)
                else:
                    zeros = (0, 0, 0) if flat else (0, 0, 0, 0)
                    k_c = jax.lax.dynamic_update_slice(
                        cache["k"], ks.astype(cache["k"].dtype), zeros)
                    v_c = jax.lax.dynamic_update_slice(
                        cache["v"], vs.astype(cache["v"].dtype), zeros)
                    # Attend through the *written cache view*, not the
                    # S-wide fresh K/V: sequential decode, spec verify and
                    # chunked-prefill windows all reduce attention over the
                    # full cache axis (naive, max_len-wide, stale tail
                    # masked as future by causality), and both the reducer
                    # width and the kernel choice change f32 accumulation
                    # grouping — an S-wide (or flash-blocked) prefill
                    # disagrees with the windowed paths by ~1 ULP on
                    # layer>=1 K/V, enough to flip greedy near-ties.
                    # Attending the view makes whole-prompt admission
                    # bitwise-equal to windowed admission (DESIGN.md §14).
                    k = _cache_view(k_c, cfg)
                    v = _cache_view(v_c, cfg)
                    attend_view = True
            new_cache = {"k": k_c, "v": v_c}

    if cache_pos is not None and q.shape[1] > 1:
        # verify window: causal masking gives token j of the window exactly
        # the prefix+window-causal view sequential decode at position
        # cache_pos+j would see (its own K/V at that slot included; stale
        # rows beyond the window are masked as "future" by causality)
        o = naive_attention(q, k, v, causal=True, window=cfg.sliding_window,
                            q_offset=cache_pos)
    elif cache_pos is not None:
        # decode: 1-token query against the cache (plain attention)
        cache_len = (cache["k"].shape[2] if opt
                     else cache["k"].shape[1]) if cache is not None else 0
        rolling = (cfg.sliding_window and cache is not None
                   and cache_len <= cfg.sliding_window)
        if rolling:
            valid = jnp.minimum(cache_pos + 1, cache_len)
            win, q_off = 0, 0
        else:
            valid = cache_pos + 1
            win, q_off = cfg.sliding_window, cache_pos
        if opt:
            o = delta_decode_attention(
                q, cache["k"], cache["v"],
                k.astype(cache["k"].dtype), v.astype(cache["v"].dtype),
                cache_pos=cache_pos, rolling=bool(rolling),
                window=cfg.sliding_window)
        else:
            o = naive_attention(q, k, v, causal=False, window=win,
                                q_offset=q_off, kv_valid_len=valid)
    elif attend_view:
        # prefill into a cache: same kernel + reduction width as the
        # decode/verify/chunk consumers of these positions (see above) —
        # never flash/pallas, whose blockwise accumulation differs
        o = naive_attention(q, k, v, causal=True,
                            window=cfg.sliding_window)
    else:
        if (cfg.gqa_repeat_kv or cfg.attn_impl == "pallas") \
                and k.shape[2] < h:
            # §Perf B1: repeat K/V to full MHA so every attention einsum
            # shards cleanly on the head axis (kv=8 cannot shard over a
            # 16-way TP axis). Repeat along a sharded dim is comm-free.
            k = jnp.repeat(k, h // k.shape[2], axis=2)
            v = jnp.repeat(v, h // v.shape[2], axis=2)
        if cfg.attn_impl == "pallas" and kv_override is None \
                and not cfg.sliding_window:
            # TPU runtime path: VMEM-resident flash kernel (§Perf B — kills
            # the XLA score/accumulator HBM round-trips). interpret=True on
            # non-TPU backends.
            import jax as _jax
            from repro.kernels.flash_attention import flash_attention_pallas
            b, s, _, hd2 = q.shape
            qf = q.transpose(0, 2, 1, 3).reshape(b * h, s, hd2)
            kf = k.transpose(0, 2, 1, 3).reshape(b * h, s, hd2)
            vf = v.transpose(0, 2, 1, 3).reshape(b * h, s, hd2)
            of = flash_attention_pallas(
                qf, kf, vf, causal=causal,
                block_q=min(cfg.attn_block_q, 512),
                block_kv=min(cfg.attn_block_kv, 512),
                interpret=_jax.default_backend() != "tpu")
            o = of.reshape(b, h, s, hd2).transpose(0, 2, 1, 3)
        elif cfg.attn_impl == "flash" and kv_override is None:
            o = flash_attention(q, k, v, causal=causal,
                                window=cfg.sliding_window,
                                block_q=cfg.attn_block_q,
                                block_kv=cfg.attn_block_kv)
        else:
            o = naive_attention(q, k, v, causal=causal,
                                window=cfg.sliding_window)
    y = linear_apply(params["o"], o.reshape(*x.shape[:-1], h * hd), cfg)
    return y, new_cache


def _paged_decode(params, x, cfg: ModelConfig, q, k, v, cache,
                  cache_pos, block_table):
    """Paged decode step (DESIGN.md §9): scatter the token's K/V into its
    row's current page, then attend over the block-table-indexed pages.

    Every live row writes to a page it privately owns (COW in the page pool
    guarantees this); free slots' block tables are all-zero, so their
    garbage writes land in the reserved trash page 0 and are never read.

    A multi-token window (S > 1: speculative verify, DESIGN.md §10, and
    chunked prefill, §14) scatters all S tokens first — the engine's
    ``ensure_append`` horizon made every page in positions
    cache_pos..cache_pos+S-1 privately owned — then attends through
    ``kops.paged_window_attention``: token j sees valid tokens < pos+j+1.
    The Pallas lowering walks each row's pages once for all S tokens; the
    ``jax`` lowering flattens the window into (B·S) single-query rows.
    """
    from repro.paging.quant import Int8Pages, quantize_rows

    k_pages, v_pages = cache["k_pages"], cache["v_pages"]
    quantized = isinstance(k_pages, Int8Pages)
    ps = (k_pages.codes if quantized else k_pages).shape[-3]
    pos = jnp.asarray(cache_pos)
    b, sq = k.shape[0], k.shape[1]
    h = cfg.num_heads + cfg.head_pad
    if sq == 1:
        rows = jnp.arange(k.shape[0])
        pids = block_table[rows, pos // ps]       # (B,) page of this token
        offs = pos % ps
        k_tok, v_tok = k[:, 0], v[:, 0]           # (B, KV, hd)
        if quantized:
            kc, ks = quantize_rows(k_tok)
            vc, vs = quantize_rows(v_tok)
            k_pages = Int8Pages(k_pages.codes.at[pids, offs].set(kc),
                                k_pages.scales.at[pids, offs].set(ks))
            v_pages = Int8Pages(v_pages.codes.at[pids, offs].set(vc),
                                v_pages.scales.at[pids, offs].set(vs))
        else:
            k_pages = k_pages.at[pids, offs].set(k_tok.astype(k_pages.dtype))
            v_pages = v_pages.at[pids, offs].set(v_tok.astype(v_pages.dtype))
        o = kops.paged_decode_attention(
            q[:, 0], k_pages, v_pages, block_table, pos + 1,
            window=cfg.sliding_window, impl=cfg.paged_attn_impl)
        o_seq = o[:, None]                        # (B, 1, H, hd)
    else:
        base = pos[:, None] if pos.ndim else pos
        pos2d = jnp.broadcast_to(base + jnp.arange(sq), (b, sq))
        rows = jnp.arange(b)[:, None]
        pids = block_table[rows, pos2d // ps]     # (B, S)
        offs = pos2d % ps
        if quantized:
            kc, ks = quantize_rows(k)             # (B,S,KV,hd)/(B,S,KV)
            vc, vs = quantize_rows(v)
            k_pages = Int8Pages(k_pages.codes.at[pids, offs].set(kc),
                                k_pages.scales.at[pids, offs].set(ks))
            v_pages = Int8Pages(v_pages.codes.at[pids, offs].set(vc),
                                v_pages.scales.at[pids, offs].set(vs))
        else:
            k_pages = k_pages.at[pids, offs].set(k.astype(k_pages.dtype))
            v_pages = v_pages.at[pids, offs].set(v.astype(v_pages.dtype))
        o_seq = kops.paged_window_attention(
            q, k_pages, v_pages, block_table,
            jnp.broadcast_to(pos + 1, (b,)), window=cfg.sliding_window,
            impl=cfg.paged_attn_impl)
    y = linear_apply(params["o"],
                     o_seq.reshape(*x.shape[:-1], h * cfg.head_dim),
                     cfg)
    return y, {"k_pages": k_pages, "v_pages": v_pages}


def init_paged_kv_cache(cfg: ModelConfig, n_pages: int, page_size: int,
                        dtype=jnp.bfloat16, kv_dtype: Optional[str] = None,
                        ) -> dict:
    """Per-layer page arrays for the paged KV cache (DESIGN.md §9): K and V
    as (n_pages, page_size, KV, hd), either dense ``dtype`` buffers or
    int8 ``Int8Pages`` containers (``kv_dtype="int8"``). Page id 0 is the
    pool's reserved trash page for free-slot garbage writes."""
    shape = (n_pages, page_size, cfg.num_kv_heads, cfg.head_dim)
    if kv_dtype in ("int8", "i8"):
        from repro.paging.quant import Int8Pages
        return {"k_pages": Int8Pages.zeros(shape),
                "v_pages": Int8Pages.zeros(shape)}
    return {"k_pages": jnp.zeros(shape, dtype),
            "v_pages": jnp.zeros(shape, dtype)}


def init_kv_cache(cfg: ModelConfig, batch: int, max_len: int,
                  dtype=jnp.bfloat16) -> dict:
    """Per-layer KV cache. SWA models keep a rolling window-sized cache —
    that boundedness is what makes SWA decode sub-quadratic.

    decode_cache_shard == "flat": store (B, S, kv*hd) with the channel dim
    TP-sharded — the seq axis stays local (in-place one-token DUS) and
    GSPMD propagates the channel sharding to the natural (kv x hd) split
    through the reshape at the attention einsum (§Perf iteration A4)."""
    s = min(max_len, cfg.sliding_window) if cfg.sliding_window else max_len
    if cfg.cache_layout == "opt":
        # transpose-free dot layouts (§Perf A6): contracting dims minor-most
        return {"k": jnp.zeros((batch, cfg.num_kv_heads, s, cfg.head_dim),
                               dtype),
                "v": jnp.zeros((batch, cfg.num_kv_heads, cfg.head_dim, s),
                               dtype)}
    if cfg.decode_cache_shard == "flat":
        shape = (batch, s, cfg.num_kv_heads * cfg.head_dim)
    else:
        shape = (batch, s, cfg.num_kv_heads, cfg.head_dim)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def _cache_view(c: jnp.ndarray, cfg: ModelConfig) -> jnp.ndarray:
    """(B, S, kv*hd) storage -> (B, S, kv, hd) compute view."""
    if c.ndim == 3:
        return c.reshape(c.shape[0], c.shape[1], cfg.num_kv_heads,
                         cfg.head_dim)
    return c


def _store_view(k: jnp.ndarray, cfg: ModelConfig, flat: bool) -> jnp.ndarray:
    if flat:
        return k.reshape(k.shape[0], k.shape[1], -1)
    return k
