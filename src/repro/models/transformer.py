"""Model assembly: decoder-only / encoder-decoder / hybrid / VLM / audio LMs
from a ``ModelConfig``, with layers stacked under ``jax.lax.scan`` (HLO size
and compile time are O(1) in depth — required for 61-80 layer dry-runs).

Heterogeneous stacks (jamba's 1:7 attn:mamba interleave with MoE every 2nd
layer) scan over *period groups*: the layer pattern repeats with period p
(jamba: 8), params are stacked over L/p groups, and the scan body unrolls the
p distinct blocks. Leading layers that break the period (a dense first layer
before a stack of MoE layers) run once, unrolled, before the scan: they are
``lead{i}`` in the parameter tree (unstacked) and in the cache tree (a
leading axis of 1, like one scan group).

Public API (all functional):
    m = LM(cfg)
    params, specs = m.init_with_specs(key)
    loss, metrics = m.loss(params, batch)
    cache = m.init_cache(batch_size, max_len)
    cache, logits = m.prefill(params, batch, max_len)
    logits, cache = m.decode_step(params, cache, tokens)
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.models import attention, layers, moe, ssm
from repro.models.layers import MODEL


def tree_nbytes(tree) -> int:
    """Total payload bytes across a cache/param tree's array leaves — the
    serving-memory figure of merit (dense slot pools and paged pools
    alike report it in the engine metrics)."""
    import numpy as np
    total = 0
    for leaf in jax.tree_util.tree_leaves(tree):
        nb = getattr(leaf, "nbytes", None)
        if nb is None:
            nb = int(np.prod(leaf.shape)) * np.dtype(leaf.dtype).itemsize
        total += int(nb)
    return total


def _stack_tree(trees):
    return jax.tree.map(lambda *xs: jnp.stack(xs), *trees)


def _stack_specs(spec_tree):
    return jax.tree.map(
        lambda s: P(None, *s) if isinstance(s, P) else s, spec_tree,
        is_leaf=lambda x: isinstance(x, P))


def split_period(kinds) -> Tuple[int, int]:
    """(n_lead, period) of a layer sequence: ``n_lead`` leading layers run
    once, then the rest repeats with ``period``. The split unrolls the
    fewest layers (n_lead + period), fewer leading layers first, so a
    stack that repeats from its first layer, two periods or more, keeps
    n_lead = 0 and its smallest period."""
    n = len(kinds)
    for total in range(1, n + 1):
        for lead in range(total):
            p = total - lead
            if (n - lead) % p == 0 and all(
                    kinds[i] == kinds[lead + (i - lead) % p]
                    for i in range(lead, n)):
                return lead, p
    return 0, n


class LM:
    def __init__(self, cfg: ModelConfig):
        self.cfg = cfg
        kinds = [(cfg.layer_kind(i), cfg.layer_ffn(i), cfg.layer_window(i))
                 for i in range(cfg.num_layers)]
        lead, p = split_period(kinds)
        self.n_lead = lead
        self.period = p
        self.n_groups = (cfg.num_layers - lead) // p
        self.lead_kinds = [k[:2] for k in kinds[:lead]]
        self.lead_windows = [k[2] for k in kinds[:lead]]
        self.block_kinds = [k[:2] for k in kinds[lead:lead + p]]
        self.block_windows = [k[2] for k in kinds[lead:lead + p]]
        self._layer_cfgs: Dict[int, ModelConfig] = {}

    def layer_cfg(self, window: int) -> ModelConfig:
        """The config one attention layer runs under: the stack's, with its
        own window, and without RoPE in a full layer of a stack that
        rotates its window layers only."""
        cfg = self.cfg
        if window not in self._layer_cfgs:
            theta = (0.0 if cfg.rope_window_only and not window
                     else cfg.rope_theta)
            if window == cfg.sliding_window and theta == cfg.rope_theta:
                self._layer_cfgs[window] = cfg
            else:
                self._layer_cfgs[window] = dataclasses.replace(
                    cfg, sliding_window=window, layer_windows=(),
                    rope_theta=theta)
        return self._layer_cfgs[window]

    # ------------------------------------------------------------------
    # Init
    # ------------------------------------------------------------------
    def _block_init(self, key, kind: str, ffn: str, cross: bool):
        cfg = self.cfg
        ks = jax.random.split(key, 6)
        params: Dict[str, Any] = {}
        specs: Dict[str, Any] = {}
        params["norm1"], specs["norm1"] = layers.norm_init(ks[0], cfg, cfg.d_model)
        if kind == "attn":
            params["mixer"], specs["mixer"] = attention.attn_init(ks[1], cfg)
        else:
            params["mixer"], specs["mixer"] = ssm.ssm_init(ks[1], cfg)
        if cross:
            params["norm_cross"], specs["norm_cross"] = layers.norm_init(
                ks[2], cfg, cfg.d_model)
            params["cross"], specs["cross"] = attention.attn_init(ks[3], cfg)
        if ffn != "none":
            params["norm2"], specs["norm2"] = layers.norm_init(ks[4], cfg, cfg.d_model)
            if ffn == "moe":
                params["ffn"], specs["ffn"] = moe.moe_init(ks[5], cfg)
            else:
                params["ffn"], specs["ffn"] = layers.mlp_init(ks[5], cfg, cfg.d_ff)
        return params, specs

    def init_with_specs(self, key):
        cfg = self.cfg
        keys = jax.random.split(key, 8)
        params, specs = {}, {}
        params["embed"], specs["embed"] = layers.embed_init(keys[0], cfg)
        cross = cfg.is_encdec

        # leading layers, once each; then the blocks, stacked over groups
        # per period-offset
        for i, (kind, ffn) in enumerate(self.lead_kinds):
            params[f"lead{i}"], specs[f"lead{i}"] = self._block_init(
                jax.random.fold_in(keys[6], i), kind, ffn, cross)
        for j, (kind, ffn) in enumerate(self.block_kinds):
            ps, ss = [], None
            for g in range(self.n_groups):
                k = jax.random.fold_in(keys[1], g * self.period + j)
                p_, s_ = self._block_init(k, kind, ffn, cross)
                ps.append(p_)
                ss = s_
            params[f"block{j}"] = _stack_tree(ps)
            specs[f"block{j}"] = _stack_specs(ss)

        if cfg.is_encdec:
            enc_ps, enc_ss = [], None
            for g in range(cfg.enc_layers):
                k = jax.random.fold_in(keys[2], g)
                p_, s_ = self._block_init(k, "attn", "mlp", cross=False)
                enc_ps.append(p_)
                enc_ss = s_
            params["enc_block"] = _stack_tree(enc_ps)
            specs["enc_block"] = _stack_specs(enc_ss)
            params["enc_norm"], specs["enc_norm"] = layers.norm_init(
                keys[3], cfg, cfg.d_model)

        params["final_norm"], specs["final_norm"] = layers.norm_init(
            keys[4], cfg, cfg.d_model)
        if not cfg.tie_embeddings:
            params["unembed"], specs["unembed"] = layers.unembed_init(keys[5], cfg)
        return params, specs

    def init(self, key):
        return self.init_with_specs(key)[0]

    def init_with_specs_abstract(self):
        """(param ShapeDtypeStructs, PartitionSpec tree) — no allocation.
        Specs are static python objects built during tracing; capture them
        through a side channel since eval_shape only maps array outputs."""
        captured = {}

        def f(key):
            params, specs = self.init_with_specs(key)
            captured["specs"] = specs
            return params

        shapes = jax.eval_shape(f, jax.ShapeDtypeStruct((2,), jnp.uint32))
        return shapes, captured["specs"]

    # ------------------------------------------------------------------
    # One block
    # ------------------------------------------------------------------
    def _apply_block(self, bparams, x, kind: str, ffn: str, *,
                     positions, causal=True, cache=None, cache_pos=None,
                     enc_out=None, block_table=None, window=None,
                     token_mask=None, layer=None):
        """One layer -> (x, new_cache, aux loss, MoE stats (2,) int32:
        (row, held expert) pairs computed and held experts with a row).
        A paged ``cache`` is the whole stacked page pool and ``layer`` the
        group this layer reads and writes."""
        cfg = self.cfg
        lcfg = self.layer_cfg(cfg.sliding_window if window is None
                              else window)
        post = cfg.sublayer_norm == "post"
        h = x if post else layers.norm_apply(bparams["norm1"], x, cfg)
        if kind == "attn":
            h, new_cache = attention.attn_apply(
                bparams["mixer"], h, lcfg, positions=positions,
                causal=causal, cache=cache, cache_pos=cache_pos,
                block_table=block_table, layer=layer)
        else:
            h, new_cache = ssm.ssm_apply(
                bparams["mixer"], h, cfg, cache=cache, cache_pos=cache_pos)
        if post:
            h = layers.norm_apply(bparams["norm1"], h, cfg)
        x = x + h
        if enc_out is not None and "cross" in bparams:
            hc = layers.norm_apply(bparams["norm_cross"], x, cfg)
            kv, hd = cfg.num_kv_heads, cfg.head_dim
            ek = layers.linear_apply(bparams["cross"]["k"], enc_out, cfg)
            ev = layers.linear_apply(bparams["cross"]["v"], enc_out, cfg)
            ek = ek.reshape(*enc_out.shape[:-1], kv, hd)
            ev = ev.reshape(*enc_out.shape[:-1], kv, hd)
            hc, _ = attention.attn_apply(
                bparams["cross"], hc, cfg, positions=positions,
                kv_override=(ek, ev))
            x = x + hc
        aux = jnp.zeros((), jnp.float32)
        stats = jnp.zeros((2,), jnp.int32)
        if ffn != "none":
            h2 = x if post else layers.norm_apply(bparams["norm2"], x, cfg)
            if ffn == "moe":
                h2, aux, stats = moe.moe_apply(bparams["ffn"], h2, cfg,
                                               token_mask=token_mask)
            else:
                h2 = layers.mlp_apply(bparams["ffn"], h2, cfg)
            if post:
                h2 = layers.norm_apply(bparams["norm2"], h2, cfg)
            x = x + h2
        return x, new_cache, aux, stats

    # ------------------------------------------------------------------
    # Stacked decoder
    # ------------------------------------------------------------------
    def _run_stack(self, params, x, *, positions, causal=True,
                   caches=None, cache_pos=None, enc_out=None,
                   block_table=None, token_mask=None):
        """caches: dict block{j} -> stacked (n_groups, ...) cache trees,
        lead{i} -> (1, ...) trees. ``block_table`` (paged decode) is
        layer-invariant, so it rides into the scan body as a closure
        constant rather than a sliced xs leaf. ``token_mask`` (B, S) marks
        the rows that carry a request: MoE layers route only those.
        Returns (x, new caches, summed aux loss, summed MoE stats).

        A paged layer gets its whole stacked page pool and its group's
        index, and writes its new tokens into the pool in place; no copy
        of its pages is taken or written back. Dense and SSM caches are
        sliced to the layer's group and written back."""
        cfg = self.cfg
        kw = dict(positions=positions, causal=causal, cache_pos=cache_pos,
                  enc_out=enc_out, block_table=block_table,
                  token_mask=token_mask)
        new_caches = {}
        aux_lead = jnp.zeros((), jnp.float32)
        stats_lead = jnp.zeros((2,), jnp.int32)
        for i, (kind, ffn) in enumerate(self.lead_kinds):
            c = caches.get(f"lead{i}") if caches is not None else None
            paged = c is not None and "k_pages" in c
            if c is not None and not paged:
                c = jax.tree.map(lambda a: a[0], c)
            x, nc, aux, stats = self._apply_block(
                params[f"lead{i}"], x, kind, ffn, cache=c,
                window=self.lead_windows[i], layer=0 if paged else None,
                **kw)
            aux_lead += aux
            stats_lead += stats
            if nc is not None:
                new_caches[f"lead{i}"] = nc if paged else jax.tree.map(
                    lambda a: a[None], nc)

        # The stacked caches ride in the scan's carry, so no second
        # cache-sized stack is built. A paged layer updates its group of
        # the pool in place; a dense layer reads its group's slice and
        # writes its new cache back; a layer whose new cache has another
        # form (the 'opt' layout's new tokens) emits it per group.
        def body(carry, xs):
            x, stacked = carry
            stacked = dict(stacked)
            aux_total = jnp.zeros((), jnp.float32)
            stats_total = jnp.zeros((2,), jnp.int32)
            emitted = {}
            for j, (kind, ffn) in enumerate(self.block_kinds):
                key = f"cache{j}"
                c = stacked.get(key)
                paged = c is not None and "k_pages" in c
                if c is not None and not paged:
                    c = jax.tree.map(lambda a: a[xs["g"]], c)
                x, nc, aux, stats = self._apply_block(
                    xs[f"block{j}"], x, kind, ffn, cache=c,
                    window=self.block_windows[j],
                    layer=xs["g"] if paged else None, **kw)
                aux_total += aux
                stats_total += stats
                if nc is None:
                    continue
                if paged:
                    stacked[key] = nc
                elif (jax.tree_util.tree_structure(nc)
                        == jax.tree_util.tree_structure(c)):
                    stacked[key] = jax.tree.map(
                        lambda a, n: a.at[xs["g"]].set(n.astype(a.dtype)),
                        stacked[key], nc)
                else:
                    emitted[key] = nc
            return (x, stacked), (emitted, aux_total, stats_total)

        # Remat only where there is a backward pass to save memory for —
        # wrapping the serving scans in jax.checkpoint makes XLA route the
        # full stacked KV cache through f32 select/convert chains every
        # layer step (measured +150 GB/chip/step on decode_32k).
        if cfg.remat == "full" and caches is None:
            body = jax.checkpoint(body, prevent_cse=False)

        xs = {f"block{j}": params[f"block{j}"]
              for j in range(len(self.block_kinds))}
        xs["g"] = jnp.arange(self.n_groups)
        stacked = {f"cache{j}": caches[f"cache{j}"]
                   for j in range(len(self.block_kinds))
                   if caches is not None and f"cache{j}" in caches}
        (x, stacked), (emitted, auxs, stats) = jax.lax.scan(
            body, (x, stacked), xs)
        if caches is not None:
            new_caches.update(stacked)
            new_caches.update(emitted)
        return (x, new_caches, aux_lead + jnp.sum(auxs),
                stats_lead + jnp.sum(stats, axis=0))

    def _run_encoder(self, params, enc_x):
        cfg = self.cfg
        positions = jnp.broadcast_to(
            jnp.arange(enc_x.shape[1]), enc_x.shape[:2])

        def body(x, bp):
            x, _, _, _ = self._apply_block(bp, x, "attn", "mlp",
                                           positions=positions,
                                           causal=False)
            return x, None

        if cfg.remat == "full":
            body = jax.checkpoint(body, prevent_cse=False)
        x, _ = jax.lax.scan(body, enc_x, params["enc_block"])
        return layers.norm_apply(params["enc_norm"], x, cfg)

    # ------------------------------------------------------------------
    # Forward / loss
    # ------------------------------------------------------------------
    def _embed_inputs(self, params, batch):
        cfg = self.cfg
        x = layers.embed_apply(params["embed"], batch["tokens"], cfg)
        n_front = 0
        if "vision_embeds" in batch:
            ve = batch["vision_embeds"].astype(x.dtype)
            x = jnp.concatenate([ve, x], axis=1)
            n_front = ve.shape[1]
        return x, n_front

    def _logits(self, params, x):
        cfg = self.cfg
        if cfg.tie_embeddings:
            return jnp.dot(x, params["embed"]["table"].astype(x.dtype).T)
        return layers.unembed_apply(params["unembed"], x, cfg)

    def forward(self, params, batch):
        """Full-sequence forward -> (hidden (B,S,D), n_frontend, aux)."""
        cfg = self.cfg
        x, n_front = self._embed_inputs(params, batch)
        enc_out = None
        if cfg.is_encdec:
            enc_out = self._run_encoder(params, batch["enc_embeds"].astype(x.dtype))
        positions = jnp.broadcast_to(jnp.arange(x.shape[1]), x.shape[:2])
        x = _shard_act(x, P(("pod", "data"), None, None))
        x, _, aux, _ = self._run_stack(params, x, positions=positions,
                                       causal=True, enc_out=enc_out)
        x = layers.norm_apply(params["final_norm"], x, cfg)
        return x, n_front, aux

    def loss(self, params, batch):
        """Causal-LM cross-entropy (chunked over seq if cfg.logits_chunk)."""
        cfg = self.cfg
        x, n_front, aux = self.forward(params, batch)
        x_text = x[:, n_front:]
        targets = batch["targets"]
        v = cfg.padded_vocab()

        def ce_of(xc, tc):
            logits = self._logits(params, xc).astype(jnp.float32)
            logz = jax.nn.logsumexp(logits, axis=-1)
            gold = jnp.take_along_axis(logits, tc[..., None], axis=-1)[..., 0]
            return logz - gold

        if cfg.logits_chunk and x_text.shape[1] % cfg.logits_chunk == 0:
            b, s, d = x_text.shape
            nc = s // cfg.logits_chunk
            xc = jnp.moveaxis(x_text.reshape(b, nc, cfg.logits_chunk, d), 1, 0)
            tc = jnp.moveaxis(targets.reshape(b, nc, cfg.logits_chunk), 1, 0)
            ce = jax.lax.map(lambda args: ce_of(*args), (xc, tc))
            ce = jnp.moveaxis(ce, 0, 1).reshape(b, s)
        else:
            ce = ce_of(x_text, targets)
        loss = jnp.mean(ce) + 0.01 * aux
        return loss, {"loss": loss, "ce": jnp.mean(ce), "aux": aux}

    # ------------------------------------------------------------------
    # Serving
    # ------------------------------------------------------------------
    def _cache_slots(self):
        """(cache key, mixer kind, leading layer count) of every entry of
        the cache tree: leading layers, then the scanned blocks."""
        return ([(f"lead{i}", kind, 1)
                 for i, (kind, _) in enumerate(self.lead_kinds)]
                + [(f"cache{j}", kind, self.n_groups)
                   for j, (kind, _) in enumerate(self.block_kinds)])

    def init_cache(self, batch: int, max_len: int, dtype=None):
        cfg = self.cfg
        dtype = jnp.dtype(cfg.cache_dtype) if dtype is None else dtype
        caches = {}
        for key, kind, n in self._cache_slots():
            if kind == "attn":
                one = attention.init_kv_cache(cfg, batch, max_len, dtype)
            else:
                one = ssm.init_ssm_cache(cfg, batch, dtype)
            caches[key] = jax.tree.map(
                lambda x, n=n: jnp.broadcast_to(x, (n, *x.shape)), one)
        return {"layers": caches, "pos": jnp.zeros((), jnp.int32)}

    def init_paged_cache(self, n_pages: int, page_size: int, batch: int,
                         dtype=None, kv_dtype=None):
        """Paged-cache layer tree (DESIGN.md §9): attention layers hold
        global page arrays (``n_pages`` shared across all slots, indexed
        through per-slot block tables), while SSM layers keep their O(1)
        per-slot rows — state paging buys nothing for constant-size state.
        Owned and indexed by ``repro.paging.PagePool``."""
        cfg = self.cfg
        dtype = jnp.dtype(cfg.cache_dtype) if dtype is None else dtype
        caches = {}
        for key, kind, n in self._cache_slots():
            if kind == "attn":
                one = attention.init_paged_kv_cache(
                    cfg, n_pages, page_size, dtype, kv_dtype)
            else:
                one = ssm.init_ssm_cache(cfg, batch, dtype)
            caches[key] = jax.tree.map(
                lambda x, n=n: jnp.broadcast_to(x, (n, *x.shape)), one)
        return {"layers": caches}

    @staticmethod
    def insert_cache(pool_layers, req_layers, slots):
        """Slot-pool cache contract: write a freshly prefilled k-request
        cache (batch dim k, same max_len) into the batch rows ``slots``
        (scalar or (k,) vector) of a pool cache (batch dim max_slots).
        Every cache leaf is stacked as (n_groups, B, ...), so one tree-wide
        row scatter covers attention K/V and SSM state/conv alike. Used by
        ``repro.serving.SlotPool``."""
        slots = jnp.atleast_1d(slots)
        return jax.tree.map(
            lambda big, small: big.at[:, slots].set(
                small.astype(big.dtype)), pool_layers, req_layers)

    def cache_specs(self, decode_seq_sharded: bool = True):
        """PartitionSpec tree matching init_cache output."""
        cfg = self.cfg
        caches = {}
        seq_ax = MODEL if decode_seq_sharded else None
        hd_ax = None
        if cfg.decode_cache_shard == "heads":
            # shard head_dim: the seq axis stays local -> the per-token DUS
            # is an in-place write instead of a GSPMD select over the whole
            # cache shard (§Perf iteration A2)
            seq_ax, hd_ax = None, MODEL
        for key, kind, _ in self._cache_slots():
            if kind == "attn":
                if cfg.cache_layout == "opt":
                    # K (.., B, KV, S, hd) / V (.., B, KV, hd, S): seq
                    # TP-sharded on its new position (§Perf A6)
                    one = {"k": P(None, ("pod", "data"), None, seq_ax, None),
                           "v": P(None, ("pod", "data"), None, None, seq_ax)}
                elif cfg.decode_cache_shard == "flat":
                    # (n_groups, B, S, kv*hd): channel dim TP-sharded,
                    # seq local (§Perf iteration A4)
                    one = {"k": P(None, ("pod", "data"), None, MODEL),
                           "v": P(None, ("pod", "data"), None, MODEL)}
                else:
                    one = {"k": P(None, ("pod", "data"), seq_ax, None, hd_ax),
                           "v": P(None, ("pod", "data"), seq_ax, None, hd_ax)}
            else:
                one = {"state": P(None, ("pod", "data"), None, None, None),
                       "conv": P(None, ("pod", "data"), None, MODEL)}
            caches[key] = one
        return {"layers": caches, "pos": P()}

    def prefill(self, params, batch, max_len: int, cache_dtype=jnp.bfloat16):
        """Run the prompt, fill caches, return (cache, last-position logits)."""
        cfg = self.cfg
        x, n_front = self._embed_inputs(params, batch)
        enc_out = None
        if cfg.is_encdec:
            enc_out = self._run_encoder(params, batch["enc_embeds"].astype(x.dtype))
        positions = jnp.broadcast_to(jnp.arange(x.shape[1]), x.shape[:2])
        x = _shard_act(x, P(("pod", "data"), None, None))
        cache0 = self.init_cache(x.shape[0], max_len, cache_dtype)
        x, new_caches, _, _ = self._run_stack(
            params, x, positions=positions, causal=True,
            caches=cache0["layers"], cache_pos=None, enc_out=enc_out)
        x = layers.norm_apply(params["final_norm"], x, cfg)
        logits = self._logits(params, x[:, -1:])
        cache = {"layers": new_caches,
                 "pos": jnp.asarray(x.shape[1], jnp.int32)}
        if cfg.is_encdec:
            cache["enc_out"] = enc_out
        return cache, logits

    def _decode_window_unrolled(self, cache) -> bool:
        """Whether a multi-token verify window must unroll per token.

        The batched window path (scatter all S tokens' K/V, then attend
        with causal-within-window masking) equals sequential decode only
        when a token's cache write cannot clobber state an earlier window
        token still reads: non-rolling attention caches in the bshd/flat
        layouts, paged included. Rolling SWA caches (a wrapped write
        overwrites the oldest live entry), the 'opt' delta-commit layout
        and SSM recurrences instead unroll inside the same call — bitwise
        equal to sequential decode by construction."""
        cfg = self.cfg
        if cfg.cache_layout == "opt":
            return True
        if any(kind != "attn" for kind, _ in self.block_kinds):
            return True
        if "block_table" in cache:        # paged pools reject SWA models
            return False
        if cfg.sliding_window:
            cache_len = cache["layers"]["cache0"]["k"].shape[2]
            return cache_len <= cfg.sliding_window      # rolling cache
        return False

    def decode_step(self, params, cache, tokens, with_stats: bool = False):
        """tokens: (B, S) -> (logits (B,S,V), updated cache). S is 1 for
        plain decode; S > 1 is a speculative-decoding *verify window*
        (DESIGN.md §10): the S tokens sit at consecutive positions
        pos..pos+S-1 and each position's logits equal what S sequential
        single-token calls would produce.

        ``cache["pos"]`` may be a scalar (classic batched decode: all rows
        at the same position) or a (B,) vector (continuous batching: each
        slot decodes at its own position; K/V writes scatter per slot).
        A ``cache["block_table"]`` entry switches attention layers to the
        paged cache path (pages + block tables, DESIGN.md §9). A
        ``cache["token_mask"]`` (B, S) bool marks the tokens that carry a
        request (the engine's pad lanes are False): MoE layers route only
        those. ``with_stats`` adds a third result, (2,) int32 summed over
        the MoE layers: (row, held expert) pairs computed and held experts
        that got a row."""
        cfg = self.cfg
        pos = cache["pos"]
        sq = tokens.shape[1]
        if sq > 1 and self._decode_window_unrolled(cache):
            lgs, cur = [], cache
            mask = cache.get("token_mask")
            stats = jnp.zeros((2,), jnp.int32)
            for j in range(sq):
                if mask is not None:
                    cur = dict(cur, token_mask=mask[:, j:j + 1])
                lg, cur, st = self.decode_step(params, cur,
                                               tokens[:, j:j + 1], True)
                stats = stats + st
                lgs.append(lg)
            if mask is not None:
                cur = dict(cur, token_mask=mask)
            out = jnp.concatenate(lgs, axis=1), cur
            return out + (stats,) if with_stats else out
        positions_src = pos[:, None] if jnp.ndim(pos) else pos
        x = layers.embed_apply(params["embed"], tokens, cfg)
        if sq == 1:
            positions = jnp.broadcast_to(positions_src, tokens.shape)
        else:
            positions = jnp.broadcast_to(positions_src + jnp.arange(sq),
                                         tokens.shape)
        x, new_caches, _, stats = self._run_stack(
            params, x, positions=positions, causal=True,
            caches=cache["layers"], cache_pos=pos,
            enc_out=cache.get("enc_out"),
            block_table=cache.get("block_table"),
            token_mask=cache.get("token_mask"))
        x = layers.norm_apply(params["final_norm"], x, cfg)
        logits = self._logits(params, x)
        # delta-mode commit (§Perf A7): the scan emitted per-layer K/V
        # tokens; write them all with one batched DUS per cache tensor.
        committed = {}
        for key, nc in new_caches.items():
            if isinstance(nc, dict) and "k_tok" in nc:
                old = cache["layers"][key]
                s_len = old["k"].shape[3]
                if cfg.sliding_window and s_len <= cfg.sliding_window:
                    slot = pos % s_len
                else:
                    slot = pos
                if jnp.ndim(slot):
                    # per-slot positions: scatter each batch row's token at
                    # its own offset (k_tok (L,B,KV,1,hd), v_tok (L,B,KV,hd,1))
                    rows = jnp.arange(slot.shape[0])
                    committed[key] = {
                        "k": old["k"].at[:, rows, :, slot, :].set(
                            nc["k_tok"][:, :, :, 0].transpose(1, 0, 2, 3)),
                        "v": old["v"].at[:, rows, :, :, slot].set(
                            nc["v_tok"][..., 0].transpose(1, 0, 2, 3)),
                    }
                else:
                    committed[key] = {
                        "k": jax.lax.dynamic_update_slice(
                            old["k"], nc["k_tok"], (0, 0, 0, slot, 0)),
                        "v": jax.lax.dynamic_update_slice(
                            old["v"], nc["v_tok"], (0, 0, 0, 0, slot)),
                    }
            else:
                committed[key] = nc
        new_cache = dict(cache, layers=committed, pos=pos + sq)
        if with_stats:
            return logits, new_cache, stats
        return logits, new_cache


# ---------------------------------------------------------------------------
# Activation sharding constraint helper (no-op outside a mesh context)
# ---------------------------------------------------------------------------

_MESH = None


def set_mesh(mesh):
    """Install the mesh used to resolve activation sharding constraints."""
    global _MESH
    _MESH = mesh


def _shard_act(x, spec: P):
    if _MESH is None:
        return x
    names = set(_MESH.axis_names)

    def size_of(axes):
        n = 1
        for a in (axes if isinstance(axes, (tuple, list)) else (axes,)):
            n *= _MESH.shape[a]
        return n

    def fix(axes, dim):
        if axes is None:
            return None
        if isinstance(axes, (tuple, list)):
            kept = tuple(a for a in axes if a in names)
            if not kept or dim % size_of(kept) != 0:
                return None
            return kept
        if axes not in names or dim % size_of(axes) != 0:
            return None
        return axes

    entries = tuple(spec) + (None,) * (x.ndim - len(tuple(spec)))
    resolved = P(*(fix(a, d) for a, d in zip(entries, x.shape)))
    return jax.lax.with_sharding_constraint(
        x, jax.sharding.NamedSharding(_MESH, resolved))
