"""Mixture-of-Experts with token-choice top-k routing.

Two dispatch paths, by the weights' format:

* Latent weights (training, QAT): capacity-bounded expert-side dispatch
  (GShard-style dropping). The expert matmuls are real ``(E, C, d) x
  (E, d, f)`` batched GEMMs whose FLOP count equals ``top_k * tokens *
  capacity_factor`` active-expert FLOPs, so ``cost_analysis()`` reflects
  genuine MoE compute.
* Packed 2-bit banks (serving): dropless, and one device's share of an
  expert-parallel layer. The layer holds ``experts_held`` banks, experts
  ``expert_offset ..`` of ``num_experts``; every row is routed over all
  ``num_experts``, the (row, expert) pairs whose expert is held are sorted
  by expert, run through the grouped ternary GEMM
  (``kernels.ops.moe_expert_gemm``) for gate, up and down, weighted by
  their gates and summed back per row. The shared expert, which every
  device computes alike, is added once. A layer that holds all its experts
  is the uncut layer. No bank is ever decoded whole: an expert's words are
  read only when rows were routed to it.

Router scores are ``softmax`` or ``sigmoid``; top-k is taken on the
scores, the chosen ones are renormalised to sum 1 and scaled by
``routed_scaling`` (with sigmoid scores, the DeepSeek-V3 form).
"""
from __future__ import annotations

import math
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.core import quantize, weights
from repro.models.layers import (FSDP, MODEL, _is_ternary, _pdtype,
                                 _use_pallas_gemm, mlp_apply, mlp_init)

EXPERT = "expert"   # logical axis: resolved to "model" when E % model == 0


def moe_init(key, cfg: ModelConfig):
    d, e, f = cfg.d_model, cfg.num_experts, cfg.d_ff_expert
    ks = jax.random.split(key, 5)
    std = 1.0 / math.sqrt(d)
    params = {
        "router": jax.random.normal(ks[0], (d, e), _pdtype(cfg)) * std,
    }
    specs = {
        "router": P(None, None),
    }
    if cfg.quantization == "ternary_packed":
        # serving format: TernaryWeight containers of 2-bit packed expert
        # banks + per-channel scales — 16x less weight bandwidth where it
        # matters most (expert weights dominate MoE bytes; the paper's
        # technique at its highest leverage)
        kw_d, kw_f = (d + 15) // 16, (f + 15) // 16
        held = cfg.experts_held or e

        def bank(kw, n, kdim):
            return weights.Dense2Bit(
                packed=jnp.zeros((held, kw, n), jnp.uint32),
                scale=jnp.ones((held, n), jnp.float32), bias=None,
                shape=(kdim, n))

        params.update({
            "w_in": bank(kw_d, f, d),
            "w_gate": bank(kw_d, f, d),
            "w_out": bank(kw_f, d, f),
        })
        specs.update({
            "w_in": params["w_in"].replace(
                packed=P(EXPERT, FSDP, MODEL), scale=P(EXPERT, MODEL)),
            "w_gate": params["w_gate"].replace(
                packed=P(EXPERT, FSDP, MODEL), scale=P(EXPERT, MODEL)),
            "w_out": params["w_out"].replace(
                packed=P(EXPERT, MODEL, FSDP), scale=P(EXPERT, FSDP)),
        })
    else:
        params.update({
            "w_in": jax.random.normal(ks[1], (e, d, f), _pdtype(cfg)) * std,
            "w_gate": jax.random.normal(ks[2], (e, d, f), _pdtype(cfg)) * std,
            "w_out": jax.random.normal(ks[3], (e, f, d), _pdtype(cfg))
            / math.sqrt(f),
        })
        specs.update({
            "w_in": P(EXPERT, FSDP, MODEL),
            "w_gate": P(EXPERT, FSDP, MODEL),
            "w_out": P(EXPERT, MODEL, FSDP),
        })
    if cfg.n_shared_experts and cfg.quantization == "ternary_packed":
        params["shared"], specs["shared"] = mlp_init(
            ks[4], cfg, cfg.n_shared_experts * f)
    elif cfg.n_shared_experts:
        fs = cfg.n_shared_experts * f
        params["shared_in"] = jax.random.normal(ks[4], (d, fs), _pdtype(cfg)) * std
        params["shared_gate"] = jax.random.normal(ks[4], (d, fs), _pdtype(cfg)) * std
        params["shared_out"] = jax.random.normal(ks[4], (fs, d), _pdtype(cfg)) / math.sqrt(fs)
        specs["shared_in"] = P(FSDP, MODEL)
        specs["shared_gate"] = P(FSDP, MODEL)
        specs["shared_out"] = P(MODEL, FSDP)
    return params, specs


def _expert_weight(w, cfg: ModelConfig):
    if cfg.quantization == "ternary":
        # per-expert per-channel ternarization (vmapped STE)
        return jax.vmap(lambda wi: quantize.ste_ternarize(
            wi, cfg.ternary_threshold))(w)
    return w


def moe_apply(params, x: jnp.ndarray, cfg: ModelConfig, token_mask=None
              ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """x: (B, S, d) -> (y, aux_loss, stats). ``stats`` (2,) int32: the
    (row, held expert) pairs computed and the held experts that got a row
    (packed path; zeros on the latent path). ``token_mask`` (B, S) bool:
    rows that carry no request route to no expert (packed path)."""
    if isinstance(params["w_in"], weights.TernaryWeight):
        return _moe_packed(params, x, cfg, token_mask)
    y, aux = _moe_capacity(params, x, cfg)
    return y, aux, jnp.zeros((2,), jnp.int32)


def _moe_capacity(params, x: jnp.ndarray, cfg: ModelConfig
                  ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Latent weights: x (B, S, d) -> (y, aux_loss). Capacity
    C = ceil(T*k/E * cf).

    With ``cfg.moe_route_blocks = nb`` (aligned to the DP shard count),
    routing/capacity/gather/scatter are per token-block: every data-movement
    op stays shard-local and the only cross-shard communication is the
    dispatched (nb, E, C/nb, d) tensor meeting the model-sharded experts —
    an all-to-all of the *active* tokens instead of global-token all-reduces
    (§Perf D1: measured 488x f32[81936,7168] all-reduces on kimi train)."""
    b, s, d = x.shape
    e, k = cfg.num_experts, cfg.num_experts_per_tok
    t = b * s
    nb = max(cfg.moe_route_blocks, 1)
    if t % nb != 0:
        nb = 1
    tb = t // nb
    xb = x.reshape(nb, tb, d)

    logits = jnp.einsum("ntd,de->nte", xb, params["router"].astype(x.dtype))
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)  # (nb,Tb,E)
    top_p, top_ids = jax.lax.top_k(probs, k)                     # (nb,Tb,k)
    top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)      # renormalize

    # token-side sparse gate matrix (nb, Tb, E), built shard-locally
    gates = jnp.zeros((nb, tb, e), jnp.float32)
    gates = jax.vmap(jax.vmap(lambda g, i, p: g.at[i].set(p)))(
        gates, top_ids, top_p)

    # expert-side capacity truncation per block: top-C/nb tokens by gate
    cap = int(math.ceil(tb * k / e * cfg.capacity_factor))
    cap = min(max(cap, 1), tb)
    g_sel, tok_sel = jax.lax.top_k(
        jnp.swapaxes(gates, 1, 2), cap)                          # (nb,E,C)

    xe = jnp.take_along_axis(
        xb[:, None], tok_sel[..., None], axis=2)                 # (nb,E,C,d)
    # (§Perf D2 tried pinning the dispatch sharding here; measured: it
    # fights GSPMD propagation — t_coll 165 -> 436 s. Refuted.)
    w_in = _expert_weight(params["w_in"], cfg).astype(x.dtype)
    w_gate = _expert_weight(params["w_gate"], cfg).astype(x.dtype)
    w_out = _expert_weight(params["w_out"], cfg).astype(x.dtype)
    h = jax.nn.silu(jnp.einsum("necd,edf->necf", xe, w_gate)) \
        * jnp.einsum("necd,edf->necf", xe, w_in)
    ye = jnp.einsum("necf,efd->necd", h, w_out)                  # (nb,E,C,d)
    ye = ye * g_sel[..., None].astype(ye.dtype)

    # per-block scatter-add back to token order (shard-local when nb == DP)
    y = jnp.zeros((nb, tb, d), ye.dtype)
    y = y.at[jnp.arange(nb)[:, None], tok_sel.reshape(nb, -1)].add(
        ye.reshape(nb, -1, d), mode="drop")

    if cfg.n_shared_experts:
        xt = xb.reshape(t, d)
        hs = jax.nn.silu(jnp.dot(xt, params["shared_gate"].astype(x.dtype))) \
            * jnp.dot(xt, params["shared_in"].astype(x.dtype))
        y = y + jnp.dot(hs, params["shared_out"].astype(x.dtype)
                        ).reshape(nb, tb, d)

    # Switch-style load-balancing auxiliary loss
    me = jnp.mean(probs, axis=(0, 1))                            # (E,)
    ce = jnp.mean(
        jax.nn.one_hot(top_ids[..., 0], e, dtype=jnp.float32), axis=(0, 1))
    aux = e * jnp.sum(me * ce)
    return y.reshape(b, s, d).astype(x.dtype), aux


def route(x2: jnp.ndarray, router, cfg: ModelConfig):
    """Router of the packed path, in float32: x2 (T, d) -> (gates (T, k)
    float32, expert ids (T, k)) over all ``num_experts``."""
    logits = jnp.dot(x2.astype(jnp.float32), router.astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    if cfg.moe_scoring == "sigmoid":
        scores = jax.nn.sigmoid(logits)
    elif cfg.moe_scoring == "softmax":
        scores = jax.nn.softmax(logits, axis=-1)
    else:
        raise ValueError(f"unknown moe_scoring {cfg.moe_scoring!r}")
    top, ids = jax.lax.top_k(scores, cfg.num_experts_per_tok)
    top = top / jnp.sum(top, axis=-1, keepdims=True)
    return top * cfg.routed_scaling, ids


def expert_tiles(local: jnp.ndarray, held: int, block_m: int):
    """Expert-sorted, tile-padded layout of (row, expert) pairs.

    ``local`` (P,) int32: each pair's held-expert index, ``held`` for a
    pair whose expert is not held (or whose row carries no request).
    Returns (pos (P,): each held pair's row in the padded layout, the
    layout's length for the others; tile_group, tile_row (n_tiles,);
    n_active (1,); sizes (held,)). Each expert's rows start a tile; the
    static bound holds every pair: n_tiles = ceil(P / block_m) + held."""
    p = local.shape[0]
    n_tiles = -(-p // block_m) + held
    # a pair's place in its expert's group: the pairs of that expert
    # before it (a counting sort, stable in pair order)
    onehot = (local[:, None] == jnp.arange(held)).astype(jnp.int32)
    before = jnp.cumsum(onehot, axis=0) - onehot
    sizes = jnp.sum(onehot, axis=0)
    tiles = -(-sizes // block_m)
    tile_end = jnp.cumsum(tiles)
    tile_start = tile_end - tiles
    mine = local < held
    g = jnp.minimum(local, held - 1)
    rank = jnp.take_along_axis(before, g[:, None], axis=1)[:, 0]
    pos = jnp.where(mine, tile_start[g] * block_m + rank,
                    n_tiles * block_m)
    n_active = tile_end[-1]
    t = jnp.minimum(jnp.arange(n_tiles, dtype=jnp.int32),
                    jnp.maximum(n_active - 1, 0))
    tile_group = jnp.minimum(
        jnp.searchsorted(tile_end, t, side="right"), held - 1
    ).astype(jnp.int32)
    return (pos, tile_group, t.astype(jnp.int32),
            n_active.reshape(1).astype(jnp.int32), sizes)


def _moe_packed(params, x: jnp.ndarray, cfg: ModelConfig, token_mask):
    """Packed banks, dropless: this device's share of the layer (see the
    module docstring) plus the shared expert."""
    from repro.kernels import ops as kops
    b, s, d = x.shape
    t, k = b * s, cfg.num_experts_per_tok
    w_in, w_gate, w_out = params["w_in"], params["w_gate"], params["w_out"]
    held = w_in.packed.shape[0]
    x2 = x.reshape(t, d)
    # decode rows take the smallest bf16 tile; wider windows fewer tiles
    block_m = 16 if t * k <= 1024 else 128
    with jax.named_scope("moe_route"):
        gates, ids = route(x2, params["router"], cfg)
        local = ids - cfg.expert_offset
        mine = (local >= 0) & (local < held)
        if token_mask is not None:
            mine &= token_mask.reshape(t)[:, None]
        pos, tile_group, tile_row, n_active, sizes = expert_tiles(
            jnp.where(mine, local, held).reshape(-1).astype(jnp.int32),
            held, block_m)
        m_pad = tile_group.shape[0] * block_m
        # the row each padded slot reads: its pair's token, else a zero row
        src = jnp.full((m_pad,), t, jnp.int32).at[pos].set(
            jnp.repeat(jnp.arange(t, dtype=jnp.int32), k), mode="drop")
        xs = jnp.concatenate([x2, jnp.zeros((1, d), x.dtype)])[src]
    impl = "pallas" if _use_pallas_gemm(cfg) else "xla"

    def gemm(a, w):
        return kops.moe_expert_gemm(a, w, tile_group, tile_row, n_active,
                                    block_m=block_m, impl=impl)

    h = jax.nn.silu(gemm(xs, w_gate)) * gemm(xs, w_in)
    ys = gemm(h, w_out)
    with jax.named_scope("moe_route"):
        yp = ys[jnp.minimum(pos, m_pad - 1)].astype(jnp.float32)
        w = jnp.where(mine.reshape(-1), gates.reshape(-1), 0.0)
        yp = jnp.where(mine.reshape(-1, 1), yp * w[:, None], 0.0)
        y = jnp.sum(yp.reshape(t, k, d), axis=1)
    if "shared" in params:
        y = y + mlp_apply(params["shared"], x2, cfg).astype(jnp.float32)
    stats = jnp.stack([jnp.sum(mine), jnp.sum(sizes > 0)]).astype(jnp.int32)
    return (y.astype(x.dtype).reshape(b, s, d), jnp.zeros((), jnp.float32),
            stats)


def pack_moe(params: dict, cfg: ModelConfig) -> dict:
    """Host-side: convert a latent MoE node's expert banks ((E, K, N) or
    scan-stacked (L, E, K, N)) into packed ``Dense2Bit`` containers, each
    expert matrix ternarized per-channel, and a shared expert into the
    packed MLP the serving format holds. The router stays latent (small,
    and routed in float32). Gated like
    ``layers.pack_linear``: an unquantized config (or experts below
    ``ternary_min_dim``) passes through untouched — packing is lossy and
    must never be applied unrequested."""
    if isinstance(params.get("w_in"), weights.TernaryWeight) \
            or "w_in" not in params \
            or not _is_ternary(cfg, *params["w_in"].shape[-2:]):
        return params
    from repro.models.layers import pack_linear
    shared = ("shared_in", "shared_gate", "shared_out")
    out = {k: v for k, v in params.items()
           if k not in ("w_in", "w_gate", "w_out") + shared}
    for name in ("w_in", "w_gate", "w_out"):
        out[name] = weights.pack(params[name], "dense2bit",
                                 threshold=cfg.ternary_threshold)
    if "shared_in" in params:
        # the serving format's shared expert is a packed MLP
        out["shared"] = {
            name: pack_linear({"w": params["shared_" + src]}, cfg)
            for name, src in (("in", "in"), ("gate", "gate"),
                              ("out", "out"))}
    return out
