"""Core layers (functional, no framework): linear (dense / ternary-QAT /
ternary-packed), norms, embeddings, RoPE, gated MLP.

Every ``init_*`` returns ``(params, specs)`` where ``specs`` mirrors the
params pytree with ``jax.sharding.PartitionSpec`` leaves — keeping shardings
structurally in sync with parameters (the distributed layer consumes them).

Axis-name conventions used in specs (resolved to mesh axes in
``repro.distributed.sharding``): "fsdp" (data axes when cfg.fsdp), "model".
We store specs directly as PartitionSpec with logical names; resolution
replaces names with mesh axes or None.
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.core import quantize, weights

# Logical axis names (resolved in distributed/sharding.py)
FSDP = "fsdp"      # -> data axes if cfg.fsdp else None
MODEL = "model"    # -> tensor-parallel axis
EMPTY = None


def _dtype(cfg: ModelConfig):
    return jnp.dtype(cfg.dtype)


def _pdtype(cfg: ModelConfig):
    return jnp.dtype(cfg.param_dtype)


# ---------------------------------------------------------------------------
# Linear — the layer the paper's technique lives in
# ---------------------------------------------------------------------------

def linear_init(key, cfg: ModelConfig, d_in: int, d_out: int,
                in_axis=FSDP, out_axis=MODEL, use_bias: Optional[bool] = None,
                scale: Optional[float] = None):
    """A (d_in, d_out) projection. Under ``quantization='ternary_packed'``
    the parameter is the packed 2-bit word matrix + per-channel scale
    (serving format); otherwise a latent dense matrix (QAT applies STE)."""
    use_bias = cfg.use_bias if use_bias is None else use_bias
    std = scale if scale is not None else 1.0 / math.sqrt(d_in)
    ternary = _is_ternary(cfg, d_in, d_out)
    params, specs = {}, {}
    if cfg.quantization == "ternary_packed" and ternary:
        # Serving format: a TernaryWeight container is the parameter (its
        # array leaves flow through stacking/scan/sharding like any other
        # leaf; the spec twin mirrors it with PartitionSpec leaves).
        kw = (d_in + 15) // 16
        wc = weights.Dense2Bit(
            packed=jnp.zeros((kw, d_out), jnp.uint32),
            scale=jnp.ones((d_out,), jnp.float32),
            bias=jnp.zeros((d_out,), jnp.float32) if use_bias else None,
            shape=(d_in, d_out))
        params["w_packed"] = wc
        specs["w_packed"] = wc.replace(
            packed=P(in_axis, out_axis), scale=P(out_axis),
            bias=P(out_axis) if use_bias else None)
        return params, specs
    params["w"] = jax.random.normal(key, (d_in, d_out), _pdtype(cfg)) * std
    specs["w"] = P(in_axis, out_axis)
    if use_bias:
        params["b"] = jnp.zeros((d_out,), _pdtype(cfg))
        specs["b"] = P(out_axis)
    return params, specs


def _is_ternary(cfg: ModelConfig, d_in: int, d_out: int) -> bool:
    return (cfg.quantization != "none"
            and min(d_in, d_out) >= cfg.ternary_min_dim)


def _use_pallas_gemm(cfg: ModelConfig) -> bool:
    if cfg.ternary_kernel == "pallas":
        return True
    if cfg.ternary_kernel == "xla":
        return False
    return jax.default_backend() == "tpu"


def gemm_impl(cfg: ModelConfig) -> str:
    """The ``ternary_gemm`` impl the packed-linear apply path dispatches
    for this config: ``"auto"`` (registry + autotuner, Pallas) when the
    Pallas path is active, else the XLA dense-decode ``"ref"`` oracle.
    Single source of truth — the serving engine warms GemmPlans for
    exactly this impl."""
    return "auto" if _use_pallas_gemm(cfg) else "ref"


def linear_apply(params, x: jnp.ndarray, cfg: ModelConfig) -> jnp.ndarray:
    """x: (..., d_in) -> (..., d_out)."""
    wc = params.get("w_packed")
    if wc is not None and not isinstance(wc, weights.TernaryWeight):
        raise TypeError(
            "params['w_packed'] is a raw array (the pre-container packed "
            "format); re-pack from the latent (unpacked) weights with "
            "models.layers.pack_params, or wrap the buffer directly via "
            "weights.Dense2Bit.from_packed(words, k=d_in, scale=...)")
    if isinstance(wc, weights.TernaryWeight):
        lead = x.shape[:-1]
        x2 = x.reshape(-1, x.shape[-1])
        from repro.kernels import ops as kops
        y = kops.ternary_gemm(x2, wc, impl=gemm_impl(cfg))
        y = y.reshape(*lead, -1)
    else:
        w = params["w"]
        if cfg.quantization == "ternary" and _is_ternary(cfg, *w.shape):
            w = quantize.ste_ternarize(w, cfg.ternary_threshold)
        y = jnp.dot(x, w.astype(x.dtype))
    if "b" in params:
        y = y + params["b"].astype(y.dtype)
    return y


def pack_linear(params: dict, cfg: ModelConfig) -> dict:
    """Convert a latent-weight linear into the packed serving format
    (host-side): the parameter becomes a ``weights.Dense2Bit`` container
    carrying per-channel ternarization scales (and the bias, when present).
    Handles scan-stacked weights: a (L, K, N) stack packs to
    (L, ceil(K/16), N) leaves — scan slicing hands the kernel 2-D blocks
    at apply time."""
    if "w" not in params:
        return params
    w = params["w"]
    if not _is_ternary(cfg, *w.shape[-2:]):
        return params
    return {"w_packed": weights.pack(w, "dense2bit", bias=params.get("b"),
                                     threshold=cfg.ternary_threshold)}


def pack_params(params, cfg: ModelConfig):
    """Walk a model param tree, converting every ternarizable projection
    (plain or scan-stacked linears, MoE expert banks) into the packed
    ``TernaryWeight`` serving format. The single pack entry point for
    serving / checkpointing (examples, launch.serve, tests)."""
    from repro.models import moe as moe_lib

    def walk(p):
        if isinstance(p, dict):
            if "router" in p and "w_in" in p:
                return moe_lib.pack_moe(p, cfg)
            if "w" in p and getattr(p["w"], "ndim", 0) in (2, 3) \
                    and min(p["w"].shape[-2:]) >= cfg.ternary_min_dim:
                return pack_linear(p, cfg)
            return {k: walk(v) for k, v in p.items()}
        return p

    return walk(params)


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------

def norm_init(key, cfg: ModelConfig, d: int):
    del key
    params = {"scale": jnp.ones((d,), _pdtype(cfg))}
    specs = {"scale": P(EMPTY)}
    if cfg.norm_type == "layernorm":
        params["bias"] = jnp.zeros((d,), _pdtype(cfg))
        specs["bias"] = P(EMPTY)
    return params, specs


def norm_apply(params, x: jnp.ndarray, cfg: ModelConfig) -> jnp.ndarray:
    xf = x.astype(jnp.float32)
    if cfg.norm_type == "layernorm":
        xf = xf - jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + cfg.norm_eps)
    y = y * params["scale"].astype(jnp.float32)
    if "bias" in params:
        y = y + params["bias"].astype(jnp.float32)
    return y.astype(x.dtype)


# ---------------------------------------------------------------------------
# Embedding / unembedding
# ---------------------------------------------------------------------------

def embed_init(key, cfg: ModelConfig):
    v, d = cfg.padded_vocab(), cfg.d_model
    params = {"table": jax.random.normal(key, (v, d), _pdtype(cfg)) * 0.02}
    specs = {"table": P(MODEL, FSDP)}
    return params, specs


def embed_apply(params, tokens: jnp.ndarray, cfg: ModelConfig) -> jnp.ndarray:
    # gather, then cast: casting the whole table first would write a
    # table-sized temporary on every call
    return params["table"][tokens].astype(_dtype(cfg))


def unembed_init(key, cfg: ModelConfig):
    # The vocab head is a plain linear layer -> the paper's ternary format
    # applies to it like any other projection.
    return linear_init(key, cfg, cfg.d_model, cfg.padded_vocab(),
                       FSDP, MODEL, use_bias=False)


def unembed_apply(params, x: jnp.ndarray, cfg: ModelConfig) -> jnp.ndarray:
    return linear_apply(params, x, cfg)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------

def rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float) -> jnp.ndarray:
    """x: (B, S, H, hd), positions: (B, S) -> rotated x."""
    hd = x.shape[-1]
    half = hd // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = positions[..., None].astype(jnp.float32) * freqs  # (B, S, half)
    cos = jnp.cos(ang)[:, :, None, :]
    sin = jnp.sin(ang)[:, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Gated MLP (SwiGLU)
# ---------------------------------------------------------------------------

def mlp_init(key, cfg: ModelConfig, d_ff: int):
    k1, k2, k3 = jax.random.split(key, 3)
    w_in, s_in = linear_init(k1, cfg, cfg.d_model, d_ff, FSDP, MODEL)
    w_gate, s_gate = linear_init(k2, cfg, cfg.d_model, d_ff, FSDP, MODEL)
    w_out, s_out = linear_init(k3, cfg, d_ff, cfg.d_model, MODEL, FSDP)
    return ({"in": w_in, "gate": w_gate, "out": w_out},
            {"in": s_in, "gate": s_gate, "out": s_out})


def _fused_mlp_weights(params, cfg: ModelConfig):
    """The (w_in, w_out, w_gate) containers when this MLP can dispatch the
    fused lowering: every projection packed (bias inside the container),
    the Pallas path active, and fusion not configured off."""
    if getattr(cfg, "fused_mlp", "auto") == "off" or not _use_pallas_gemm(cfg):
        return None
    ws = []
    for name in ("in", "out", "gate"):
        p = params.get(name, {})
        wc = p.get("w_packed") if isinstance(p, dict) else None
        if not isinstance(wc, weights.TernaryWeight) or "b" in p:
            return None
        ws.append(wc)
    return tuple(ws)


def mlp_apply(params, x: jnp.ndarray, cfg: ModelConfig) -> jnp.ndarray:
    fused = _fused_mlp_weights(params, cfg)
    if fused is not None:
        w_in, w_out, w_gate = fused
        from repro.kernels import ops as kops
        lead = x.shape[:-1]
        y = kops.fused_mlp(x.reshape(-1, x.shape[-1]), w_in, w_out, w_gate)
        return y.reshape(*lead, -1)
    h = jax.nn.silu(linear_apply(params["gate"], x, cfg)) \
        * linear_apply(params["in"], x, cfg)
    return linear_apply(params["out"], h, cfg)
