"""Plain float32 reference forward of the sparse-expert family
(K-EXAONE-236B-A23B), its seeded weights, and its lower-precision control.

Straightforward ``jax.numpy`` under ``default_matmul_precision("highest")``,
one layer at a time, from the configuration file's keys:

* attention: grouped-query, causal, with the layer's window from
  ``sliding_windows`` (0 = full); q and k RMS-normalised over head_dim,
  then rotated (rotate-half form, ``rope_parameters.rope_theta``) in the
  window layers only;
* the block: ``x + rms(attn(x))``, then ``x + rms(ffn(x))``: the norms
  sit on the sub-layer outputs, none on their inputs;
* ``mlp_layer_types``: a ``dense`` layer is a SwiGLU MLP of
  ``intermediate_size``; a ``sparse`` one routes each token over the
  published ``published_num_experts`` experts by ``sigmoid(x W_r)``,
  keeps the top ``num_experts_per_tok``, renormalises their scores to sum
  1 and scales them by ``routed_scaling_factor``; the experts held here
  (``num_experts`` of them, from ``expert_offset``) are SwiGLU MLPs of
  ``moe_intermediate_size``, each computed on every token and weighted by
  its gate (0 where it was not chosen); the shared expert is added;
* a final RMSNorm and the untied head.

The ``assumed`` block of the configuration file says which of these the
published config leaves open. It imports nothing of the program under
test: every weight is regenerated from the seed (``leaf``, which the
served model's parameters are filled from too) and each 2-bit code is
decoded here.

The control (``lowp=True``) is the same forward with every matrix
product's inputs, the router's included, rounded to float8 (e4m3), the
precision below the bf16 the configuration states. It has to fail the
output check.
"""
from __future__ import annotations

import functools
import json
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

import gen
from reference import (NEG, Q_BLOCK, _lp, _rope, decode_words,
                       pack_sequences, token_gaps)

BANKS = ("w_in", "w_gate", "w_out")
# one compiled program per (configuration, layer kind, precision)
_PROGRAMS: Dict[tuple, object] = {}


# -- seeded weights -----------------------------------------------------------

def _expert_keys(key, shape, offset: int):
    """One key per held expert, drawn from its global expert id."""
    ids = offset + jnp.arange(shape[0], dtype=jnp.uint32)
    return jax.vmap(lambda e: jax.random.fold_in(key, e))(ids)


def leaf(c: Dict, root, name: str, shape, layer=None, k_in: int = 0):
    """The weight named ``name`` (``gen.leaf`` for everything the dense
    family has too). Expert banks (``.../ffn/w_in/packed`` and ``/scale``,
    leading axis the experts held) draw each expert from its global id, so
    a share of the layer is a slice of the whole; the router is normal
    with std 1/sqrt(hidden)."""
    key = gen.leaf_key(root, name)
    if layer is not None:
        key = jax.random.fold_in(key, layer)
    parts = name.split("/")
    if len(parts) >= 2 and parts[-2] in BANKS:
        keys = _expert_keys(key, shape, int(c["expert_offset"]))
        if parts[-1] == "packed":
            return jax.vmap(lambda k: gen.ternary_words(k, shape[1:]))(keys)
        k = (c["moe_intermediate_size"] if parts[-2] == "w_out"
             else c["hidden_size"])
        return jax.vmap(lambda kk: gen.channel_scale(kk, shape[-1], k))(keys)
    if parts[-1] == "router":
        return jax.random.normal(key, shape, jnp.float32) \
            / np.float32(np.sqrt(shape[0]))
    return gen.leaf(root, name, shape, layer, k_in)


# -- layer layout (how the served model names each layer's weights) ----------

def layer_names(c: Dict) -> List[Tuple[str, object]]:
    """(name prefix, stacked index) of each layer's weights: the leading
    layers that break the period are ``lead{i}`` (not stacked), the rest
    ``block{j}`` at group ``g``. The split keeps the fewest layers
    unrolled (leading layers plus one period), fewer leading layers
    first."""
    kinds = list(zip(c["sliding_windows"], c["mlp_layer_types"]))
    n = len(kinds)
    for total in range(1, n + 1):
        for lead in range(total):
            p = total - lead
            if (n - lead) % p == 0 and all(
                    kinds[i] == kinds[lead + (i - lead) % p]
                    for i in range(lead, n)):
                return ([(f"lead{i}", None) for i in range(lead)]
                        + [(f"block{(i - lead) % p}", (i - lead) // p)
                           for i in range(lead, n)])
    raise AssertionError("unreachable")


# -- the forward --------------------------------------------------------------

def _w(c, root, name, shape, layer):
    return leaf(c, root, name, shape, layer)


def _proj(c, root, base: str, x, k: int, n: int, layer, lowp: bool):
    words = gen.leaf(root, base + "/w_packed/packed", (-(-k // 16), n),
                     layer)
    scale = gen.leaf(root, base + "/w_packed/scale", (n,), layer, k_in=k)
    return (_lp(x, lowp) @ decode_words(words, k)) * scale


def _rms(c, root, name: str, x, layer):
    g = _w(c, root, name, (x.shape[-1],), layer)
    eps = c["rms_norm_eps"]
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * g


def _attention(q, k, v, window: int, lowp: bool):
    """Causal GQA attention over (n, T, heads, hd), keys within
    ``window`` positions when it is not 0."""
    n, t, h, hd = q.shape
    g = h // k.shape[2]
    k = jnp.repeat(k, g, axis=2)
    v = jnp.repeat(v, g, axis=2)
    q, k, v = _lp(q, lowp), _lp(k, lowp), _lp(v, lowp)
    outs = []
    for a in range(0, t, Q_BLOCK):
        qb = q[:, a:a + Q_BLOCK]
        s = jnp.einsum("nqhd,nkhd->nhqk", qb, k) / np.sqrt(hd)
        dist = (a + jnp.arange(qb.shape[1]))[:, None] - jnp.arange(t)[None]
        mask = dist >= 0
        if window:
            mask &= dist < window
        s = jnp.where(mask[None, None], s, NEG)
        p = jax.nn.softmax(s, axis=-1)
        outs.append(jnp.einsum("nhqk,nkhd->nqhd", _lp(p, lowp), v))
    return jnp.concatenate(outs, axis=1)


def _mlp(c, root, base: str, x, ff: int, layer, lowp: bool):
    d = c["hidden_size"]
    gate = _proj(c, root, base + "/gate", x, d, ff, layer, lowp)
    up = _proj(c, root, base + "/in", x, d, ff, layer, lowp)
    return _proj(c, root, base + "/out", jax.nn.silu(gate) * up, ff, d,
                 layer, lowp)


def _moe(c, root, base: str, x, layer, lowp: bool):
    """The held experts' share of the routed sum, plus the shared
    expert. x (N, d) -> (N, d)."""
    d, f = c["hidden_size"], c["moe_intermediate_size"]
    e_all, held = c["published_num_experts"], c["num_experts"]
    off = c["expert_offset"]
    router = _w(c, root, base + "/router", (d, e_all), layer)
    scores = jax.nn.sigmoid(_lp(x, lowp) @ _lp(router, lowp))
    top, ids = jax.lax.top_k(scores, c["num_experts_per_tok"])
    if c["norm_topk_prob"]:
        top = top / jnp.sum(top, axis=-1, keepdims=True)
    top = top * c["routed_scaling_factor"]
    kw_d, kw_f = -(-d // 16), -(-f // 16)
    banks = {b: (_w(c, root, f"{base}/{b}/packed",
                    (held, kw_f if b == "w_out" else kw_d,
                     d if b == "w_out" else f), layer),
                 _w(c, root, f"{base}/{b}/scale",
                    (held, d if b == "w_out" else f), layer))
             for b in BANKS}
    y = jnp.zeros_like(x)
    for i in range(held):
        gate_i = jnp.sum(jnp.where(ids == off + i, top, 0.0), axis=-1)

        def bank(b, a, k):
            words, scale = banks[b]
            return (_lp(a, lowp) @ decode_words(words[i], k)) * scale[i]

        h = jax.nn.silu(bank("w_gate", x, d)) * bank("w_in", x, d)
        y = y + gate_i[:, None] * bank("w_out", h, f)
    shared = c["num_shared_experts"] * f
    return y + _mlp(c, root, base + "/shared", x, shared, layer, lowp)


def _layer(c: Dict, root, *, prefix: str, window: int, dense: bool, layer,
           x, lowp: bool):
    d, h, kvh, hd = (c["hidden_size"], c["num_attention_heads"],
                     c["num_key_value_heads"], c["head_dim"])
    theta = c["rope_parameters"]["rope_theta"]
    n, t, _ = x.shape
    q = _proj(c, root, prefix + "/mixer/q", x, d, h * hd, layer, lowp)
    k = _proj(c, root, prefix + "/mixer/k", x, d, kvh * hd, layer, lowp)
    v = _proj(c, root, prefix + "/mixer/v", x, d, kvh * hd, layer, lowp)
    q = _rms(c, root, prefix + "/mixer/q_norm/scale",
             q.reshape(n, t, h, hd), layer)
    k = _rms(c, root, prefix + "/mixer/k_norm/scale",
             k.reshape(n, t, kvh, hd), layer)
    if window:
        q, k = _rope(q, theta), _rope(k, theta)
    o = _attention(q, k, v.reshape(n, t, kvh, hd), window, lowp)
    a = _proj(c, root, prefix + "/mixer/o", o.reshape(n, t, h * hd),
              h * hd, d, layer, lowp)
    x = x + _rms(c, root, prefix + "/norm1/scale", a, layer)
    x2 = x.reshape(n * t, d)
    if dense:
        m = _mlp(c, root, prefix + "/ffn", x2, c["intermediate_size"],
                 layer, lowp)
    else:
        m = _moe(c, root, prefix + "/ffn", x2, layer, lowp)
    return x + _rms(c, root, prefix + "/norm2/scale", m.reshape(n, t, d),
                    layer)


def logits_at(c: Dict, seed: int, tokens: np.ndarray,
              positions: np.ndarray, lowp: bool = False) -> jnp.ndarray:
    """Logits (P, vocab) at ``positions`` (P, 2) = (row, position) of the
    (n, T) token batch ``tokens``, from the full causal forward."""
    root = gen.root_key(seed)
    tag = json.dumps(c, sort_keys=True)
    with jax.default_matmul_precision("highest"):
        table = gen.leaf(root, "embed/table",
                         (c["vocab_size"], c["hidden_size"]))
        x = jnp.asarray(table)[jnp.asarray(tokens)]
        del table
        for i, (prefix, layer) in enumerate(layer_names(c)):
            window = c["sliding_windows"][i]
            dense = c["mlp_layer_types"][i] == "dense"
            key = (tag, prefix, window, dense, lowp)
            if key not in _PROGRAMS:
                _PROGRAMS[key] = jax.jit(functools.partial(
                    _layer, c, prefix=prefix, window=window, dense=dense,
                    lowp=lowp))
            x = _PROGRAMS[key](root, layer=None if layer is None
                               else jnp.int32(layer), x=x)
        sel = x[positions[:, 0], positions[:, 1]]
        del x

        def head(r, h):
            h = _rms(c, r, "final_norm/scale", h, None)
            return _proj(c, r, "unembed", h, c["hidden_size"],
                         c["vocab_size"], None, lowp)

        return jax.jit(head)(root, sel)


def served_gaps(c: Dict, seed: int, seqs) -> List[float]:
    """The widest gap of each sequence's served tokens under the float32
    reference."""
    tokens, pos, tgt, owner = pack_sequences(seqs)
    gaps = token_gaps(logits_at(c, seed, tokens, pos), tgt)
    return [float(gaps[owner == i].max()) for i in range(len(seqs))]


def control_gaps(c: Dict, seed: int, seqs) -> Tuple[List[float], List[float]]:
    """At every position of the same prompts and served tokens: the
    reference's gap of the token the float8 control puts first, and that
    of the served token. Widest per sequence, (control, served)."""
    tokens, pos, tgt, owner = pack_sequences(seqs)
    low = logits_at(c, seed, tokens, pos, lowp=True)
    pick = np.asarray(jnp.argmax(low, axis=-1))
    del low
    ref = logits_at(c, seed, tokens, pos)
    g_ctl = token_gaps(ref, pick)
    g_srv = token_gaps(ref, tgt)
    return ([float(g_ctl[owner == i].max()) for i in range(len(seqs))],
            [float(g_srv[owner == i].max()) for i in range(len(seqs))])
