"""Plain float32 reference forward of a dense GQA decoder, and its
lower-precision control.

Straightforward ``jax.numpy`` under ``default_matmul_precision("highest")``:
RMSNorm, rotary embeddings (rotate-half form, ``theta`` from the
configuration), grouped-query causal attention (query head ``h`` reads
key/value head ``h // (heads / kv_heads)``), a SwiGLU MLP
(``down(silu(gate(x)) * up(x))``) and an untied head. It covers the
Mistral-NeMo equations and the ternary-paper decoder alike. It imports
nothing of the program under test: every weight is regenerated from the
seed by ``gen`` and each 2-bit code is decoded here to -1, 0 or +1 times
its channel's scale.

Departures from the published model: the projection weights are ternary
times a per-channel scale (the served format) and random; the embedding is
random and the head untied, as published.

The control (``lowp=True``) is the same forward with every matrix
product's inputs rounded to float8 (e4m3): the precision below the bf16
the configuration states. It has to fail the output check.

Runs layer by layer, one compiled layer program for all layers, so it fits
next to nothing else on one chip.
"""
from __future__ import annotations

from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

import gen

F32 = jnp.float32
NEG = -1e30
Q_BLOCK = 512


def decode_words(words, k: int):
    """(kw, n) uint32 words -> (k, n) float32 of -1, 0, +1."""
    shifts = 2 * jnp.arange(16, dtype=jnp.uint32)
    codes = (words[:, None, :] >> shifts[None, :, None]) & 3
    t = (codes == 1).astype(F32) - (codes == 2).astype(F32)
    return t.reshape(words.shape[0] * 16, words.shape[1])[:k]


def _lp(x, lowp: bool):
    if not lowp:
        return x
    return x.astype(jnp.float8_e4m3fn).astype(F32)


def _proj(root, name: str, x, k: int, n: int, layer, lowp: bool):
    base = f"block0/{name}/w_packed/" if layer is not None else f"{name}/w_packed/"
    words = gen.leaf(root, base + "packed", (-(-k // 16), n), layer=layer)
    scale = gen.leaf(root, base + "scale", (n,), layer=layer, k_in=k)
    t = decode_words(words, k)
    return (_lp(x, lowp) @ t) * scale


def _rms(root, name: str, x, eps: float, layer=None):
    g = gen.leaf(root, name, (x.shape[-1],), layer=layer)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def _rope(x, theta: float):
    """x (n, T, H, hd); rotate-half form."""
    t, hd = x.shape[1], x.shape[-1]
    half = hd // 2
    inv = theta ** (-jnp.arange(half, dtype=F32) * 2.0 / hd)
    ang = jnp.arange(t, dtype=F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(q, k, v, lowp: bool):
    """Causal GQA attention; q (n,T,H,hd), k/v (n,T,KV,hd)."""
    n, t, h, hd = q.shape
    g = h // k.shape[2]
    k = jnp.repeat(k, g, axis=2)
    v = jnp.repeat(v, g, axis=2)
    q, k, v = _lp(q, lowp), _lp(k, lowp), _lp(v, lowp)
    outs = []
    for a in range(0, t, Q_BLOCK):
        qb = q[:, a:a + Q_BLOCK]
        s = jnp.einsum("nqhd,nkhd->nhqk", qb, k) / np.sqrt(hd)
        qpos = a + jnp.arange(qb.shape[1])
        mask = qpos[:, None] >= jnp.arange(t)[None, :]
        s = jnp.where(mask[None, None], s, NEG)
        p = jax.nn.softmax(s, axis=-1)
        outs.append(jnp.einsum("nhqk,nkhd->nqhd", _lp(p, lowp), v))
    return jnp.concatenate(outs, axis=1)


def _layer(c: Dict, root, layer, x, lowp: bool):
    d, h, kvh, hd = (c["hidden_size"], c["num_attention_heads"],
                     c["num_key_value_heads"], c["head_dim"])
    ff, eps, theta = c["intermediate_size"], c["rms_norm_eps"], c["rope_theta"]
    n, t, _ = x.shape
    a = _rms(root, "block0/norm1/scale", x, eps, layer)
    q = _proj(root, "mixer/q", a, d, h * hd, layer, lowp).reshape(n, t, h, hd)
    k = _proj(root, "mixer/k", a, d, kvh * hd, layer, lowp).reshape(n, t, kvh, hd)
    v = _proj(root, "mixer/v", a, d, kvh * hd, layer, lowp).reshape(n, t, kvh, hd)
    o = _attention(_rope(q, theta), _rope(k, theta), v, lowp)
    x = x + _proj(root, "mixer/o", o.reshape(n, t, h * hd), h * hd, d,
                  layer, lowp)
    a = _rms(root, "block0/norm2/scale", x, eps, layer)
    gate = _proj(root, "ffn/gate", a, d, ff, layer, lowp)
    up = _proj(root, "ffn/in", a, d, ff, layer, lowp)
    return x + _proj(root, "ffn/out", jax.nn.silu(gate) * up, ff, d,
                     layer, lowp)


def _embed(c: Dict, root, tokens):
    table = gen.leaf(root, "embed/table",
                     (c["vocab_size"], c["hidden_size"]))
    return table[tokens]


def _head(c: Dict, root, h, lowp: bool):
    x = _rms(root, "final_norm/scale", h, c["rms_norm_eps"])
    return _proj(root, "unembed", x, c["hidden_size"], c["vocab_size"],
                 None, lowp)


def logits_at(c: Dict, seed: int, tokens: np.ndarray,
              positions: np.ndarray, lowp: bool = False) -> jnp.ndarray:
    """Logits (P, vocab) at ``positions`` (P, 2) = (row, position) of the
    (n, T) token batch ``tokens``, from the full causal forward."""
    root = gen.root_key(seed)
    with jax.default_matmul_precision("highest"):
        layer = jax.jit(lambda r, l, x: _layer(c, r, l, x, lowp))
        x = jax.jit(lambda r, t: _embed(c, r, t))(root, jnp.asarray(tokens))
        for l in range(c["num_hidden_layers"]):
            x = layer(root, jnp.int32(l), x)
        sel = x[positions[:, 0], positions[:, 1]]
        del x
        return jax.jit(lambda r, h: _head(c, r, h, lowp))(root, sel)


def pack_sequences(seqs: Sequence[Tuple[np.ndarray, Sequence[int]]],
                   pad_to: int = 256):
    """Batch (prompt, served tokens) pairs for one forward. Row i feeds
    its prompt and all but its last served token; the logits at
    ``prompt_len - 1 + j`` predict served token j. Returns (tokens (n, T),
    positions (P, 2), targets (P,), owner (P,))."""
    lens = [len(p) + len(s) - 1 for p, s in seqs]
    t = -(-max(lens) // pad_to) * pad_to
    tokens = np.zeros((len(seqs), t), np.int32)
    pos, tgt, owner = [], [], []
    for i, (p, s) in enumerate(seqs):
        full = np.concatenate([np.asarray(p, np.int32),
                               np.asarray(s[:-1], np.int32)])
        tokens[i, :len(full)] = full
        for j, tok in enumerate(s):
            pos.append((i, len(p) - 1 + j))
            tgt.append(int(tok))
            owner.append(i)
    return (tokens, np.asarray(pos, np.int32), np.asarray(tgt, np.int32),
            np.asarray(owner, np.int32))


def token_gaps(logits, targets) -> np.ndarray:
    """For each position, how far the target token's logit lies below the
    best logit there (0 when the target is the best)."""
    best = jnp.max(logits, axis=-1)
    got = jnp.take_along_axis(logits, jnp.asarray(targets)[:, None],
                              axis=-1)[:, 0]
    return np.asarray(best - got)


def served_gaps(c: Dict, seed: int, seqs) -> List[float]:
    """The widest gap of each sequence's served tokens under the
    float32 reference."""
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
