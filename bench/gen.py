"""Seeded weights, made on the device, shared by the served model and the
plain reference.

Every weight is a pure function of ``(seed, leaf name, layer)``: the served
model's parameter tree is filled leaf by leaf from it inside one jitted
call, and the reference regenerates each layer from the same function when
it needs it. Nothing here imports the program under test.

Leaf names are the program tree's keys joined by ``/`` (for example
``block0/mixer/q/w_packed/packed``); a stacked leaf carries its layer index
as the leading axis, and layer ``l`` is drawn from ``fold_in(leaf, l)``.

Ternary projections are 2-bit words, 16 weights to a uint32 word along K:
bits ``[2r, 2r+2)`` of word ``q`` hold weight ``16q + r``, code 0 is 0,
1 is +1, 2 is -1. Half of the weights are nonzero, a quarter of each sign
(the paper's 50%-nonzero point). A projection's per-output-channel scale is
drawn from ``U(0.75, 1.25) * sqrt(2 / K)``, which keeps unit-variance
inputs at unit variance, so activations stay finite at any depth.
"""
from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp
import numpy as np

EVEN_BITS = 0x55555555


def root_key(seed: int):
    """A PRNG key from any non-negative seed (64 bits are kept)."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    k = jax.random.key(0)
    k = jax.random.fold_in(k, np.uint32(seed & 0xFFFFFFFF))
    return jax.random.fold_in(k, np.uint32((seed >> 32) & 0xFFFFFFFF))


def leaf_key(root, name: str):
    return jax.random.fold_in(root, np.uint32(zlib.crc32(name.encode())))


def ternary_words(key, shape):
    """(kw, n) uint32 words of random 2-bit codes: P(0)=1/2, P(+-1)=1/4."""
    a, b = jax.random.bits(key, (2, *shape), jnp.uint32)
    even = jnp.uint32(EVEN_BITS)
    plus = a & b & even
    minus = (a & ~b & even) << 1
    return plus | minus


def channel_scale(key, n: int, k: int):
    u = jax.random.uniform(key, (n,), jnp.float32, 0.75, 1.25)
    return u * jnp.float32(np.sqrt(2.0 / k))


def norm_scale(key, n: int):
    return jax.random.uniform(key, (n,), jnp.float32, 0.8, 1.2)


def embedding(key, shape):
    return jax.random.normal(key, shape, jnp.float32)


def leaf(root, name: str, shape, layer=None, k_in: int = 0):
    """The weight named ``name`` (one layer's slice when ``layer`` is not
    None). ``k_in`` is the logical K of a projection's scale."""
    key = leaf_key(root, name)
    if layer is not None:
        key = jax.random.fold_in(key, layer)
    kind = name.rsplit("/", 1)[-1]
    if name.endswith("w_packed/packed"):
        return ternary_words(key, shape)
    if name.endswith("w_packed/scale"):
        return channel_scale(key, shape[-1], k_in)
    if name == "embed/table":
        return embedding(key, shape)
    if kind == "scale":
        return norm_scale(key, shape[-1])
    raise ValueError(f"no generator for leaf {name!r}")
