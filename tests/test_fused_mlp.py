"""Fused ternary kernel pass (DESIGN.md §12): double-buffered
tile-skipping, the fused MLP lowering, fusion autotune keys, rooflines.

Every equality here is *bitwise* (``np.array_equal``), not allclose — the
fused/double-buffered paths are pure scheduling changes over the same
f32 accumulation order, so exact equality is the contract the registry
relies on to dispatch them transparently.
"""
from __future__ import annotations

import os
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import formats, weights
from repro.kernels import ops
from repro.kernels.autotune import Autotuner, BlockConfig, FusedBlockConfig


def _rt(rng, k, n, density=0.25):
    return formats.random_ternary(rng, k, n, density)


def _mlp_weights(fmt, k=256, ff=384, n=128, *, scale=True, bias=True,
                 seed=0):
    rng = np.random.default_rng(seed)

    def pk(w):
        kw = dict(tile_k=64, tile_n=32) if fmt == "tiled" else {}
        sc = (np.abs(rng.standard_normal(w.shape[1])) + 0.5).astype(
            np.float32) if scale else None
        b = rng.standard_normal(w.shape[1]).astype(np.float32) if bias \
            else None
        return weights.pack(w, fmt, scale=sc, bias=b, **kw)

    return pk(_rt(rng, k, ff)), pk(_rt(rng, ff, n)), pk(_rt(rng, k, ff))


# ---------------------------------------------------------------------------
# Double-buffered skip kernel == skip == dense, bitwise
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("density", [0.5, 0.125, 0.0])
def test_skip_db_bit_exact(density):
    rng = np.random.default_rng(2)
    m, k, n = 16, 256, 128
    w = formats.random_tile_ternary(rng, k, n, 64, 32, density)
    wc = weights.pack(w, "tiled", tile_k=64, tile_n=32)
    x = jnp.asarray(rng.standard_normal((m, k)), jnp.float32)
    y_db = ops.ternary_gemm(x, wc, impl="skip_db")
    y_skip = ops.ternary_gemm(x, wc, impl="skip")
    y_dense = ops.ternary_gemm(x, wc, block_n=32, block_k=64, impl="dense")
    assert np.array_equal(np.asarray(y_db), np.asarray(y_skip))
    assert np.array_equal(np.asarray(y_db), np.asarray(y_dense))


def test_skip_db_epilogue_and_grad():
    rng = np.random.default_rng(3)
    m, k, n = 8, 128, 64
    w = formats.random_tile_ternary(rng, k, n, 32, 16, 0.25)
    sc = (np.abs(rng.standard_normal(n)) + 0.5).astype(np.float32)
    b = rng.standard_normal(n).astype(np.float32)
    wc = weights.pack(w, "tiled", tile_k=32, tile_n=16, scale=sc, bias=b)
    x = jnp.asarray(rng.standard_normal((m, k)), jnp.float32)
    y_db = ops.ternary_gemm(x, wc, fuse_prelu=True, impl="skip_db")
    y_skip = ops.ternary_gemm(x, wc, fuse_prelu=True, impl="skip")
    assert np.array_equal(np.asarray(y_db), np.asarray(y_skip))
    g = jax.grad(lambda xx: ops.ternary_gemm(xx, wc, impl="skip_db").sum())(x)
    g0 = jax.grad(lambda xx: ops.ternary_gemm(xx, wc, impl="skip").sum())(x)
    np.testing.assert_allclose(np.asarray(g), np.asarray(g0), rtol=1e-5)


def test_skip_db_outranks_skip_in_auto_dispatch():
    rng = np.random.default_rng(4)
    w = formats.random_tile_ternary(rng, 128, 64, 32, 16, 0.0625)
    wc = weights.pack(w, "tiled", tile_k=32, tile_n=16)
    assert ops.ternary_gemm_plan(wc, 8).impl == "skip_db"


# ---------------------------------------------------------------------------
# Fused MLP == unfused chain, bitwise
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("fmt", ["dense2bit", "tiled"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_fused_mlp_bit_exact(fmt, dtype):
    wi, wo, wg = _mlp_weights(fmt)
    rng = np.random.default_rng(10)
    x = jnp.asarray(rng.standard_normal((12, wi.k)), dtype)
    y_fused = ops.fused_mlp(x, wi, wo, wg, impl="pallas")
    y_chain = ops.fused_mlp(x, wi, wo, wg, impl="chain")
    assert y_fused.dtype == x.dtype and y_fused.shape == (12, wo.n)
    assert np.array_equal(np.asarray(y_fused), np.asarray(y_chain))


@pytest.mark.parametrize("activation", ["silu", "relu", "none"])
def test_fused_mlp_ungated_activations(activation):
    wi, wo, _ = _mlp_weights("dense2bit", scale=False, bias=False)
    rng = np.random.default_rng(11)
    x = jnp.asarray(rng.standard_normal((8, wi.k)), jnp.float32)
    y_fused = ops.fused_mlp(x, wi, wo, activation=activation, impl="pallas")
    y_chain = ops.fused_mlp(x, wi, wo, activation=activation, impl="chain")
    assert np.array_equal(np.asarray(y_fused), np.asarray(y_chain))


@pytest.mark.parametrize("phase", ops.SERVING_PHASES)
def test_fused_mlp_bit_exact_under_phases(phase):
    wi, wo, wg = _mlp_weights("dense2bit")
    rng = np.random.default_rng(12)
    x = jnp.asarray(rng.standard_normal((4, wi.k)), jnp.float32)
    with ops.serving_phase(phase):
        y_fused = ops.fused_mlp(x, wi, wo, wg, impl="pallas")
        y_chain = ops.fused_mlp(x, wi, wo, wg, impl="chain")
    assert np.array_equal(np.asarray(y_fused), np.asarray(y_chain))


def test_fused_mlp_misaligned_shapes():
    # nothing divides the default blocks: padding must stay bit-invisible
    wi, wo, wg = _mlp_weights("dense2bit", k=208, ff=176, n=144)
    rng = np.random.default_rng(13)
    x = jnp.asarray(rng.standard_normal((5, 208)), jnp.float32)
    y_fused = ops.fused_mlp(x, wi, wo, wg, impl="pallas")
    y_chain = ops.fused_mlp(x, wi, wo, wg, impl="chain")
    assert y_fused.shape == (5, 144)
    assert np.array_equal(np.asarray(y_fused), np.asarray(y_chain))


def test_fused_mlp_auto_and_bitplane_fallback():
    # auto on a fusable pair resolves the pallas lowering
    wi, wo, wg = _mlp_weights("dense2bit")
    plan = ops.fused_mlp_plan(wi, wo, wg, m=8)
    assert plan.impl == "pallas" and plan.gated
    up, down = plan.sub_plans()
    assert (up.block_n, up.block_k) == (plan.block_n1, plan.block_k1)
    # bitplane containers are not fusable -> the chain lowering serves them
    bi, bo, bg = _mlp_weights("bitplane")
    plan_b = ops.fused_mlp_plan(bi, bo, bg, m=8)
    assert plan_b.impl == "chain"
    rng = np.random.default_rng(14)
    x = jnp.asarray(rng.standard_normal((8, bi.k)), jnp.float32)
    y = ops.fused_mlp(x, bi, bo, bg)     # dispatches, no error
    assert y.shape == (8, bo.n)


def test_fused_mlp_validates_chain_k():
    wi, _, _ = _mlp_weights("dense2bit", k=256, ff=384, n=128)
    wo_bad, _, _ = _mlp_weights("dense2bit", k=256, ff=384, n=128, seed=1)
    with pytest.raises(ValueError, match="down projection expects"):
        ops.fused_mlp_plan(wi, wo_bad, m=8)


def test_fused_mlp_grad_matches_chain():
    wi, wo, wg = _mlp_weights("dense2bit")
    rng = np.random.default_rng(15)
    x = jnp.asarray(rng.standard_normal((8, wi.k)), jnp.float32)
    g = jax.grad(lambda xx: ops.fused_mlp(xx, wi, wo, wg,
                                          impl="pallas").sum())(x)
    g0 = jax.grad(lambda xx: ops.fused_mlp(xx, wi, wo, wg,
                                           impl="chain").sum())(x)
    assert np.all(np.isfinite(np.asarray(g)))
    np.testing.assert_allclose(np.asarray(g), np.asarray(g0),
                               rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# layers.mlp_apply adoption + engine-style plan warmup
# ---------------------------------------------------------------------------

def _tiny_cfg(**over):
    from repro.configs.base import ModelConfig
    base = dict(name="t", family="dense", num_layers=1, d_model=256,
                num_heads=4, num_kv_heads=4, d_ff=384, vocab_size=512,
                quantization="ternary_packed", ternary_min_dim=64,
                ternary_kernel="pallas")
    base.update(over)
    return ModelConfig(**base)


def test_mlp_apply_adopts_fused_lowering():
    from repro.models import layers
    wi, wo, wg = _mlp_weights("dense2bit")
    params = {"in": {"w_packed": wi}, "gate": {"w_packed": wg},
              "out": {"w_packed": wo}}
    rng = np.random.default_rng(16)
    x = jnp.asarray(rng.standard_normal((2, 3, wi.k)), jnp.float32)
    y_fused = layers.mlp_apply(params, x, _tiny_cfg())
    y_off = layers.mlp_apply(params, x, _tiny_cfg(fused_mlp="off"))
    assert y_fused.shape == y_off.shape == (2, 3, wo.n)
    assert np.array_equal(np.asarray(y_fused), np.asarray(y_off))
    # fused path requires the full packed triple; a latent MLP falls back
    assert layers._fused_mlp_weights({"in": {}, "out": {}, "gate": {}},
                                     _tiny_cfg()) is None


def test_precompute_fused_plans_warms_phase_keys():
    wi, wo, wg = _mlp_weights("dense2bit")
    tree = {"blk": {"mlp": {"in": {"w_packed": wi}, "gate": {"w_packed": wg},
                            "out": {"w_packed": wo}}}}
    plans = ops.precompute_fused_plans(tree, prefill_ms=(8, 64),
                                       decode_ms=(4,), verify_ms=(5,),
                                       chunk_ms=(16,))
    assert len(plans) == 5
    assert {p.phase for p in plans.values()} == set(ops.SERVING_PHASES)
    assert all(p.gated for p in plans.values())
    assert all(p.impl == "pallas" for p in plans.values())


def test_precompute_fused_plans_stacked_containers():
    """Scan-stacked (L, K/16, N) containers plan through their layer-0
    slice — the 2-D per-layer view each scan step dispatches on — so the
    warmed plans match the runtime lowering (pallas, not chain)."""
    wi, wo, wg = _mlp_weights("dense2bit")
    stack = jax.tree_util.tree_map(lambda a: jnp.stack([a, a]), wi)
    assert stack.packed.ndim == 3
    tree = {"blk": {"mlp": {
        "in": {"w_packed": jax.tree_util.tree_map(
            lambda a: jnp.stack([a, a]), wi)},
        "gate": {"w_packed": jax.tree_util.tree_map(
            lambda a: jnp.stack([a, a]), wg)},
        "out": {"w_packed": jax.tree_util.tree_map(
            lambda a: jnp.stack([a, a]), wo)}}}}
    plans = ops.precompute_fused_plans(tree, decode_ms=(4,))
    assert len(plans) == 1
    assert all(p.impl == "pallas" for p in plans.values())


# ---------------------------------------------------------------------------
# Autotuner fusion keys
# ---------------------------------------------------------------------------

def test_fused_cache_key_roundtrip():
    path = os.path.join(tempfile.mkdtemp(prefix="repro_fused_"), "c.json")
    tuner = Autotuner(path=path, mode="model")
    cfg = tuner.lookup_fused(32, 256, 384, 128, phase="decode")
    assert isinstance(cfg, FusedBlockConfig)
    # composed from the per-GEMM lookups -> fused/unfused tiling agrees
    up = tuner.lookup(32, 256, 384, sparsity=1.0, impl="dense",
                      phase="decode")
    assert (cfg.block_n1, cfg.block_k1) == (up.block_n, up.block_k)
    assert cfg.up() == BlockConfig(cfg.block_m, cfg.block_n1, cfg.block_k1)
    reloaded = Autotuner(path=path, mode="model")
    assert reloaded.lookup_fused(32, 256, 384, 128, phase="decode") == cfg
    # 5-int fused entries and 3-int gemm entries coexist in one cache file
    assert any(isinstance(v, FusedBlockConfig)
               for v in reloaded.entries().values())
    assert any(isinstance(v, BlockConfig)
               for v in reloaded.entries().values())


def test_fused_key_pins_to_chain_tiles():
    path = os.path.join(tempfile.mkdtemp(prefix="repro_fused_"), "c.json")
    tuner = Autotuner(path=path, mode="model")
    a = tuner.lookup_fused(32, 256, 384, 128, fixed_n1=32, fixed_k1=64)
    assert (a.block_n1, a.block_k1) == (32, 64)
    b = tuner.lookup_fused(32, 256, 384, 128)
    assert isinstance(b, FusedBlockConfig)   # re-resolve, pins dropped


# ---------------------------------------------------------------------------
# Rooflines
# ---------------------------------------------------------------------------

def test_gemm_plan_roofline():
    rng = np.random.default_rng(20)
    wc = weights.pack(_rt(rng, 256, 128), "dense2bit")
    rl = ops.ternary_gemm_plan(wc, 32).roofline()
    assert rl["flops"] == 2 * 32 * 256 * 128
    assert rl["bound"] in ("compute", "memory")
    assert 0 < rl["achieved_flops"] <= rl["ceiling_flops"] <= rl["peak_flops"]
    assert 0.0 <= rl["headroom"] < 1.0


def test_fused_plan_roofline_beats_chain_on_bytes():
    wi, wo, wg = _mlp_weights("dense2bit", k=512, ff=2048, n=512)
    rl = ops.fused_mlp_plan(wi, wo, wg, m=256, impl="pallas").roofline()
    # fused never spills h to HBM -> strictly fewer modeled bytes
    assert rl["bytes"] < rl["unfused_bytes"]
    assert rl["fused_speedup"] > 1.0
