"""Regression tests for the §Perf features: transpose-free cache layouts +
delta cache updates (A6/A7), head padding + repeat-KV (B1), packed MoE
experts (C1). Each must preserve the model function exactly (f32)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import LM


def _decode_consistency(cfg, steps=16):  # 16+16: SSD chunk-divisible
    m = LM(cfg)
    params = m.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    s = 16 + steps
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (2, s)), jnp.int32)
    x, _, _ = m.forward(params, {"tokens": toks})
    full = np.asarray(m._logits(params, x), np.float32)
    cache, _ = m.prefill(params, {"tokens": toks[:, :16]}, s,
                         cache_dtype=jnp.float32)
    dec = jax.jit(m.decode_step)
    errs = []
    for t in range(16, s):
        lg, cache = dec(params, cache, toks[:, t:t + 1])
        errs.append(float(np.abs(np.asarray(lg[:, 0], np.float32)
                                 - full[:, t]).max()))
    return max(errs)


@pytest.mark.parametrize("arch,overrides", [
    ("mistral-nemo-12b", {}),
    ("mixtral-8x22b", {"sliding_window": 8}),      # rolling SWA + delta
    ("jamba-v0.1-52b", {}),                        # hybrid: attn delta + ssm
])
def test_opt_cache_layout_delta_updates(arch, overrides):
    """cache_layout=opt (K (B,KV,S,hd) / V (B,KV,hd,S) + delta commits)
    must decode identically to the full forward."""
    cfg = get_config(arch, reduced=True, dtype="float32",
                     cache_layout="opt", **overrides)
    assert _decode_consistency(cfg) < 2e-2


def test_head_pad_preserves_function_shape():
    """Padded q-heads: extra heads exist, forward finite, decode == forward
    (pad heads participate but with learned weights; function class is a
    superset — here we check the machinery, not equivalence to unpadded)."""
    cfg = get_config("deepseek-coder-33b", reduced=True, dtype="float32",
                     num_heads=6, num_kv_heads=2, head_pad=2,
                     gqa_repeat_kv=True)
    assert _decode_consistency(cfg) < 2e-2


def test_repeat_kv_equals_gqa():
    """repeat_kv is a pure re-expression of GQA: logits must be identical
    with and without it."""
    base = get_config("mistral-nemo-12b", reduced=True, dtype="float32")
    rep = get_config("mistral-nemo-12b", reduced=True, dtype="float32",
                     gqa_repeat_kv=True)
    m1, m2 = LM(base), LM(rep)
    params = m1.init(jax.random.PRNGKey(0))
    toks = jnp.arange(64, dtype=jnp.int32).reshape(2, 32) % base.vocab_size
    x1, _, _ = m1.forward(params, {"tokens": toks})
    x2, _, _ = m2.forward(params, {"tokens": toks})
    np.testing.assert_allclose(np.asarray(x1, np.float32),
                               np.asarray(x2, np.float32),
                               rtol=1e-4, atol=1e-4)


def test_pallas_attn_impl_matches_flash():
    """attn_impl='pallas' (the VMEM flash kernel, interpret on CPU) must
    produce the same logits as the XLA flash path."""
    base = get_config("mistral-nemo-12b", reduced=True, dtype="float32")
    pallas = get_config("mistral-nemo-12b", reduced=True, dtype="float32",
                        attn_impl="pallas")
    m1, m2 = LM(base), LM(pallas)
    params = m1.init(jax.random.PRNGKey(0))
    toks = jnp.arange(64, dtype=jnp.int32).reshape(2, 32) % base.vocab_size
    x1, _, _ = m1.forward(params, {"tokens": toks})
    x2, _, _ = m2.forward(params, {"tokens": toks})
    np.testing.assert_allclose(np.asarray(x1, np.float32),
                               np.asarray(x2, np.float32),
                               rtol=1e-4, atol=1e-4)


def test_blocked_moe_routing_matches_global():
    """moe_route_blocks (per-DP-shard dispatch, §Perf D1) must equal global
    routing when capacity is not binding."""
    base = get_config("mixtral-8x22b", reduced=True, dtype="float32",
                      capacity_factor=8.0)
    blocked = get_config("mixtral-8x22b", reduced=True, dtype="float32",
                         capacity_factor=8.0, moe_route_blocks=4)
    m1, m2 = LM(base), LM(blocked)
    params = m1.init(jax.random.PRNGKey(0))
    toks = jnp.arange(128, dtype=jnp.int32).reshape(4, 32) % base.vocab_size
    x1, _, _ = m1.forward(params, {"tokens": toks})
    x2, _, _ = m2.forward(params, {"tokens": toks})
    np.testing.assert_allclose(np.asarray(x1, np.float32),
                               np.asarray(x2, np.float32),
                               rtol=1e-4, atol=1e-4)


def test_packed_moe_matches_qat(monkeypatch):
    """MoE experts in the 2-bit packed serving format == QAT forward: the
    packed banks run through the grouped expert GEMM (gate, up and down),
    dropless, and no bank is decoded whole."""
    import dataclasses
    from repro.core import weights
    from repro.kernels import ops as kops
    from repro.models import layers as L
    cfg = get_config("mixtral-8x22b", reduced=True, dtype="float32",
                     ternary_min_dim=64, quantization="ternary",
                     d_model=128, d_ff_expert=128)
    m = LM(cfg)
    params = m.init(jax.random.PRNGKey(0))
    toks = jnp.arange(64, dtype=jnp.int32).reshape(2, 32) % cfg.vocab_size
    x1, _, _ = m.forward(params, {"tokens": toks})

    # one call packs expert banks (per layer, per expert) and linears alike
    packed = L.pack_params(params, cfg)
    moe_node = packed["block0"]["ffn"]
    assert isinstance(moe_node["w_in"], weights.TernaryWeight)
    assert moe_node["w_in"].packed.ndim == 4       # (L, E, K/16, N) leaves
    cfg2 = dataclasses.replace(cfg, quantization="ternary_packed")
    m2 = LM(cfg2)
    calls = []
    grouped = kops.moe_expert_gemm
    monkeypatch.setattr(kops, "moe_expert_gemm",
                        lambda *a, **k: calls.append(1) or grouped(*a, **k))
    monkeypatch.setattr(weights.Dense2Bit, "materialize", None)
    x2, _, _ = m2.forward(packed, {"tokens": toks})
    assert len(calls) == 3
    np.testing.assert_allclose(np.asarray(x1, np.float32),
                               np.asarray(x2, np.float32),
                               rtol=1e-3, atol=1e-3)
