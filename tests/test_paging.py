"""Paged KV-cache subsystem tests (DESIGN.md §9).

Pins the three correctness contracts:

1. the Pallas paged decode-attention kernel is **bit-exact** vs its
   pure-JAX reference across page-size / window / GQA / kv-dtype variants,
   and the Pallas window kernel matches that reference over the window's
   flattened rows;
2. the ``jax`` lowering (the engine's off-TPU path) is **bit-identical**
   to ``models.attention.naive_attention`` on the gathered cache — the
   foundation of the paged-vs-dense token-exactness guarantee;
3. the same request stream through ``cache="dense"`` and ``cache="paged"``
   produces **identical tokens**, including page evict→reuse churn,
   shared-prefix admissions with copy-on-write, and OOM-pressure
   defer/preempt recovery.

Plus host-side unit tests for PagePool/PrefixCache/SlotPool bookkeeping.
"""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.kernels import ops as kops
from repro.launch import serve
from repro.models import LM, attention
from repro.paging import Int8Pages, PagePool, PrefixCache, page_keys
from repro.paging import kernels as pk
from repro.paging.kernels import (paged_decode_attention_jax,
                                  paged_decode_attention_pallas,
                                  paged_decode_attention_ref,
                                  paged_window_attention_pallas)
from repro.serving import ContinuousScheduler, SlotPool


def _cfg(**overrides):
    return get_config("ternary-paper", reduced=True, num_layers=2,
                      **overrides)


def _workload(cfg, n, prompt_len=16, seed=0, lens=(2, 9)):
    rng = np.random.default_rng(seed)
    prompts = rng.integers(0, cfg.vocab_size,
                           size=(n, prompt_len)).astype(np.int32)
    gens = [int(g) for g in rng.integers(lens[0], lens[1], size=n)]
    return prompts, gens


def _run_engine(cfg, params, prompts, gens, **engine_kw):
    eng = ContinuousScheduler(cfg, **engine_kw)
    eng.load(params)
    reqs = [eng.submit(p, g) for p, g in zip(prompts, gens)]
    metrics = eng.run()
    return [list(r.tokens) for r in reqs], metrics


# ---------------------------------------------------------------------------
# Kernel-level exactness
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("heads,kv_heads", [(4, 2), (4, 4)])
@pytest.mark.parametrize("page_size", [4, 8])
@pytest.mark.parametrize("window", [0, 5])
@pytest.mark.parametrize("kv_dtype", ["f32", "int8"])
def test_paged_kernel_bitexact_vs_ref(heads, kv_heads, page_size, window,
                                      kv_dtype):
    """Pallas kernel (interpret off-TPU) == pure-JAX reference, bitwise,
    with garbage padding entries in the block table masked by lengths."""
    rng = np.random.default_rng(0)
    b, p, t, hd = 3, 10, 4, 16
    q = jnp.asarray(rng.standard_normal((b, heads, hd)), jnp.float32)
    kp = jnp.asarray(rng.standard_normal((p, page_size, kv_heads, hd)),
                     jnp.float32)
    vp = jnp.asarray(rng.standard_normal((p, page_size, kv_heads, hd)),
                     jnp.float32)
    if kv_dtype == "int8":
        kp, vp = Int8Pages.quantize(kp), Int8Pages.quantize(vp)
    table = jnp.asarray(rng.integers(0, p, size=(b, t)), jnp.int32)
    lengths = jnp.asarray(rng.integers(1, t * page_size + 1, size=(b,)),
                          jnp.int32)
    out_kernel = paged_decode_attention_pallas(q, kp, vp, table, lengths,
                                               window=window)
    out_ref = paged_decode_attention_ref(q, kp, vp, table, lengths,
                                         window=window)
    np.testing.assert_array_equal(np.asarray(out_kernel),
                                  np.asarray(out_ref))
    # and the ref agrees with the batched jax lowering numerically
    out_jax = paged_decode_attention_jax(q, kp, vp, table, lengths,
                                         window=window)
    np.testing.assert_allclose(np.asarray(out_jax), np.asarray(out_ref),
                               rtol=2e-5, atol=2e-5)


def _window_case(heads, kv_heads, page_size, s, kv_dtype, seed=0):
    """A (B=3, S) window over 4 pages a row: row 0 mid-sequence, row 1
    ending on the last page, row 2 a pad row (all-zeros block table, the
    window at position 0, as the chunker packs it)."""
    rng = np.random.default_rng(seed)
    b, p, t, hd = 3, 10, 4, 16
    q = jnp.asarray(rng.standard_normal((b, s, heads, hd)), jnp.float32)
    kp = jnp.asarray(rng.standard_normal((p, page_size, kv_heads, hd)),
                     jnp.float32)
    vp = jnp.asarray(rng.standard_normal((p, page_size, kv_heads, hd)),
                     jnp.float32)
    if kv_dtype == "int8":
        kp, vp = Int8Pages.quantize(kp), Int8Pages.quantize(vp)
    elif kv_dtype == "bf16":
        kp, vp = kp.astype(jnp.bfloat16), vp.astype(jnp.bfloat16)
    table = jnp.asarray(rng.integers(1, p, size=(b, t)), jnp.int32)
    table = table.at[2].set(0)
    lengths = jnp.asarray([page_size + 2, t * page_size - s + 1, 1],
                          jnp.int32)
    return q, kp, vp, table, lengths


def _flat_ref(q, kp, vp, table, lengths, window, layer=0):
    """The window as (B·S) single-query rows through the decode
    kernel's reference: token j of row b has ``lengths[b] + j`` keys."""
    b, s, h, hd = q.shape
    return paged_decode_attention_ref(
        q.reshape(b * s, h, hd), kp, vp, jnp.repeat(table, s, axis=0),
        (lengths[:, None] + jnp.arange(s)).reshape(-1), layer=layer,
        window=window).reshape(q.shape)


@pytest.mark.parametrize("heads,kv_heads", [(4, 4), (4, 2)])
@pytest.mark.parametrize("page_size,s", [(4, 3), (8, 5)])
@pytest.mark.parametrize("window", [0, 5])
@pytest.mark.parametrize("kv_dtype", ["f32", "int8"])
def test_paged_window_kernel_matches_flattened_ref(heads, kv_heads,
                                                   page_size, s, window,
                                                   kv_dtype):
    """The window kernel (interpret off-TPU) == the decode reference over
    the B·S flattened rows: GQA groups of 1 and 2, windows that cross a
    page boundary with S not dividing the page size, a pad row, sliding
    windows, f32 and int8 pages. Each (row, page) does all S tokens'
    matmuls at once, so accumulation order may differ in the last bit."""
    q, kp, vp, table, lengths = _window_case(heads, kv_heads, page_size, s,
                                             kv_dtype)
    out = paged_window_attention_pallas(q, kp, vp, table, lengths,
                                        window=window)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(_flat_ref(q, kp, vp, table, lengths,
                                              window)),
        rtol=2e-5, atol=2e-5)


def _stacked_case(kv_dtype, groups=3):
    """A window case over a stacked pool of ``groups`` layers with
    distinct pages. Row 0's table entries past its length and all of row
    2's (a pad row) point at the trash page 0."""
    q, _, _, table, lengths = _window_case(4, 2, 4, 3, kv_dtype)
    layers = [_window_case(4, 2, 4, 3, kv_dtype, seed=10 + g)[1:3]
              for g in range(groups)]
    kp = jax.tree.map(lambda *a: jnp.stack(a), *[k for k, _ in layers])
    vp = jax.tree.map(lambda *a: jnp.stack(a), *[v for _, v in layers])
    table = table.at[0, 2:].set(0)
    if kv_dtype == "bf16":
        q = q.astype(jnp.bfloat16)
    return q, kp, vp, table, lengths


def _paged_call(impl, form, q, kp, vp, table, lengths, window, **layer):
    if form == "decode":
        fn = {"pallas": paged_decode_attention_pallas,
              "jax": paged_decode_attention_jax,
              "ref": paged_decode_attention_ref}[impl]
        return fn(q[:, 0], kp, vp, table, lengths, window=window, **layer)
    if impl == "ref":
        return _flat_ref(q, kp, vp, table, lengths, window, **layer)
    return kops.paged_window_attention(q, kp, vp, table, lengths,
                                       window=window, impl=impl, **layer)


@pytest.mark.parametrize("impl", ["pallas", "jax", "ref"])
@pytest.mark.parametrize("form", ["decode", "window"])
@pytest.mark.parametrize("window", [0, 5])
@pytest.mark.parametrize("kv_dtype", ["bf16", "int8"])
def test_layer_indexed_lowerings_equal_the_layers_slice(impl, form, window,
                                                        kv_dtype):
    """Each lowering (Pallas in interpret mode, the jax gather, the
    reference) reading group ``g`` of a stacked (G, P, ps, KV, hd) pool
    equals the same call on ``pages[g]`` alone, bit for bit, for every
    g: decode rows and 3-token windows, bf16 and int8 pages, a sliding
    window, dead table entries on the trash page."""
    q, kp, vp, table, lengths = _stacked_case(kv_dtype)
    for g in range(3):
        one_k = jax.tree.map(lambda a: a[g], kp)
        one_v = jax.tree.map(lambda a: a[g], vp)
        got = _paged_call(impl, form, q, kp, vp, table, lengths, window,
                          layer=g)
        want = _paged_call(impl, form, q, one_k, one_v, table, lengths,
                           window)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("impl", ["jax", "pallas"])
@pytest.mark.parametrize("sq", [1, 3])
@pytest.mark.parametrize("kv_dtype", [None, "int8"])
def test_paged_decode_writes_its_layer_of_the_stack_only(impl, sq,
                                                         kv_dtype):
    """``_paged_decode`` at layer ``g`` of a stacked pool scatters the new
    tokens into group ``g`` alone: every other group's pages stay bit for
    bit as they were, and group ``g`` and the output equal the same step
    on a one-group pool holding ``g``'s pages."""
    cfg = _cfg(paged_attn_impl=impl)
    params, _ = attention.attn_init(jax.random.PRNGKey(0), cfg)
    b, ps, t, groups = 2, 4, 3, 3
    one = attention.init_paged_kv_cache(cfg, 1 + b * t, ps,
                                        dtype=jnp.float32,
                                        kv_dtype=kv_dtype)
    keys = jax.random.split(jax.random.PRNGKey(1), 8)
    pool = {n: jax.tree.map(
        lambda a, key=key: jax.random.normal(
            key, (groups,) + a.shape, jnp.float32).astype(a.dtype)
        if a.dtype != jnp.int8 else jax.random.randint(
            key, (groups,) + a.shape, -127, 128, jnp.int32).astype(a.dtype),
        one[n]) for n, key in zip(one, keys[:2])}
    table = 1 + jnp.arange(b * t, dtype=jnp.int32).reshape(b, t)
    pos = jnp.asarray([2, 5], jnp.int32)
    x = jax.random.normal(keys[2], (b, sq, cfg.d_model), jnp.float32)
    q = jax.random.normal(keys[3], (b, sq, cfg.num_heads, cfg.head_dim))
    k = jax.random.normal(keys[4], (b, sq, cfg.num_kv_heads, cfg.head_dim))
    v = jax.random.normal(keys[5], (b, sq, cfg.num_kv_heads, cfg.head_dim))
    for g in range(groups):
        y, new = attention._paged_decode(params, x, cfg, q, k, v, pool, pos,
                                         table, g)
        alone = jax.tree.map(lambda a: a[g:g + 1], pool)
        y1, new1 = attention._paged_decode(params, x, cfg, q, k, v, alone,
                                           pos, table, 0)
        np.testing.assert_array_equal(np.asarray(y), np.asarray(y1))
        for a, n, n1 in zip(jax.tree.leaves(pool), jax.tree.leaves(new),
                            jax.tree.leaves(new1)):
            a, n, n1 = np.asarray(a), np.asarray(n), np.asarray(n1)
            others = [o for o in range(groups) if o != g]
            np.testing.assert_array_equal(n[others], a[others])
            np.testing.assert_array_equal(n[g], n1[0])
            assert not np.array_equal(n[g], a[g])


def test_paged_window_kernel_query_tiles(monkeypatch):
    """Past the VMEM budget the query axis splits into tiles (grid
    (B, tiles, T)); each tile's page walk and causal offsets still match
    the flattened reference."""
    monkeypatch.setattr(pk, "WINDOW_VMEM_BUDGET", 1)
    assert pk._window_tile(8, 4, 2, 16) == 4       # 16 rows a tile
    assert pk._window_tile(5, 2, 2, 16) == 5       # no aligned divisor
    q, kp, vp, table, lengths = _window_case(8, 2, 4, 8, "f32", seed=3)
    lengths = lengths.at[1].set(6)
    for window in (0, 5):
        out = paged_window_attention_pallas(q, kp, vp, table, lengths,
                                            window=window)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(_flat_ref(q, kp, vp, table,
                                                  lengths, window)),
            rtol=2e-5, atol=2e-5)


def test_paged_window_jax_lowering_is_the_flattened_decode():
    """The ``jax`` lowering has no window form: the window entry runs
    today's flatten (block table repeated, lengths + j) through
    ``paged_decode_attention_jax``, bit for bit — what paged-vs-dense
    token exactness on CPU rests on. The Pallas entry agrees to 2e-5."""
    reg = kops.paged_attention_registry()
    assert reg["jax"].window_fn is None
    assert reg["pallas"].window_fn is paged_window_attention_pallas
    q, kp, vp, table, lengths = _window_case(4, 2, 4, 3, "bf16", seed=1)
    q = q.astype(jnp.bfloat16)
    b, s, h, hd = q.shape
    got = kops.paged_window_attention(q, kp, vp, table, lengths, impl="jax")
    want = paged_decode_attention_jax(
        q.reshape(b * s, h, hd), kp, vp, jnp.repeat(table, s, axis=0),
        (lengths[:, None] + jnp.arange(s)).reshape(-1)).reshape(q.shape)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    # f32 pages: the jax lowering rounds probabilities to the pages' dtype
    q, kp, vp, table, lengths = _window_case(4, 2, 4, 3, "f32", seed=1)
    np.testing.assert_allclose(
        np.asarray(kops.paged_window_attention(q, kp, vp, table, lengths,
                                               impl="pallas")),
        np.asarray(kops.paged_window_attention(q, kp, vp, table, lengths,
                                               impl="jax")),
        rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("sq", [1, 3])
def test_paged_decode_dispatch_by_window_width(monkeypatch, sq):
    """``_paged_decode`` takes the decode entry for one token a row and
    the window entry for S > 1."""
    cfg = _cfg()
    calls = []
    for name in ("paged_decode_attention", "paged_window_attention"):
        fn = getattr(kops, name)
        monkeypatch.setattr(
            kops, name, lambda *a, _n=name, _f=fn, **k: (
                calls.append(_n), _f(*a, **k))[1])
    params, _ = attention.attn_init(jax.random.PRNGKey(0), cfg)
    b, ps, t = 2, 4, 3
    cache = jax.tree.map(lambda a: a[None], attention.init_paged_kv_cache(
        cfg, 1 + b * t, ps, dtype=jnp.float32))
    table = 1 + jnp.arange(b * t, dtype=jnp.int32).reshape(b, t)
    pos = jnp.asarray([2, 5], jnp.int32)
    x = jnp.ones((b, sq, cfg.d_model), jnp.float32)
    q = jnp.ones((b, sq, cfg.num_heads, cfg.head_dim), jnp.float32)
    kv = jnp.ones((b, sq, cfg.num_kv_heads, cfg.head_dim), jnp.float32)
    attention._paged_decode(params, x, cfg, q, kv, kv, cache, pos, table,
                            0)
    assert calls[0] == ("paged_decode_attention" if sq == 1
                        else "paged_window_attention")
    assert ("paged_window_attention" in calls) == (sq > 1)


def test_paged_jax_impl_bitexact_vs_naive():
    """The engine's paged attention must be line-identical math to the
    dense decode path: gather + ``naive_attention`` == the jax lowering,
    bitwise (this is what makes paged serving token-exact vs dense)."""
    rng = np.random.default_rng(1)
    b, p, t, ps, h, kv, hd = 2, 6, 3, 8, 4, 2, 16
    q = jnp.asarray(rng.standard_normal((b, h, hd)), jnp.bfloat16)
    kp = jnp.asarray(rng.standard_normal((p, ps, kv, hd)), jnp.bfloat16)
    vp = jnp.asarray(rng.standard_normal((p, ps, kv, hd)), jnp.bfloat16)
    table = jnp.asarray(rng.integers(0, p, size=(b, t)), jnp.int32)
    lengths = jnp.asarray([10, 20], jnp.int32)
    out = paged_decode_attention_jax(q, kp, vp, table, lengths)
    ks = kp[table].reshape(b, t * ps, kv, hd)
    vs = vp[table].reshape(b, t * ps, kv, hd)
    ref = attention.naive_attention(
        q[:, None], ks, vs, causal=False, window=0,
        q_offset=lengths - 1, kv_valid_len=lengths)[:, 0]
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


def test_paged_attn_registry_dispatch():
    reg = kops.paged_attention_registry()
    assert {"jax", "pallas"} <= set(reg)
    with pytest.raises(ValueError, match="no paged-attention impl"):
        kops.paged_decode_attention(jnp.zeros((1, 2, 4)),
                                    jnp.zeros((2, 2, 1, 4)),
                                    jnp.zeros((2, 2, 1, 4)),
                                    jnp.zeros((1, 1), jnp.int32),
                                    jnp.ones((1,), jnp.int32),
                                    impl="nope")
    # off-TPU, auto must resolve to the dense-bit-identical jax lowering
    if jax.default_backend() != "tpu":
        assert kops.resolve_paged_attn("auto") == "jax"
    assert kops.resolve_paged_attn("pallas") == "pallas"


# ---------------------------------------------------------------------------
# Engine-level token exactness
# ---------------------------------------------------------------------------

def test_paged_vs_dense_token_exact_with_page_churn():
    """Same stream through both cache modes: identical tokens. More
    requests than slots and a pool sized near the working set force
    evict→reuse of pages across requests."""
    cfg = _cfg()
    prompts, gens = _workload(cfg, 8, prompt_len=12, seed=3, lens=(2, 12))
    model = LM(cfg)
    params = model.init(jax.random.PRNGKey(0))
    dense, md = _run_engine(cfg, params, prompts, gens,
                            max_slots=3, max_len=32)
    paged, mp = _run_engine(cfg, params, prompts, gens,
                            max_slots=3, max_len=32,
                            cache="paged", page_size=8, n_pages=13)
    assert mp["drained"] == md["drained"] == 8
    for i, (a, b) in enumerate(zip(dense, paged)):
        assert a == b, f"request {i} diverged under paging"
    # pool smaller than total demand -> pages must have been reused
    total_pages_needed = sum(-(-(p.shape[0] + g) // 8)
                             for p, g in zip(prompts, gens))
    assert total_pages_needed > mp["cache"]["pages_total"]


def test_paged_shared_prefix_and_cow_token_exact():
    """A batch sharing a long prompt prefix (and two *identical* prompts,
    which share their partial tail page) must hit the prefix cache, COW on
    first divergence, and stay token-exact vs dense."""
    cfg = _cfg()
    rng = np.random.default_rng(5)
    common = rng.integers(0, cfg.vocab_size, size=16).astype(np.int32)
    tail_a = rng.integers(0, cfg.vocab_size, size=4).astype(np.int32)
    tail_b = rng.integers(0, cfg.vocab_size, size=4).astype(np.int32)
    # 20-token prompts on 8-token pages: 2 full pages shared by everyone,
    # plus a *partial* tail page shared only within each identical pair —
    # the first decode append into a shared tail must copy-on-write
    prompts = np.stack([np.concatenate([common, tail_a]),
                        np.concatenate([common, tail_a]),
                        np.concatenate([common, tail_b]),
                        np.concatenate([common, tail_b])])
    gens = [6, 4, 5, 3]
    model = LM(cfg)
    params = model.init(jax.random.PRNGKey(1))
    dense, _ = _run_engine(cfg, params, prompts, gens,
                           max_slots=2, max_len=40)
    paged, mp = _run_engine(cfg, params, prompts, gens,
                            max_slots=2, max_len=40,
                            cache="paged", page_size=8)
    for i, (a, b) in enumerate(zip(dense, paged)):
        assert a == b, f"request {i} diverged under prefix sharing"
    prefix = mp["cache"]["prefix"]
    assert prefix["hits"] > 0 and prefix["hit_rate"] > 0
    assert mp["cache"]["cow_copies"] > 0


def test_paged_oom_defers_preempts_and_stays_exact():
    """A pool far smaller than the workload's working set must defer
    admissions and preempt+replay mid-decode — and still drain everything
    with dense-identical tokens (greedy replay is deterministic)."""
    cfg = _cfg()
    prompts, gens = _workload(cfg, 8, prompt_len=12, seed=2, lens=(6, 21))
    model = LM(cfg)
    params = model.init(jax.random.PRNGKey(0))
    dense, _ = _run_engine(cfg, params, prompts, gens,
                           max_slots=4, max_len=36)
    paged, mp = _run_engine(cfg, params, prompts, gens,
                            max_slots=4, max_len=36,
                            cache="paged", page_size=8, n_pages=9)
    assert mp["drained"] == 8
    for i, (a, b) in enumerate(zip(dense, paged)):
        assert a == b, f"request {i} diverged under OOM pressure"
    assert mp["cache"]["deferrals"] > 0 or mp["cache"]["preemptions"] > 0


@pytest.mark.parametrize("arch", ["mamba2-130m"])
def test_paged_cross_family_ssm_rows(arch):
    """Non-attention layers keep dense per-slot rows inside the paged
    tree; an SSM model must stay token-exact through paged mode."""
    cfg = get_config(arch, reduced=True)
    prompts, gens = _workload(cfg, 4, prompt_len=16, seed=0, lens=(2, 8))
    model = LM(cfg)
    params = model.init(jax.random.PRNGKey(0))
    dense, _ = _run_engine(cfg, params, prompts, gens,
                           max_slots=2, max_len=32)
    paged, _ = _run_engine(cfg, params, prompts, gens,
                           max_slots=2, max_len=32,
                           cache="paged", page_size=8)
    for a, b in zip(dense, paged):
        assert a == b


def test_paged_rejects_unsupported_layouts():
    cfg = _cfg(cache_layout="opt")
    with pytest.raises(ValueError, match="bshd"):
        ContinuousScheduler(cfg, max_slots=2, max_len=16, cache="paged")
    cfg = get_config("mixtral-8x22b", reduced=True)   # sliding window
    with pytest.raises(ValueError, match="sliding-window"):
        ContinuousScheduler(cfg, max_slots=2, max_len=16, cache="paged")


# ---------------------------------------------------------------------------
# int8 pages
# ---------------------------------------------------------------------------

def test_int8_quant_roundtrip_and_pytree():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((5, 8, 2, 32)) * 3, jnp.float32)
    pages = Int8Pages.quantize(x)
    back = pages.dequantize(jnp.float32)
    err = np.abs(np.asarray(back) - np.asarray(x)).max()
    scale = np.abs(np.asarray(x)).max()
    assert err <= scale / 127 + 1e-6          # half-ulp of the int8 grid
    assert pages.nbytes < x.nbytes // 2 + pages.scales.nbytes + 1
    # pytree: flatten/unflatten and jit-arg round trips preserve structure
    leaves, treedef = jax.tree_util.tree_flatten(pages)
    assert len(leaves) == 2
    rebuilt = jax.tree_util.tree_unflatten(treedef, leaves)
    assert isinstance(rebuilt, Int8Pages)
    out = jax.jit(lambda p: p.dequantize(jnp.float32))(pages)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(back))
    # zero rows dequantize exactly
    z = Int8Pages.quantize(jnp.zeros((2, 4, 1, 8)))
    assert np.all(np.asarray(z.dequantize()) == 0)


def test_paged_int8_engine_runs_and_halves_cache():
    cfg = _cfg()
    prompts, gens = _workload(cfg, 5, prompt_len=16, seed=1, lens=(2, 6))
    model = LM(cfg)
    params = model.init(jax.random.PRNGKey(0))
    _, m16 = _run_engine(cfg, params, prompts, gens, max_slots=2,
                         max_len=24, cache="paged", page_size=8)
    toks8, m8 = _run_engine(cfg, params, prompts, gens, max_slots=2,
                            max_len=24, cache="paged", page_size=8,
                            kv_dtype="int8")
    assert m8["drained"] == 5 and all(len(t) == g
                                      for t, g in zip(toks8, gens))
    assert m8["cache"]["kv_dtype"] == "int8"
    # int8 codes are half of bf16; per-page scale tensors add f32/(KV row)
    assert m8["cache"]["nbytes"] < m16["cache"]["nbytes"]


# ---------------------------------------------------------------------------
# Host-side bookkeeping units
# ---------------------------------------------------------------------------

def test_page_pool_admission_refcounts_and_release():
    cfg = _cfg()
    pool = PagePool(LM(cfg), max_slots=2, max_len=32, page_size=8,
                    n_pages=9)
    assert pool.usable_pages == 8
    prompt = np.arange(20, dtype=np.int32)       # 3 pages
    adm = pool.admit(prompt)
    assert adm is not None and adm.n_shared == 0
    assert len(adm.page_ids) == 3 and 0 not in adm.page_ids  # trash page
    assert pool.pages_used == 3
    # identical prompt: all three pages shared, refcounts bump
    adm2 = pool.admit(prompt)
    assert adm2 is not None and adm2.n_shared == 3
    assert adm2.page_ids == adm.page_ids
    assert pool.pages_used == 3                  # no new allocation
    assert pool.n_free == 0
    assert pool.admit(prompt) is None            # no slot left
    pool.release(adm.slot)
    pool.release(adm2.slot)
    # registered pages stay pinned for future prefix hits
    assert pool.pages_used == 3 and pool.n_free == 2


def test_page_pool_oom_rollback_and_reclaim():
    cfg = _cfg()
    pool = PagePool(LM(cfg), max_slots=4, max_len=32, page_size=8,
                    n_pages=5)                   # 4 usable pages
    a = pool.admit(np.arange(24, dtype=np.int32))          # 3 pages
    assert a is not None
    # 3 pages needed, 1 free -> all-or-nothing failure, state rolled back
    used_before = pool.pages_used
    assert pool.admit(np.arange(100, 124, dtype=np.int32)) is None
    assert pool.pages_used == used_before
    pool.release(a.slot)
    # pinned-but-unreferenced prefix pages are reclaimed under pressure
    b = pool.admit(np.arange(200, 232, dtype=np.int32))    # 4 pages
    assert b is not None and pool.pages_used == 4


def test_page_pool_ensure_append_grows_and_cows():
    cfg = _cfg()
    pool = PagePool(LM(cfg), max_slots=2, max_len=32, page_size=8,
                    n_pages=9)
    prompt = np.arange(12, dtype=np.int32)       # 1 full + 1 partial page
    adm = pool.admit(prompt)
    tail = adm.page_ids[-1]
    # sole owner appends into its registered tail *in place* (no copy —
    # prompt rows stay immutable; appends only touch rows >= prompt tail)
    assert pool.ensure_append(adm.slot, 12)
    assert pool.cow_count == 0
    assert pool.slot_pages[adm.slot][-1] == tail
    # a live sharer makes the tail refcount 2 -> the next append must COW
    adm2 = pool.admit(prompt)
    assert adm2 is not None and adm2.page_ids[-1] == tail
    assert pool.ensure_append(adm2.slot, 12)
    assert pool.cow_count == 1
    assert pool.slot_pages[adm2.slot][-1] != tail
    # crossing into a fresh page allocates
    used = pool.pages_used
    assert pool.ensure_append(adm.slot, 16)
    assert pool.pages_used == used + 1


def test_prefix_cache_chaining_semantics():
    ps = 8
    a = np.arange(20, dtype=np.int32)
    b = np.arange(20, dtype=np.int32)
    b[0] = 99                                             # diverges early
    c = np.arange(24, dtype=np.int32)                     # longer, same head
    keys_a = page_keys(a, ps)
    assert len(keys_a) == 3
    # chained: a divergence in page 0 changes every downstream key
    keys_b = page_keys(b, ps)
    assert all(x != y for x, y in zip(keys_a, keys_b))
    # partial-tail key (4 tokens) differs from the full-page key of the
    # longer prompt covering the same positions
    keys_c = page_keys(c, ps)
    assert keys_a[:2] == keys_c[:2] and keys_a[2] != keys_c[2]
    cache = PrefixCache(ps)
    for i, key in enumerate(keys_a):
        cache.register(key, i + 1)
    _, matched = cache.lookup(a)
    assert matched == [1, 2, 3]
    _, matched = cache.lookup(c)
    assert matched == [1, 2]                     # stops at the tail
    assert cache.hit_rate is not None and 0 < cache.hit_rate < 1
    cache.unregister_page(2)
    _, matched = cache.lookup(a)
    assert matched == [1]                        # chain broken at page 1


def test_slotpool_liveness_is_o1_and_lifo():
    cfg = _cfg()
    pool = SlotPool(LM(cfg), max_slots=4, max_len=8)
    s0 = pool.alloc()
    pool.free(s0)
    with pytest.raises(AssertionError):
        pool.free(s0)                            # double free caught in O(1)
    assert pool.alloc() == s0                    # LIFO order preserved
    assert pool.nbytes > 0


# ---------------------------------------------------------------------------
# Metrics + CLI
# ---------------------------------------------------------------------------

def test_cache_metrics_sections():
    cfg = _cfg()
    prompts, gens = _workload(cfg, 4, prompt_len=8, seed=0, lens=(1, 4))
    model = LM(cfg)
    params = model.init(jax.random.PRNGKey(0))
    _, md = _run_engine(cfg, params, prompts, gens, max_slots=2, max_len=16)
    assert md["cache"]["mode"] == "dense" and md["cache"]["nbytes"] > 0
    assert md["concurrency"]["peak"] >= 1
    _, mp = _run_engine(cfg, params, prompts, gens, max_slots=2, max_len=16,
                        cache="paged", page_size=8)
    cm = mp["cache"]
    assert cm["mode"] == "paged" and cm["nbytes"] > 0
    assert cm["pages_total"] > 0 and cm["pages_used_peak"] >= 1
    assert 0 < cm["occupancy_peak"] <= 1
    assert cm["prefix"]["lookups"] > 0
    json.dumps(md), json.dumps(mp)               # JSON-serializable


def test_serve_cli_paged(capsys):
    metrics = serve.main(["--arch", "ternary-paper", "--reduced",
                          "--requests", "5", "--slots", "2",
                          "--prompt-len", "8", "--gen-lens", "2,5",
                          "--cache", "paged", "--page-size", "8"])
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["submitted"] == out["drained"] == 5
    assert out["cache"]["mode"] == "paged"
    assert metrics["cache"]["page_size"] == 8
