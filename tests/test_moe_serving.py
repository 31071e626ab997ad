"""Sparse experts on the serving path: the expert-share MoE layer, the
grouped ternary expert GEMM, per-layer types (leading dense layers, window
layers) and the engine's expert counters.

Small seeded models on the CPU (Pallas kernels in interpret mode)."""
import dataclasses
import zlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, list_archs
from repro.configs.base import ModelConfig
from repro.core import weights
from repro.kernels import ops
from repro.models import LM, moe
from repro.models.transformer import split_period
from repro.serving import ContinuousScheduler
from repro.serving.sched import SchedConfig

D, F, E, K = 64, 32, 16, 4


def _bank(rng, e, k, n):
    codes = rng.integers(-1, 2, size=(e, k, n))
    scale = rng.uniform(0.5, 1.5, size=(e, n)).astype(np.float32)
    return weights.Dense2Bit.from_dense(codes, scale=jnp.asarray(scale)), \
        codes.astype(np.float32) * scale[:, None, :]


def _moe_layer(seed=0, router_zero=False):
    """A 16-expert top-4 sigmoid layer with one packed shared expert:
    (params, the decoded banks, the config)."""
    rng = np.random.default_rng(seed)
    cfg = ModelConfig(name="moe", family="moe", num_layers=1, d_model=D,
                      num_heads=4, num_kv_heads=2, d_ff=0, vocab_size=64,
                      num_experts=E, num_experts_per_tok=K, d_ff_expert=F,
                      n_shared_experts=1, moe_scoring="sigmoid",
                      routed_scaling=2.5, quantization="ternary_packed",
                      ternary_min_dim=16, dtype="float32", fused_mlp="off")
    params, dense = {}, {}
    for name, k, n in (("w_in", D, F), ("w_gate", D, F), ("w_out", F, D)):
        params[name], dense[name] = _bank(rng, E, k, n)
    router = rng.normal(size=(D, E)).astype(np.float32) / np.sqrt(D)
    params["router"] = jnp.zeros((D, E)) if router_zero \
        else jnp.asarray(router)
    shared = {}
    for name, k, n in (("in", D, F), ("gate", D, F), ("out", F, D)):
        w, dense["shared_" + name] = _bank(rng, 1, k, n)
        shared[name] = {"w_packed": weights.Dense2Bit(
            packed=w.packed[0], scale=w.scale[0], bias=None, shape=(k, n))}
        dense["shared_" + name] = dense["shared_" + name][0]
    params["shared"] = shared
    return params, dense, cfg


def _share(params, cfg, i, n_shares):
    held = E // n_shares
    sl = slice(i * held, (i + 1) * held)
    p = dict(params)
    for name in ("w_in", "w_gate", "w_out"):
        w = params[name]
        p[name] = dataclasses.replace(w, packed=w.packed[sl],
                                      scale=w.scale[sl])
    return p, dataclasses.replace(cfg, experts_held=held,
                                  expert_offset=i * held)


def _plain_layer(x, router, dense):
    """The uncut layer in plain float32: every expert on every row,
    weighted by its (normalised, scaled) sigmoid gate."""
    with jax.default_matmul_precision("highest"):
        scores = jax.nn.sigmoid(x @ router)
        top, ids = jax.lax.top_k(scores, K)
        top = top / top.sum(-1, keepdims=True) * 2.5
        y = jnp.zeros_like(x)
        for e in range(E):
            g = jnp.sum(jnp.where(ids == e, top, 0.0), -1)
            h = jax.nn.silu(x @ dense["w_gate"][e]) * (x @ dense["w_in"][e])
            y = y + g[:, None] * (h @ dense["w_out"][e])
        hs = jax.nn.silu(x @ dense["shared_gate"]) * (x @ dense["shared_in"])
        return y, hs @ dense["shared_out"]


def test_expert_shares_add_up_to_the_uncut_layer():
    """Four devices' shares of a 16-expert top-4 sigmoid layer, the shared
    expert (which each computes alike) counted once, add up to the plain
    uncut layer; each share routes over all 16 experts."""
    params, dense, cfg = _moe_layer()
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 12, D))
    ys = []
    rows = 0
    for i in range(4):
        p, c = _share(params, cfg, i, 4)
        y, _, stats = moe.moe_apply(p, x, c)
        ys.append(np.asarray(y))
        rows += int(stats[0])
    routed, shared = _plain_layer(x.reshape(-1, D), params["router"], dense)
    total = sum(ys) - 3 * np.asarray(shared).reshape(x.shape)
    # float32 sums of terms up to a few hundred: about 1e-4 of rounding
    np.testing.assert_allclose(total, np.asarray(routed + shared).reshape(
        x.shape), rtol=1e-5, atol=1e-3)
    assert rows == x.shape[0] * x.shape[1] * K      # every pair, once
    y_all, _, _ = moe.moe_apply(params, x, cfg)
    np.testing.assert_allclose(np.asarray(y_all), total, rtol=1e-5,
                               atol=1e-3)


def _tiny_lm(**kw):
    base = dict(name="toy", family="moe", num_layers=2, d_model=D,
                num_heads=4, num_kv_heads=2, head_dim=16, d_ff=0,
                vocab_size=64, num_experts=E, num_experts_per_tok=K,
                d_ff_expert=F, n_shared_experts=1, moe_scoring="sigmoid",
                routed_scaling=2.5, quantization="ternary_packed",
                ternary_min_dim=16, dtype="float32", cache_dtype="float32",
                fused_mlp="off", remat="none")
    base.update(kw)
    return ModelConfig(**base)


def _packed_params(model, seed=0):
    """Every leaf of ``model`` seeded: random 2-bit words, scales near
    sqrt(2/K), normal routers and embedding, unit norms."""
    shapes, _ = model.init_with_specs_abstract()
    key = jax.random.PRNGKey(seed)

    def fill(path, s):
        name = jax.tree_util.keystr(path)
        k = jax.random.fold_in(key, zlib.crc32(name.encode()))
        if s.dtype == jnp.uint32:
            return jax.random.bits(k, s.shape, jnp.uint32)
        if "norm" in name:
            return jnp.ones(s.shape, s.dtype)
        if "scale" in name:
            return jax.random.uniform(k, s.shape, s.dtype, 0.05, 0.1)
        return jax.random.normal(k, s.shape, s.dtype) * 0.5

    return jax.tree_util.tree_map_with_path(fill, shapes)


def test_dropless_when_every_token_picks_the_same_experts():
    """A zero router ties every score, so every token picks experts 0-3:
    the capacity path would drop most of them; the packed path loses no
    row, and decode logits equal the full forward's at each position."""
    cfg = _tiny_lm()
    model = LM(cfg)
    params = _packed_params(model)
    for j in range(len(model.block_kinds)):
        params[f"block{j}"]["ffn"]["router"] = jnp.zeros_like(
            params[f"block{j}"]["ffn"]["router"])
    toks = jax.random.randint(jax.random.PRNGKey(3), (2, 12), 0, 64)
    x, _, _ = model.forward(params, {"tokens": toks})
    full = model._logits(params, x)
    cache, _ = model.prefill(params, {"tokens": toks[:, :8]}, 12,
                             cache_dtype=jnp.float32)
    for t in range(8, 12):
        logits, cache, stats = model.decode_step(params, cache,
                                                 toks[:, t:t + 1], True)
        np.testing.assert_allclose(np.asarray(logits[:, 0]),
                                   np.asarray(full[:, t]), rtol=2e-4,
                                   atol=2e-4)
        # every row's 4 pairs, in every MoE layer; 4 experts active each
        assert int(stats[0]) == 2 * K * cfg.num_layers
        assert int(stats[1]) == K * cfg.num_layers


def _layout(local, held, bm):
    pos, tile_group, tile_row, n_active, sizes = moe.expert_tiles(
        jnp.asarray(local, jnp.int32), held, bm)
    return pos, tile_group, tile_row, n_active, sizes


@pytest.mark.parametrize("impl", ["pallas", "xla"])
@pytest.mark.parametrize("bm", [8, 16])
def test_moe_expert_gemm_matches_plain_decode(impl, bm):
    """Ragged groups (one of 19 rows across two tiles), empty experts and
    pairs held elsewhere: each held pair's row equals its row of x times
    its expert's decoded bank times the scale, in both lowerings."""
    rng = np.random.default_rng(0)
    held, k, n = 4, 64, 256
    local = np.array([3] * 19 + [0] * 5 + [held] * 7 + [3] * 2)
    rng.shuffle(local)
    w, dense = _bank(rng, held, k, n)
    pos, tg, tr, na, sizes = _layout(local, held, bm)
    assert list(np.asarray(sizes)) == [5, 0, 0, 21]
    x_pairs = jnp.asarray(rng.normal(size=(local.size, k)), jnp.float32)
    m_pad = tg.shape[0] * bm
    src = jnp.full((m_pad,), local.size, jnp.int32).at[pos].set(
        jnp.arange(local.size, dtype=jnp.int32), mode="drop")
    xs = jnp.concatenate([x_pairs, jnp.zeros((1, k))])[src]
    out = ops.moe_expert_gemm(xs, w, tg, tr, na, block_m=bm, impl=impl,
                              interpret=True)
    mine = local < held
    got = np.asarray(out)[np.asarray(pos)[mine]]
    want = np.einsum("pk,pkn->pn", np.asarray(x_pairs)[mine],
                     dense[local[mine]])
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-4)


def test_expert_tiles_hold_each_expert_in_its_own_tiles():
    local = np.array([2, 0, 2, 4, 2, 0, 2, 2, 2, 2, 2])   # 4 = not held
    pos, tg, tr, na, sizes = _layout(local, 4, 4)
    pos = np.asarray(pos)
    assert int(na[0]) == 1 + 2                        # 2 rows, then 8
    assert list(np.asarray(tg)[:3]) == [0, 2, 2]
    assert sorted(pos[local == 0] // 4) == [0, 0]
    assert set(pos[local == 2] // 4) == {1, 2}
    assert pos[local == 4][0] == tg.shape[0] * 4      # dropped on write
    # spare tiles point at the last active tile: no fetch, no write
    assert set(np.asarray(tr)[3:]) == {2}


def test_existing_configs_keep_their_period():
    """Every registered configuration runs no leading layers and keeps the
    smallest period of its layer kinds."""
    for arch in list_archs():
        for red in (False, True):
            cfg = get_config(arch, reduced=red)
            kinds = [(cfg.layer_kind(i), cfg.layer_ffn(i))
                     for i in range(cfg.num_layers)]
            model = LM(cfg)
            p = next(p for p in range(1, cfg.num_layers + 1)
                     if cfg.num_layers % p == 0
                     and all(kinds[i] == kinds[i % p]
                             for i in range(cfg.num_layers)))
            assert (model.n_lead, model.period) == (0, p), arch


def test_leading_dense_layer_then_lllg_periods():
    """K-EXAONE's 48 layers: a dense layer 0 and LLLG windows run layers
    0-3 once, then 11 periods of (L, L, L, G)."""
    windows = [128, 128, 128, 0] * 12
    ffns = ["mlp"] + ["moe"] * 47
    cfg = _tiny_lm(num_layers=48, layer_windows=windows, layer_ffns=ffns)
    model = LM(cfg)
    assert (model.n_lead, model.period, model.n_groups) == (4, 4, 11)
    assert model.lead_windows == [128, 128, 128, 0]
    assert [f for _, f in model.lead_kinds] == ["mlp", "moe", "moe", "moe"]
    assert model.block_windows == [128, 128, 128, 0]
    assert split_period(list("abcbcbc")) == (1, 2)
    with pytest.raises(ValueError):
        _tiny_lm(num_layers=4, layer_windows=(8,) * 3)


TOY = dict(num_layers=12, layer_windows=(8, 8, 8, 0) * 3,
           layer_ffns=("mlp",) + ("moe",) * 11, d_ff=96, qk_norm=True,
           sublayer_norm="post", rope_window_only=True)


def _toy_engine(cfg, seed=0, **kw):
    eng = ContinuousScheduler(
        cfg, max_slots=3, max_len=40, cache="paged", page_size=4,
        n_pages=40, sched=SchedConfig(chunk_tokens=4, step_token_budget=7,
                                      admission="fifo"), **kw)
    eng.load(_packed_params(eng.model, seed))
    return eng


WORK = ((13, 9), (6, 12), (17, 5), (9, 10))


def _serve(cfg):
    """Serve WORK to the end; (engine, requests, run metrics, device reads
    in each step)."""
    eng = _toy_engine(cfg)
    rng = np.random.default_rng(0)
    reqs = [eng.submit(rng.integers(0, 64, n).astype(np.int32), g)
            for n, g in WORK]
    to_host = np.asarray
    reads = [0]

    def counting_read(x, *a, **kw):
        reads[0] += isinstance(x, jax.Array)
        return to_host(x, *a, **kw)

    per_step = []
    np.asarray = counting_read
    try:
        snap = eng.begin_metrics()
        while eng.has_work():
            before = reads[0]
            eng.step()
            per_step.append(reads[0] - before)
    finally:
        np.asarray = to_host
    return eng, reqs, eng.collect_metrics(snap), per_step


@pytest.fixture(scope="module")
def served():
    """The K-EXAONE-shaped toy (a dense first layer, then LLLG windows of
    8 and MoE layers, 4-token pages) through ContinuousScheduler with
    chunked prefill."""
    return _serve(_tiny_lm(**TOY))


def test_pad_rows_route_to_no_expert(served):
    """With every expert held, the chunk windows' expert rows are the
    prompt tokens times top-k and the decode steps' the committed decode
    tokens times top-k, per MoE layer: pad rows and pad lanes add
    nothing."""
    eng, reqs, m, _ = served
    n_moe = TOY["layer_ffns"].count("moe")
    snap = eng.metrics.snapshot()
    assert snap["expert_rows.chunk"] == sum(n for n, _ in WORK) * K * n_moe
    assert snap["expert_rows.decode"] == m["rows"]["decode"]["real"] \
        * K * n_moe == sum(len(r.tokens) - 1 for r in reqs) * K * n_moe
    assert 0 < snap["experts_active.decode"] <= E * n_moe * m["decode_steps"]
    assert m["rows"]["chunk"]["computed"] > m["rows"]["chunk"]["real"]


def test_expert_counters_add_no_device_reads(served):
    """The MoE engine reads back from the device as often in every step
    as the same engine with dense MLPs in place of the experts."""
    dense = _serve(_tiny_lm(**dict(TOY, layer_ffns=("mlp",) * 12)))
    assert served[3] and all(r > 0 for r in served[3])
    assert served[3] == dense[3]


def test_window_layers_in_the_paged_cache_match_the_full_forward(served):
    """Served through ContinuousScheduler with chunked prefill (window 8,
    page 4), every served token is the full forward's greedy pick: the
    paged kernels mask the window, the leading layers run once."""
    eng, reqs, _, _ = served
    seqs = [np.concatenate([r.prompt, np.asarray(r.tokens[:-1])])
            for r in reqs]
    batch = np.zeros((len(seqs), max(map(len, seqs))), np.int32)
    for i, q in enumerate(seqs):
        batch[i, :len(q)] = q
    x, _, _ = eng.model.forward(eng.params, {"tokens": jnp.asarray(batch)})
    logits = np.asarray(eng.model._logits(eng.params, x))
    for i, r in enumerate(reqs):
        greedy = logits[i, r.prompt_len - 1:len(seqs[i])].argmax(-1)
        assert list(greedy) == list(r.tokens)


def test_spec_refuses_leading_layers_and_window_layers():
    from repro.spec import SpecConfig
    with pytest.raises(ValueError, match="leading layers"):
        ContinuousScheduler(_tiny_lm(**TOY), max_slots=2, max_len=32,
                            cache="paged", page_size=4,
                            spec=SpecConfig(k=2))
