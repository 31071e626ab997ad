"""The sparse-expert family (``families/moe.py``), its plain reference
(``moe_reference.py``) and its kernel class and readers: a K-EXAONE-shaped
toy served through the engine against the reference on the CPU, and the
configuration file at published widths read without building anything."""
import copy
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import _paths
import harness
import moe_reference
import reduce
import spec
import work

PEAKS = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
FAMILY = spec.load_family(_paths.BENCH, "moe")
CONFIG = os.path.join(_paths.BENCH, "configs", "k-exaone-236b-a23b.json")
L = 12
TOY = {"family": "moe", "hidden_size": 64, "intermediate_size": 128,
       "num_hidden_layers": L, "num_attention_heads": 4,
       "num_key_value_heads": 2, "head_dim": 16, "vocab_size": 256,
       "rms_norm_eps": 1e-5,
       "rope_parameters": {"rope_theta": 10000.0, "rope_type": "default"},
       "tie_word_embeddings": False,
       "sliding_windows": [8, 8, 8, 0] * 3,
       "mlp_layer_types": ["dense"] + ["sparse"] * (L - 1),
       "num_experts": 4, "published_num_experts": 16, "expert_offset": 4,
       "num_experts_per_tok": 4, "moe_intermediate_size": 32,
       "num_shared_experts": 1, "scoring_func": "sigmoid",
       "norm_topk_prob": True, "routed_scaling_factor": 2.5,
       "serving": {"quantization": "ternary_packed", "ternary_min_dim": 16,
                   "dtype": "float32", "param_dtype": "float32",
                   "cache_dtype": "float32", "fused_mlp": "off"}}
SEED = 2**33 + 5
WORK = ((13, 9), (6, 12), (17, 5), (9, 10))


def _published():
    with open(CONFIG) as f:
        return json.load(f)


def test_model_config_is_the_published_model_as_one_share():
    """Published widths, 48 layers, the router over all 128 experts, 8
    held; the stack runs layers 0-3 once, then 11 periods of LLLG."""
    from repro.models import LM
    c = _published()
    cfg = FAMILY.model_config(c)
    assert (cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim) \
        == (6144, 64, 8, 128)
    assert (cfg.num_experts, cfg.experts_held, cfg.expert_offset) \
        == (128, 8, 0)
    assert (cfg.num_experts_per_tok, cfg.d_ff_expert, cfg.d_ff) \
        == (8, 2048, 18432)
    assert cfg.moe_scoring == "sigmoid" and cfg.routed_scaling == 2.5
    assert cfg.layer_ffns == ("mlp",) + ("moe",) * 47
    assert cfg.layer_windows == (128, 128, 128, 0) * 12
    assert cfg.qk_norm and cfg.sublayer_norm == "post" \
        and cfg.rope_window_only
    m = LM(cfg)
    assert (m.n_lead, m.period, m.n_groups) == (4, 4, 11)
    names = moe_reference.layer_names(c)
    assert names[:5] == [("lead0", None), ("lead1", None), ("lead2", None),
                         ("lead3", None), ("block0", 0)]
    assert names[-1] == ("block3", 10)


def test_config_file_keeps_the_catalog_keys():
    """Only ``num_experts`` differs from the published config (the
    experts held here), and the file says so and states the deployment."""
    c = _published()
    assert c["num_experts"] == 8 and c["published_num_experts"] == 128
    assert list(c["reduced"]) == ["num_experts"]
    assert "16 chips" in c["deployment"]
    assert c["sliding_windows"].count(128) == 36
    for key in ("qk_norm", "sublayer_norm", "rope", "selection_bias"):
        assert key in c["assumed"]


def test_a_share_of_the_experts_is_a_slice_of_the_whole():
    """Expert ``e``'s words and scales are drawn from its global id: the
    share held from offset 4 is experts 4-7 of the uncut layer."""
    root = jax.random.key(3)
    whole = dict(TOY, num_experts=16, expert_offset=0)
    name = "lead1/ffn/w_gate/packed"
    shape = (16, 4, 32)
    FAMILY.model_config(whole)
    w_all = FAMILY.leaf(root, name, shape, None, 0)
    s_all = FAMILY.leaf(root, name[:-6] + "scale", (16, 32), None, 0)
    FAMILY.model_config(TOY)
    w4 = FAMILY.leaf(root, name, (4,) + shape[1:], None, 0)
    s4 = FAMILY.leaf(root, name[:-6] + "scale", (4, 32), None, 0)
    np.testing.assert_array_equal(np.asarray(w4), np.asarray(w_all[4:8]))
    np.testing.assert_array_equal(np.asarray(s4), np.asarray(s_all[4:8]))
    assert FAMILY.leaf(root, "lead1/ffn/router", (64, 16), None, 0).shape \
        == (64, 16)


def test_float_leaves_take_the_param_dtype():
    c = copy.deepcopy(TOY)
    c["serving"]["param_dtype"] = "bfloat16"
    FAMILY.model_config(c)
    root = jax.random.key(0)
    assert FAMILY.leaf(root, "embed/table", (256, 64), None, 0).dtype \
        == jnp.bfloat16
    assert FAMILY.leaf(root, "lead0/norm1/scale", (64,), None, 0).dtype \
        == jnp.bfloat16
    # kernel scales stay float32, words uint32
    assert FAMILY.leaf(root, "lead0/mixer/q/w_packed/scale", (64,), None,
                       64).dtype == jnp.float32
    assert FAMILY.leaf(root, "lead1/ffn/w_in/scale", (4, 32), None,
                       0).dtype == jnp.float32
    FAMILY.model_config(TOY)


@pytest.fixture(scope="module")
def served():
    """The toy served through the engine: paged cache (4-token pages,
    8-token windows), chunked prefill, the family's seeded weights."""
    from repro.models import LM
    from repro.serving import ContinuousScheduler
    from repro.serving.sched import SchedConfig
    cfg = FAMILY.model_config(TOY)
    model = LM(cfg)
    params = harness.make_params(model, SEED, FAMILY.leaf)
    eng = ContinuousScheduler(
        cfg, max_slots=3, max_len=40, cache="paged", page_size=4,
        n_pages=40, sched=SchedConfig(chunk_tokens=4, step_token_budget=7,
                                      admission="fifo"))
    eng.load(params)
    rng = np.random.default_rng(0)
    reqs = [eng.submit(rng.integers(0, 256, n).astype(np.int32), g)
            for n, g in WORK]
    acc = FAMILY.work(TOY, PEAKS)
    while eng.has_work():
        before = harness._counters(eng)
        eng.step()
        acc.counters({k: v - before.get(k, 0)
                      for k, v in harness._counters(eng).items()})
    return {"seqs": [(r.prompt, list(r.tokens)) for r in reqs],
            "model": model, "params": params, "acc": acc, "engine": eng}


def test_served_tokens_match_the_reference(served):
    """Prefill in chunks and decode through window layers and expert
    shares: in float32 each served token is the reference's greedy pick
    (a gap of at most 1e-4, float32 rounding of logits near 1), and the
    float8 control strays from it by more than the 0.05 a float32 toy's
    check would allow."""
    ctl, srv = moe_reference.control_gaps(TOY, SEED, served["seqs"])
    assert max(srv) <= 1e-4
    assert max(ctl) > 0.05


def test_program_logits_match_the_reference(served):
    """The program's forward over the served sequences against the plain
    reference's logits at every served position: float32 on both sides,
    within 1e-3 of logits of order 1."""
    tokens, pos, _, _ = moe_reference.pack_sequences(served["seqs"])
    ref = np.asarray(moe_reference.logits_at(TOY, SEED, tokens, pos))
    model, params = served["model"], served["params"]
    x, _, _ = model.forward(params, {"tokens": jnp.asarray(tokens)})
    got = np.asarray(model._logits(params, x))[pos[:, 0], pos[:, 1]]
    np.testing.assert_allclose(got, ref[:, :got.shape[1]], rtol=1e-4,
                               atol=1e-3)


def test_expert_work_from_the_counters(served):
    """The engine's expert counters reach the family's work: pairs
    routed to the 4 held experts in 11 MoE layers, every active expert's
    banks read once a layer-step, and the reader's rows per active
    expert."""
    acc, eng = served["acc"], served["engine"]
    snap = eng.metrics.snapshot()
    rows = snap["expert_rows.chunk"] + snap["expert_rows.decode"]
    active = snap["experts_active.chunk"] + snap["experts_active.decode"]
    assert acc.expert_rows == rows > 0 and acc.experts_active == active
    d, f = TOY["hidden_size"], TOY["moe_intermediate_size"]
    assert acc.expert.ops == 2.0 * rows * 3 * d * f
    assert acc.expert.bytes == pytest.approx(
        active * (3 * d * f / 4 + 4 * (2 * f + d)) + rows * 2 * 3 * (d + f))
    reader = spec.load_reader(_paths.BENCH, "expert_rows.moe")
    assert reader({"work": acc}) == pytest.approx(rows / active)
    assert reader({"work": work.StepWork(
        work.Shapes(2, 64, 128, 4, 2, 16, 256), PEAKS)}) is None
    roof = spec.load_reader(_paths.BENCH, "expert_roofline.moe")
    t = acc.expert.least_s * 4
    assert roof({"work": acc, "trace": {"kernels": {"expert": t}}}) \
        == pytest.approx(25.0)


def test_window_layers_count_at_most_the_window():
    """Attention work: full layers over the keys the harness counts,
    window layers over at most ``window`` keys a query row; useful work
    leaves pad rows out."""
    acc = FAMILY.work(TOY, PEAKS)
    acc.forward(3, 3 * 30)                  # 3 decode rows at length 30
    full = work.paged_attention(3, 90, 4, 2, 16)
    win = work.paged_attention(3, 3 * 8, 4, 2, 16)
    assert acc.attn.ops == pytest.approx(3 * full[0] + 9 * win[0])
    before = acc.useful_ops
    acc.useful(0, 0)
    assert acc.useful_ops == before


def test_expert_class_catches_the_grouped_kernel_only():
    classes = reduce.load_kernel_classes()
    name = "moe_expert_gemm.12"
    hits = [c for c, pat in classes.items() if pat.search(name)]
    assert hits == ["expert"]
    assert not classes["gemm"].search(name)
    assert classes["gemm"].search("ternary_gemm_dense.3")
    assert not classes["expert"].search("ternary_gemm_dense.3")


def test_reduce_puts_expert_time_in_its_own_class():
    dev = {"/device:TPU:0": [(0, 100, "%moe_expert_gemm.1 = bf16[16] x"),
                             (100, 150, "%ternary_gemm_dense.2 = bf16[8] y")]}
    host = [(0, 200, "bench.window")]
    out = reduce.reduce_events(dev, host, reduce.load_kernel_classes())
    assert out["kernels"]["expert"] == pytest.approx(100e-9)
    assert out["kernels"]["gemm"] == pytest.approx(50e-9)

