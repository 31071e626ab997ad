"""The sparse-expert family: K-EXAONE-236B-A23B, served as one chip's share
of an expert-parallel deployment.

The configuration file holds the published config's keys, with
``num_experts`` the experts held here (``published_num_experts`` the
router's width, ``expert_offset`` the first held expert's id). Its
weights and plain reference are ``moe_reference.py``. Its work:

* ``forward`` counts what every row computes whatever the router says:
  the attention projections of every layer, the dense layer's MLP, the
  shared expert of every MoE layer and the head (class ``gemm``), and
  paged attention (class ``attn``): full layers over the keys the harness
  counts, window layers over at most ``window`` keys a query row;
* ``counters`` turns the engine's ``expert_rows.<phase>`` and
  ``experts_active.<phase>`` deltas into the grouped expert GEMM's work
  (class ``expert``): each active held expert's three banks read once,
  and its rows' gate, up and down products. Only rows that carry a token
  are routed, so pad rows count for nothing, in ``useful`` too.
"""
from __future__ import annotations

from typing import Any, Dict, Optional

import jax.numpy as jnp

import moe_reference
import work as work_lib

_CONFIG: Dict[str, Any] = {}

MOE_PHASES = ("chunk", "decode")


def model_config(c: Dict[str, Any]):
    """The program's ``ModelConfig`` for a configuration file. Keeps the
    file for ``leaf``, which the harness calls next."""
    from repro.configs.base import ModelConfig
    if not c["norm_topk_prob"]:
        raise ValueError("the program renormalises the top-k scores; "
                         "norm_topk_prob false is another router")
    _CONFIG.clear()
    _CONFIG.update(c)
    s = c["serving"]
    return ModelConfig(
        name=str(c.get("model_type", "model")), family="moe",
        num_layers=c["num_hidden_layers"], d_model=c["hidden_size"],
        num_heads=c["num_attention_heads"],
        num_kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
        d_ff=c["intermediate_size"], vocab_size=c["vocab_size"],
        rope_theta=float(c["rope_parameters"]["rope_theta"]),
        norm_eps=float(c["rms_norm_eps"]),
        tie_embeddings=bool(c.get("tie_word_embeddings", False)),
        layer_windows=tuple(c["sliding_windows"]),
        layer_ffns=tuple("mlp" if t == "dense" else "moe"
                         for t in c["mlp_layer_types"]),
        # the block the configuration file's "assumed" describes
        qk_norm=True, sublayer_norm="post", rope_window_only=True,
        num_experts=c["published_num_experts"],
        experts_held=c["num_experts"], expert_offset=c["expert_offset"],
        num_experts_per_tok=c["num_experts_per_tok"],
        d_ff_expert=c["moe_intermediate_size"],
        n_shared_experts=c["num_shared_experts"],
        moe_scoring=c["scoring_func"],
        routed_scaling=float(c["routed_scaling_factor"]),
        quantization=s["quantization"],
        ternary_min_dim=int(s["ternary_min_dim"]), dtype=s["dtype"],
        param_dtype=s["param_dtype"], cache_dtype=s["cache_dtype"],
        fused_mlp=s.get("fused_mlp", "auto"))


def leaf(root, name: str, shape, layer, k_in: int):
    """One seeded weight of the configuration ``model_config`` last read,
    in the program's dtype: float parameters (embedding, router, norm
    scales) in ``param_dtype``, 2-bit words and their scales as drawn."""
    w = moe_reference.leaf(_CONFIG, root, name, shape, layer, k_in)
    parts = name.split("/")
    kernel_scale = parts[-1] == "scale" and (
        parts[-2] == "w_packed" or parts[-2] in moe_reference.BANKS)
    if w.dtype == jnp.float32 and not kernel_scale:
        w = w.astype(_CONFIG["serving"]["param_dtype"])
    return w


class Shapes:
    """The widths and layer counts the work counts need."""

    def __init__(self, c: Dict):
        wins = [w for w in c["sliding_windows"] if w]
        self.layers = c["num_hidden_layers"]
        self.d = c["hidden_size"]
        self.heads = c["num_attention_heads"]
        self.kv_heads = c["num_key_value_heads"]
        self.head_dim = c["head_dim"]
        self.vocab = c["vocab_size"]
        self.dense_ff = c["intermediate_size"]
        self.expert_ff = c["moe_intermediate_size"]
        self.shared_ff = c["num_shared_experts"] * c["moe_intermediate_size"]
        self.dense_layers = c["mlp_layer_types"].count("dense")
        self.window = max(wins, default=0)
        self.window_layers = len(wins)

    @property
    def moe_layers(self) -> int:
        return self.layers - self.dense_layers

    @property
    def full_layers(self) -> int:
        return self.layers - self.window_layers

    @property
    def row_params(self) -> int:
        """Matmul weights every token multiplies through, experts aside."""
        hq, hkv = self.heads * self.head_dim, self.kv_heads * self.head_dim
        attn = self.d * (hq + 2 * hkv) + hq * self.d
        return (self.layers * attn
                + self.dense_layers * 3 * self.d * self.dense_ff
                + self.moe_layers * 3 * self.d * self.shared_ff
                + self.d * self.vocab)


class MoeWork:
    """The window's work, summed step by step (see the module docstring).
    ``expert_rows``/``experts_active`` are the window's counter totals."""

    def __init__(self, shapes: Shapes, peaks: Dict[str, float]):
        self.s = shapes
        self.peaks = peaks
        self.gemm = work_lib.Work()
        self.attn = work_lib.Work()
        self.expert = work_lib.Work()
        self.useful_ops = 0.0
        self.expert_rows = 0
        self.experts_active = 0

    def _window_count(self, m: int, attended: int) -> int:
        """Keys a window layer's ``m`` query rows attend: each at most
        ``window``."""
        return min(attended, m * self.s.window)

    def forward(self, m: int, attended: int,
                keys: Optional[int] = None) -> None:
        s, p = self.s, self.peaks
        hq, hkv = s.heads * s.head_dim, s.kv_heads * s.head_dim
        for k, n in ((s.d, hq), (s.d, hkv), (s.d, hkv), (hq, s.d)):
            self.gemm.add(*work_lib.gemm(m, k, n), p, times=s.layers)
        for ff, times in ((s.dense_ff, s.dense_layers),
                          (s.shared_ff, s.moe_layers)):
            for k, n in ((s.d, ff), (s.d, ff), (ff, s.d)):
                self.gemm.add(*work_lib.gemm(m, k, n), p, times=times)
        self.gemm.add(*work_lib.gemm(m, s.d, s.vocab), p)
        keys = attended if keys is None else keys
        att_w = self._window_count(m, attended)
        for att, kk, times in ((attended, keys, s.full_layers),
                               (att_w, min(keys, att_w), s.window_layers)):
            self.attn.add(*work_lib.paged_attention(
                m, att, s.heads, s.kv_heads, s.head_dim, keys=kk), p,
                times=times)

    def useful(self, tokens: int, attended: int) -> None:
        """Real tokens (pad rows excluded) and the context they attend;
        the experts' part comes from ``counters``."""
        s = self.s
        self.useful_ops += (
            2.0 * s.row_params * tokens
            + 4.0 * s.heads * s.head_dim
            * (s.full_layers * attended
               + s.window_layers * self._window_count(tokens, attended)))

    def counters(self, deltas: Dict[str, int]) -> None:
        s = self.s
        rows = sum(deltas.get(f"expert_rows.{ph}", 0) for ph in MOE_PHASES)
        active = sum(deltas.get(f"experts_active.{ph}", 0)
                     for ph in MOE_PHASES)
        self.expert_rows += rows
        self.experts_active += active
        if not rows:
            return
        d, f = s.d, s.expert_ff
        ops = 2.0 * rows * 3 * d * f
        nbytes = (active * (3 * d * f / 4 + 4 * (2 * f + d))
                  + rows * work_lib.BF16 * 3 * (d + f))
        self.expert.add(ops, nbytes, self.peaks)
        self.useful_ops += ops


def work(c: Dict[str, Any], peaks: Dict[str, float]) -> MoeWork:
    return MoeWork(Shapes(c), peaks)


served_gaps = moe_reference.served_gaps
control_gaps = moe_reference.control_gaps
