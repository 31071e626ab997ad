"""The dense GQA decoder family: Mistral-NeMo, the ternary-paper decoder.

A configuration without a ``"family"`` key is of this family. Its
weights are ``gen.leaf``'s, its work is counted by ``work.StepWork`` over
``work.Shapes`` (projections, a SwiGLU MLP and paged attention in every
layer, an untied head) and its plain reference is ``reference.py``.
"""
from __future__ import annotations

from typing import Any, Dict

import gen
import reference
import work as work_lib


def model_config(c: Dict[str, Any]):
    """The program's ``ModelConfig`` for a configuration file."""
    from repro.configs.base import ModelConfig
    s = c["serving"]
    return ModelConfig(
        name=str(c.get("model_type", "model")), family="dense",
        num_layers=c["num_hidden_layers"], d_model=c["hidden_size"],
        num_heads=c["num_attention_heads"],
        num_kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
        d_ff=c["intermediate_size"], vocab_size=c["vocab_size"],
        rope_theta=float(c["rope_theta"]), norm_eps=float(c["rms_norm_eps"]),
        tie_embeddings=bool(c.get("tie_word_embeddings", False)),
        quantization=s["quantization"],
        ternary_min_dim=int(s["ternary_min_dim"]), dtype=s["dtype"],
        param_dtype=s["param_dtype"], cache_dtype=s["cache_dtype"],
        fused_mlp=s.get("fused_mlp", "auto"))


leaf = gen.leaf


def work(c: Dict[str, Any], peaks: Dict[str, float]) -> work_lib.StepWork:
    return work_lib.StepWork(work_lib.Shapes.from_config(c), peaks)


served_gaps = reference.served_gaps
control_gaps = reference.control_gaps
