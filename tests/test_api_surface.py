"""Public-API surface lock.

Golden lists of the exported names of the packages whose surface downstream
code (benchmarks, examples, serving deployments) programs against. An
accidental rename / deletion / unexported addition fails here before it
breaks a consumer; a *deliberate* API change updates the golden list in the
same PR (that diff is the review signal).
"""
import importlib

import pytest

GOLDEN = {
    "repro": {
        "configs", "core", "checkpoint", "data", "distributed", "kernels",
        "launch", "models", "obs", "optim", "paging", "serving", "spec",
        "TernaryWeight", "Dense2Bit", "Tiled", "Bitplane", "Base3", "pack",
        "ternary_gemm", "ternary_gemm_plan",
    },
    "repro.core": {
        "formats", "quantize", "weights",
        "TernaryWeight", "Dense2Bit", "Tiled", "Bitplane", "Base3",
        "pack", "register_format",
    },
    "repro.core.weights": {
        "TernaryWeight", "Dense2Bit", "Tiled", "Bitplane", "Base3",
        "FORMATS", "register_format", "pack", "ternarize_stacked",
        "validate_spec_twin",
    },
    "repro.distributed": {
        "sharding", "compression", "fault_tolerance", "tp", "router",
    },
    "repro.distributed.tp": {
        "parse_mesh", "replica_meshes", "validate_param_specs",
        "shard_params", "cache_sharding", "replicated_sharding",
        "device_put_cache", "mesh_axis_sizes", "gemm_shard_fn",
    },
    "repro.distributed.router": {"Router"},
    "repro.kernels": {
        "ternary_gemm", "ternary_gemm_plan", "GemmPlan",
        "register_kernel", "kernel_registry", "serving_phase",
        "SERVING_PHASES",
        "fused_mlp", "fused_mlp_plan", "FusedMlpPlan",
        "register_fused", "fused_registry", "precompute_fused_plans",
        "fused_mlp_pallas",
        "pack_weights", "pack_weights_tiled",
        "ternary_gemm_pallas", "ternary_gemm_skip_pallas",
        "ternary_gemm_skip_db_pallas",
        "ternary_gemm_bitplane", "K_PER_WORD", "flash_attention_pallas",
        "paged_decode_attention", "paged_window_attention",
        "register_paged_attn", "paged_attention_registry",
        "Autotuner", "BlockConfig", "FusedBlockConfig", "get_tuner",
    },
    "repro.serving": {
        "ContinuousScheduler", "Request", "RequestQueue", "SlotPool",
        "FaultConfig", "FaultInjector", "ResilienceConfig",
        "SchedConfig", "SLOClass", "SLOQueue",
        "Arrival", "TrafficConfig", "make_schedule", "run_open_loop",
    },
    "repro.serving.sched": {
        "ChunkRunner", "DEFAULT_SLO_CLASSES", "SLOClass", "SLOQueue",
        "SchedConfig", "plan_chunks",
    },
    "repro.paging": {
        "PagePool", "Admission", "PrefixCache", "Int8Pages",
        "page_keys", "tree_nbytes",
    },
    "repro.spec": {
        "SpecConfig", "DraftModel", "Draft", "build_draft",
        "resparsify", "layer_skip", "external",
        "make_draft_round", "make_verify_step", "longest_prefix_match",
        "rollback_dense", "rollback_paged",
    },
    "repro.checkpoint": {"save", "restore", "latest_step",
                         "CheckpointCorruptError"},
    "repro.obs": {
        "clock", "trace", "metrics",
        "Tracer", "phase", "load_trace", "validate_events",
        "MetricsRegistry", "Counter", "Gauge", "Histogram", "Ewma",
        "RunningStat", "percentiles",
    },
}

# Formats every deployment depends on being registered + dispatchable.
GOLDEN_FORMATS = {"dense2bit", "tiled", "bitplane", "base3"}
GOLDEN_KERNELS = {
    ("dense2bit", "dense"), ("dense2bit", "ref"),
    ("tiled", "skip"), ("tiled", "skip_db"), ("tiled", "dense"),
    ("tiled", "ref"),
    ("bitplane", "bitplane"), ("bitplane", "bitplane_factorized"),
    ("bitplane", "ref"),
    ("base3", "ref"),
}
GOLDEN_PAGED_ATTN = {"jax", "pallas"}
# Autotune phase keys the serving engine traces under (prefill GEMM /
# decode GEMV / speculative verify small-GEMM / chunked-prefill window,
# DESIGN.md §10 + §14).
GOLDEN_PHASES = ("prefill", "decode", "verify", "chunk")


@pytest.mark.parametrize("module", sorted(GOLDEN))
def test_all_matches_golden(module):
    mod = importlib.import_module(module)
    assert set(mod.__all__) == GOLDEN[module], (
        f"{module}.__all__ drifted from the golden list — if intentional, "
        f"update tests/test_api_surface.py in the same change")


@pytest.mark.parametrize("module", sorted(GOLDEN))
def test_exports_resolve(module):
    mod = importlib.import_module(module)
    for name in GOLDEN[module]:
        assert getattr(mod, name, None) is not None, f"{module}.{name}"


def test_format_and_kernel_registries_locked():
    from repro.core import weights
    from repro.kernels import ops
    assert GOLDEN_FORMATS <= set(weights.FORMATS), (
        "a registered weight format disappeared")
    assert GOLDEN_KERNELS <= set(ops.kernel_registry()), (
        "a registered kernel lowering disappeared")
    assert GOLDEN_PAGED_ATTN <= set(ops.paged_attention_registry()), (
        "a registered paged-attention lowering disappeared")
    assert ops.SERVING_PHASES == GOLDEN_PHASES, (
        "the serving-phase autotune keys drifted from the golden tuple")


def test_legacy_shim_is_contained():
    """The old weight-operand union is gone — raw operands raise TypeError
    in ops (see test_weights_api), and no public module re-exports the
    legacy config type."""
    import repro.kernels as K
    assert not hasattr(K, "TernaryGemmConfig")
    assert not hasattr(importlib.import_module("repro.kernels.ops"),
                       "TernaryGemmConfig")
