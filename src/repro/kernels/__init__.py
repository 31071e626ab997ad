from repro.kernels.autotune import (Autotuner, BlockConfig,
                                    FusedBlockConfig, get_tuner)
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.fused_mlp import fused_mlp_pallas
from repro.kernels.ops import (SERVING_PHASES, FusedMlpPlan, GemmPlan,
                               fused_mlp, fused_mlp_plan, fused_registry,
                               kernel_registry,
                               paged_attention_registry,
                               paged_decode_attention,
                               paged_window_attention, pack_weights,
                               pack_weights_tiled, precompute_fused_plans,
                               register_fused, register_kernel,
                               register_paged_attn, serving_phase,
                               ternary_gemm, ternary_gemm_plan)
from repro.kernels.ternary_gemm import (K_PER_WORD,
                                        ternary_gemm_pallas,
                                        ternary_gemm_skip_db_pallas,
                                        ternary_gemm_skip_pallas)
from repro.kernels.ternary_gemm_bitplane import ternary_gemm_bitplane

__all__ = ["ternary_gemm", "ternary_gemm_plan", "GemmPlan",
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
           "Autotuner", "BlockConfig", "FusedBlockConfig", "get_tuner"]
