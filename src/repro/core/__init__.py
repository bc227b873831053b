"""repro.core — the paper's contribution: Near-Free Parallelism (NFP).

Public API:
  hardware:    HardwareSpec, TPU_V5E, H20/A800/H800, PRESETS, get_hardware
  arch:        ArchConfig, AttentionSpec, FFNSpec, SSMSpec, ShapeSpec
  granularity: GranularitySpec, select_q_block, select_token_block, ...
  nfp:         idle-compute baselines + NFP principle predictors
  simulate:    roofline+granularity latency simulator
  measure:     T(N) sweep + N_max(eps) extraction protocol
"""
from repro.core.arch import (LAYER_ATTN, LAYER_HYBRID, LAYER_SSM, LM_SHAPES,
                             ArchConfig, AttentionSpec, EncoderSpec, FFNSpec,
                             ShapeSpec, SSMSpec, shape_applicable)
from repro.core.granularity import (GranularitySpec, attn_padded_q, cdiv,
                                    m_attn, m_moe, moe_padded_tokens,
                                    moe_tau, round_up, select_q_block,
                                    select_scan_chunk, select_token_block)
from repro.core.hardware import (BYTES_BF16, H20, H800, A800, PRESETS,
                                 TPU_V5E, HardwareSpec, get_hardware)
from repro.core.measure import (LatencyCurve, balanced_moe_baseline_n,
                                extract_nmax, sensitivity_sweep,
                                staircase_boundaries, sweep_callable,
                                time_callable)
from repro.core.nfp import (NFPPrediction, ai_attn, ai_dense, ai_moe,
                            n_idle_attn, n_idle_attn_general, n_idle_dense,
                            n_idle_moe, n_idle_ssm, parallelism_budget,
                            predict_dense, predict_model,
                            predict_moe_balanced, predict_moe_skewed)
from repro.core.simulate import (ForwardCost, ModuleCost,
                                 attention_core_cost, decode_forward_cost,
                                 dense_ffn_cost, latency_curve,
                                 module_latency_curve, moe_ffn_cost,
                                 ssm_cost)

__all__ = [n for n in dir() if not n.startswith("_")]
