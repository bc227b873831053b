"""Paper Fig. 4 / 26-37: full-model NFP principle validation.

Dense (WeDLM-8B analogue) across batch sizes and MoE (LLaDA-2.1-mini
analogue) across routing cases and sequence lengths: the NFP principle's
closed-form prediction vs the boundary extracted from the simulated
full-model T(N) (every module's physical work from the kernel padding
rules).  Also reports the limiting module — the paper's Sec. 5.2
"MoE-limited -> Attention-limited" shift with L.
"""
from __future__ import annotations

from repro.configs import get_config
from repro.core import (GranularitySpec, extract_nmax, PRESETS,
                        latency_curve, predict_model)

from benchmarks.common import curve_from_pairs, emit, n_sweep


def run(hw_names=("tpu_v5e", "h20")) -> None:
    dense_cfg = get_config("wedlm8b_like")
    moe_cfg = get_config("llada_mini_like")
    g_dense = GranularitySpec.for_backend()
    g_moe = GranularitySpec.for_backend(n_experts=moe_cfg.ffn.n_experts)

    for hw_name in hw_names:
        hw = PRESETS[hw_name]
        # --- dense: batch sweep at L in {128..512} (paper G.2) -----------
        for ell in (128, 256, 512):
            for b in (1, 2, 4, 8):
                pairs = latency_curve(dense_cfg, hw, b, ell, n_sweep(512),
                                      g_dense)
                curve = curve_from_pairs(pairs)
                measured = extract_nmax(curve, 0.2)
                pred = predict_model(dense_cfg, hw, g_dense, b, ell)
                emit(f"model_nfp/dense@{hw_name}/L{ell}/b{b}",
                     curve.baseline_time * 1e6,
                     f"measured={measured};principle={pred.n_max:.0f};"
                     f"limit={pred.limiting};idle={pred.n_idle:.0f}")
        # --- MoE: routing x L sweep (paper G.3) ---------------------------
        from repro.core import balanced_moe_baseline_n
        for routing in ("balanced", "skewed"):
            base_n = (balanced_moe_baseline_n(moe_cfg.ffn.n_experts, 1,
                                              moe_cfg.ffn.top_k)
                      if routing == "balanced" else 1)
            for ell in (256, 4096, 16384, 32768):
                ns = sorted(set(n_sweep(512) + [base_n]))
                pairs = latency_curve(moe_cfg, hw, 1, ell, ns, g_moe,
                                      routing)
                curve = curve_from_pairs(pairs, baseline_n=base_n)
                measured = extract_nmax(curve, 0.2)
                pred = predict_model(moe_cfg, hw, g_moe, 1, ell, routing)
                emit(f"model_nfp/moe@{hw_name}/{routing}/L{ell}",
                     curve.baseline_time * 1e6,
                     f"measured={measured};principle={pred.n_max:.0f};"
                     f"limit={pred.limiting}")


if __name__ == "__main__":
    run()
