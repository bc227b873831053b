"""Paper Table 24: deployment lookup — idle-compute baseline vs NFP
principle, with over-prediction factors.  Extended beyond the paper with
TPU v5e rows and all 10 assigned architectures (the survey the paper's
Sec. 6 proposes as 'a deployment lookup').
"""
from __future__ import annotations

from repro.configs import ARCH_IDS, get_config
from repro.core import (GranularitySpec, PRESETS, predict_dense,
                        predict_model, predict_moe_balanced,
                        predict_moe_skewed)


def _emit_row(name, pred):
    over = pred.overprediction
    over_s = f"{over:.1f}x" if over != float("inf") else "inf"
    print(f"{name},{pred.n_max:.0f},"
          f"idle={pred.n_idle if pred.n_idle != float('inf') else 'inf'};"
          f"limit={pred.limiting};over={over_s}")


def run(hw_names=("h20", "a800", "h800", "tpu_v5e")) -> None:
    g256 = GranularitySpec.for_backend(n_experts=256)
    # --- the paper's own Table 24 rows ------------------------------------
    for hw_name in ("h20", "a800", "h800"):
        hw = PRESETS[hw_name]
        for b in (1, 4, 8):
            _emit_row(f"lookup/paper/dense@{hw_name}/b{b}",
                      predict_dense(hw, g256, b))
        for k in (8, 32, 64):
            _emit_row(f"lookup/paper/moe_bal@{hw_name}/k{k}",
                      predict_moe_balanced(hw, g256, 256, k, 512))
        _emit_row(f"lookup/paper/moe_skew@{hw_name}/k8",
                  predict_moe_skewed(hw, g256, 8, 512))
    # --- beyond paper: the 10 assigned archs on TPU v5e -------------------
    hw = PRESETS["tpu_v5e"]
    for arch in ARCH_IDS:
        cfg = get_config(arch)
        g = GranularitySpec.for_backend(cfg.ffn.n_experts)
        for b in (1, 8):
            for ell in (4096, 32768):
                pred = predict_model(cfg, hw, g, b, ell)
                _emit_row(f"lookup/tpu_v5e/{arch}/b{b}/L{ell}", pred)


if __name__ == "__main__":
    run()
