"""NFP deployment survey: the paper's Table 24 as a living lookup over
all 10 assigned architectures x hardware targets x batch x context.

Run: PYTHONPATH=src python examples/nfp_survey.py
"""
from repro.configs import ARCH_IDS, get_config
from repro.core import (GranularitySpec, PRESETS, predict_model)


def main():
    print(f"{'arch':26s} {'hw':8s} {'b':>3s} {'L':>6s} "
          f"{'N_max':>6s} {'idle':>8s} {'over':>6s}  limiting")
    for hw_name in ("tpu_v5e", "h20", "h800"):
        hw = PRESETS[hw_name]
        for arch in ARCH_IDS:
            cfg = get_config(arch)
            g = GranularitySpec.for_backend(cfg.ffn.n_experts)
            for b in (1, 8):
                for ell in (4096, 32768):
                    p = predict_model(cfg, hw, g, b, ell)
                    idle = (f"{p.n_idle:.0f}" if p.n_idle != float("inf")
                            else "inf")
                    over = (f"{p.overprediction:.1f}x"
                            if p.overprediction != float("inf") else "-")
                    print(f"{arch:26s} {hw_name:8s} {b:3d} {ell:6d} "
                          f"{p.n_max:6.0f} {idle:>8s} {over:>6s}  "
                          f"{p.limiting}")


if __name__ == "__main__":
    main()
