"""Paper Fig. 2 / 8-19 + Tables 20-23: MoE FFN module-level NFP.

Load-balanced (upper bound) and load-skewed (lower bound) controlled
routing, k swept 2..256, E=256, d_model=4096, expert d_ff=1024 (paper
App. C.3).  The physical padded-FLOPs staircase comes from the SAME
block-alignment math the Pallas kernel executes (core.granularity).

Balanced baseline is N_bal0 = ceil(E/(b*k)) (Eq. 26).
Predictions: balanced min(M_moe*E/k, tau) (module level: no attention
term), skewed M_moe.
"""
from __future__ import annotations

from repro.core import (GranularitySpec, balanced_moe_baseline_n,
                        extract_nmax, PRESETS, m_moe, moe_tau,
                        n_idle_moe)
from repro.core.arch import ArchConfig, AttentionSpec, FFNSpec
from repro.core.simulate import moe_ffn_cost

from benchmarks.common import curve_from_pairs, emit, n_sweep

E = 256
K_SWEEP = (2, 4, 8, 16, 32, 64, 128, 256)


def module_cfg(k: int) -> ArchConfig:
    return ArchConfig(
        name="moe-ffn-module", family="moe", n_layers=1, d_model=4096,
        vocab_size=1, attention=None,
        ffn=FFNSpec(kind="moe", d_ff=1024, activation="gelu",
                    n_experts=E, top_k=k))


def run(hw_names=("tpu_v5e", "h20")) -> None:
    gran = GranularitySpec.for_backend(n_experts=E)
    for hw_name in hw_names:
        hw = PRESETS[hw_name]
        for routing in ("balanced", "skewed"):
            for k in K_SWEEP:
                cfg = module_cfg(k)
                base_n = (balanced_moe_baseline_n(E, 1, k)
                          if routing == "balanced" else 1)
                pairs = []
                for n in sorted(set(n_sweep(1024) + [base_n])):
                    c = moe_ffn_cost(cfg, 1, n, gran, routing)
                    pairs.append((n, c.time(hw)))
                curve = curve_from_pairs(pairs, baseline_n=base_n)
                measured = extract_nmax(curve, 0.2)
                if routing == "balanced":
                    pred = min(gran.m_moe * E / k, moe_tau(E))
                    e_act = E
                else:
                    pred = gran.m_moe
                    e_act = k
                idle = n_idle_moe(hw.rho, 1, k, e_act, 1024)
                emit(f"moe_ffn/nmax@{hw_name}/{routing}/k{k}",
                     curve.baseline_time * 1e6,
                     f"measured={measured};principle={pred:.0f};"
                     f"idle={idle:.0f}")
                # staircase evidence (runtime padded FLOPs, Fig. 2d)
                f1 = moe_ffn_cost(cfg, 1, base_n, gran, routing)
                f2 = moe_ffn_cost(cfg, 1, base_n + 1, gran, routing)
                emit(f"moe_ffn/padded_flops@{hw_name}/{routing}/k{k}",
                     f1.flops / 1e6,
                     f"logical={f1.logical_flops/1e6:.1f};"
                     f"next_n_flops={f2.flops/1e6:.1f}")


if __name__ == "__main__":
    run()
