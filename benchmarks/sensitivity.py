"""Paper App. I (Tables 17-23): tolerance-threshold sensitivity.

N_max extracted at eps in {0.05, 0.10, 0.15, 0.20, 0.30} for the Dense
FFN (batch sweep), Attention (L sweep) and MoE (k sweep, both routings)
modules.  Granularity-governed modules must be ~eps-invariant; Dense FFN
may shift by one sampled step.
"""
from __future__ import annotations

from repro.core import (GranularitySpec, balanced_moe_baseline_n,
                        PRESETS, sensitivity_sweep)
from repro.core.simulate import (attention_core_cost, dense_ffn_cost,
                                 moe_ffn_cost)

from benchmarks.attention import MODULE_CFG as ATTN_CFG
from benchmarks.common import curve_from_pairs, emit, n_sweep
from benchmarks.dense_ffn import MODULE_CFG as DENSE_CFG
from benchmarks.moe_ffn import E, module_cfg

EPS = (0.05, 0.10, 0.15, 0.20, 0.30)


def _fmt(sweep):
    return ";".join(f"eps{e}={v}" for e, v in sorted(sweep.items()))


def run(hw_names=("tpu_v5e",)) -> None:
    gran = GranularitySpec.for_backend(n_experts=E)
    for hw_name in hw_names:
        hw = PRESETS[hw_name]
        for b in (1, 4, 16):
            pairs = [(n, dense_ffn_cost(DENSE_CFG, b, n).time(hw))
                     for n in n_sweep(1024)]
            c = curve_from_pairs(pairs)
            emit(f"sensitivity/dense@{hw_name}/b{b}",
                 c.baseline_time * 1e6, _fmt(sensitivity_sweep(c, EPS)))
        for ell in (256, 4096, 32768):
            pairs = [(n, attention_core_cost(ATTN_CFG, 1, n, ell, gran)
                      .time(hw)) for n in n_sweep(512)]
            c = curve_from_pairs(pairs)
            emit(f"sensitivity/attn@{hw_name}/L{ell}",
                 c.baseline_time * 1e6, _fmt(sensitivity_sweep(c, EPS)))
        for routing in ("balanced", "skewed"):
            for k in (8, 64, 256):
                cfg = module_cfg(k)
                base_n = (balanced_moe_baseline_n(E, 1, k)
                          if routing == "balanced" else 1)
                pairs = [(n, moe_ffn_cost(cfg, 1, n, gran, routing).time(hw))
                         for n in sorted(set(n_sweep(1024) + [base_n]))]
                c = curve_from_pairs(pairs, baseline_n=base_n)
                emit(f"sensitivity/moe@{hw_name}/{routing}/k{k}",
                     c.baseline_time * 1e6, _fmt(sensitivity_sweep(c, EPS)))


if __name__ == "__main__":
    run()
