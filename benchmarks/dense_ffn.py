"""Paper Fig. 1 / 5-7: Dense FFN module-level NFP.

Sweeps T(N) for the isolated two-GEMM FFN across batch sizes, extracts
N_max(0.2), and compares with the idle-compute prediction rho*s/(2b).
Paper module shape: d_model=4096, d_ff=9216 (LLaDA-2.1-Flash dims).

Rows:
  dense_ffn/T@{hw}/b{b}/N{n}          — modeled module latency (us)
  dense_ffn/nmax@{hw}/b{b}            — derived: measured;predicted
"""
from __future__ import annotations

from repro.configs import get_config
from repro.core import (GranularitySpec, TPU_V5E, extract_nmax, PRESETS,
                        n_idle_dense)
from repro.core.arch import ArchConfig, AttentionSpec, FFNSpec
from repro.core.simulate import dense_ffn_cost

from benchmarks.common import curve_from_pairs, emit, n_sweep

MODULE_CFG = ArchConfig(
    name="dense-ffn-module", family="dense", n_layers=1, d_model=4096,
    vocab_size=1,
    attention=AttentionSpec(n_heads=32, n_kv_heads=32, head_dim=128),
    ffn=FFNSpec(kind="dense", d_ff=9216, activation="gelu"))

BATCHES = (1, 2, 4, 8, 16, 32)


def run(hw_names=("tpu_v5e", "h20")) -> None:
    for hw_name in hw_names:
        hw = PRESETS[hw_name]
        for b in BATCHES:
            pairs = []
            for n in n_sweep(2048):
                c = dense_ffn_cost(MODULE_CFG, b, n)
                t = c.time(hw)
                pairs.append((n, t))
                if n in (1, 16, 64, 256):
                    emit(f"dense_ffn/T@{hw_name}/b{b}/N{n}", t * 1e6,
                         c.bound(hw))
            curve = curve_from_pairs(pairs)
            measured = extract_nmax(curve, 0.2)
            predicted = n_idle_dense(hw.rho, b)
            emit(f"dense_ffn/nmax@{hw_name}/b{b}", curve.baseline_time * 1e6,
                 f"measured={measured};idle_pred={predicted:.1f}")


if __name__ == "__main__":
    run()
