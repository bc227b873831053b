"""Paper Fig. 3 / 20-25 + Tables 18-19: Attention module-level NFP.

Decode MHA over a KV cache (n_heads=32, head_dim=128, d_kv=4096, paper
App. C.4), L swept 256..32k.  The measured boundary comes from the
simulated T(N) whose physical FLOPs use OUR Pallas kernel's q-tile
padding; the idle-compute prediction is Eq. 11.  The headline result is
L-independence of N_max (= q_block) vs the L-dependent idle prediction.
"""
from __future__ import annotations

from repro.core import (GranularitySpec, extract_nmax, PRESETS,
                        m_attn, n_idle_attn)
from repro.core.arch import ArchConfig, AttentionSpec, FFNSpec
from repro.core.simulate import attention_core_cost

from benchmarks.common import curve_from_pairs, emit, n_sweep

MODULE_CFG = ArchConfig(
    name="attn-module", family="dense", n_layers=1, d_model=4096,
    vocab_size=1,
    attention=AttentionSpec(kind="gqa", n_heads=32, n_kv_heads=32,
                            head_dim=128),
    ffn=FFNSpec(kind="none"))

L_SWEEP = (256, 512, 1024, 2048, 4096, 8192, 16384, 32768)


def run(hw_names=("tpu_v5e", "h20")) -> None:
    gran = GranularitySpec.for_backend()
    for hw_name in hw_names:
        hw = PRESETS[hw_name]
        for ell in L_SWEEP:
            pairs = []
            for n in n_sweep(512):
                c = attention_core_cost(MODULE_CFG, 1, n, ell, gran)
                pairs.append((n, c.time(hw)))
            curve = curve_from_pairs(pairs)
            measured = extract_nmax(curve, 0.2)
            idle = n_idle_attn(hw.rho, ell)
            emit(f"attention/nmax@{hw_name}/L{ell}",
                 curve.baseline_time * 1e6,
                 f"measured={measured};tile_pred={m_attn()};"
                 f"idle={idle if idle != float('inf') else 'inf'}")
        # staircase evidence: padded FLOPs jump exactly at q_block
        qb = m_attn()
        c_at = attention_core_cost(MODULE_CFG, 1, qb, 8192, gran)
        c_over = attention_core_cost(MODULE_CFG, 1, qb + 1, 8192, gran)
        emit(f"attention/tile_staircase@{hw_name}", c_at.flops / 1e6,
             f"flops_at_tile={c_at.flops/1e6:.1f};"
             f"flops_over={c_over.flops/1e6:.1f}")


if __name__ == "__main__":
    run()
