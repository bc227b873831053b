"""Quickstart: the NFP principle in five minutes.

1. Pick an architecture config and hardware.
2. Ask the NFP predictor how many decode positions are near-free.
3. Build a tiny model, run a multi-position decode forward, and check
   the simulated latency curve against the closed-form prediction.

Run: PYTHONPATH=src python examples/quickstart.py
"""
import jax

from repro.configs import get_config
from repro.core import (GranularitySpec, TPU_V5E, H20, LatencyCurve,
                        extract_nmax, latency_curve, predict_model)
from repro.models import init_model
from repro.serving import DecodeEngine


def main():
    # ---- 1. the paper's headline: idle-compute over-predicts -------------
    cfg = get_config("llada_mini_like")          # MoE: E=256, k=8
    gran = GranularitySpec.for_backend(n_experts=cfg.ffn.n_experts)
    pred = predict_model(cfg, H20, gran, b=1, ell=4096)
    print(f"[{cfg.name} @ H20]  NFP principle: N_max ~= {pred.n_max:.0f} "
          f"(limited by {pred.limiting})")
    from repro.core import predict_moe_balanced
    mod = predict_moe_balanced(H20, gran, cfg.ffn.n_experts, cfg.ffn.top_k,
                               cfg.ffn.d_ff)
    print(f"  module-level idle-compute intuition says {mod.n_idle:.0f} -> "
          f"over-predicts {mod.overprediction:.0f}x (paper Table 24)")

    # ---- 2. on the deployment target (TPU v5e) ---------------------------
    pred_tpu = predict_model(cfg, TPU_V5E, gran, b=1, ell=4096)
    print(f"[{cfg.name} @ TPU v5e]  N_max ~= {pred_tpu.n_max:.0f} "
          f"(limited by {pred_tpu.limiting}, rho={TPU_V5E.rho:.0f})")

    # ---- 3. simulated T(N) curve agrees with the closed form -------------
    from repro.core import balanced_moe_baseline_n
    base_n = balanced_moe_baseline_n(cfg.ffn.n_experts, 1, cfg.ffn.top_k)
    ns = sorted(set(range(1, 129)) | {base_n})
    pts = latency_curve(cfg, TPU_V5E, 1, 4096, ns, gran)
    curve = LatencyCurve([n for n, _ in pts], [t for _, t in pts],
                         baseline_n=base_n)   # Eq. 26 balanced baseline
    print(f"  simulated N_max(0.2) = {extract_nmax(curve, 0.2)} "
          f"(baseline N_bal0={base_n}); T(N_bal0) = "
          f"{curve.baseline_time*1e6:.0f}us")

    # ---- 4. run an ACTUAL multi-position decode forward (tiny model) -----
    small = get_config("llada_mini_like", reduced=True)
    params = init_model(jax.random.PRNGKey(0), small)
    eng = DecodeEngine(small, params, batch=1, max_len=128)
    prompt = jax.random.randint(jax.random.PRNGKey(1), (8,), 0,
                                small.vocab_size)
    eng.prefill_slots({0: prompt})                 # one request, slot 0
    budget = eng.nfp_budget(ell=len(prompt))
    n = min(budget, 16)
    logits, _, _ = eng.decode_slots(jax.random.randint(
        jax.random.PRNGKey(2), (1, n), 0, small.vocab_size))
    print(f"  tiny-model engine: budget={budget}, ran one decode forward "
          f"with N={n}, logits {logits.shape}")


if __name__ == "__main__":
    main()
