"""Parallel-decoding serving demo: AR baseline vs NFP-budgeted
speculative decoding vs diffusion-style block decoding on one model.

Demonstrates the paper's capacity-normalized evaluation (Sec. J.2.3):
the same system-side budget, different algorithm-side utilization.
Each method serves the prompt through a ServingLoop with one slot.

Run: PYTHONPATH=src python examples/serve_parallel_decode.py
"""
import time

import jax
import numpy as np

from repro.configs import get_config
from repro.models import init_model
from repro.serving import DecodeEngine, ServingLoop

TOKENS = 48


def main():
    cfg = get_config("stablelm_3b", reduced=True)
    params = init_model(jax.random.PRNGKey(0), cfg)
    prompt = np.asarray(jax.random.randint(jax.random.PRNGKey(1), (12,), 0,
                                           cfg.vocab_size))

    def fresh():
        return DecodeEngine(cfg, params, batch=1, max_len=512)

    def serve(name, **loop_kw):
        loop = ServingLoop(fresh(), **loop_kw)
        loop.submit(prompt, TOKENS)
        t0 = time.perf_counter()
        toks = loop.run()[0]
        dt = time.perf_counter() - t0
        s = loop.stats()
        print(f"{name:<16} {s['tokens']} tokens, {s['forwards']} forwards "
              f"({s['tokens_per_forward']:.2f} tok/fwd, "
              f"utilization {s['position_utilization']:.2f}), {dt:.2f}s")
        return toks

    # --- AR baseline (N=1 per forward) ------------------------------------
    t0 = time.perf_counter()
    ar = np.asarray(fresh().greedy_generate(prompt[None], TOKENS)[0])
    print(f"AR greedy:       {TOKENS} tokens, {TOKENS} forwards, "
          f"{time.perf_counter() - t0:.2f}s")

    # --- speculative, verification length from the NFP budget -------------
    budget = fresh().nfp_budget(ell=len(prompt))
    toks = serve("speculative:", mode="speculative",
                 max_width=min(budget, 9))
    print(f"  lossless vs AR: {bool(np.array_equal(ar, toks))}  "
          f"(NFP budget={budget})")

    # --- diffusion-style block decode --------------------------------------
    serve("diffusion-block:", mode="diffusion",
          block_size=min(budget - 1, 12), refine_steps=3)
    print("\ncapacity-normalized view: all methods spend positions from the"
          "\nsame near-free budget; tokens/forward is the algorithm-side"
          "\nutilization the paper separates from system capacity.")


if __name__ == "__main__":
    main()
