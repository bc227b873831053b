"""Ragged per-slot decode attention: granularity slack vs slot mix.

Sweeps the ragged Pallas decode-attention kernel over mixed-length slot
distributions at verification widths N = 1..16 (the scheduler's
per-request positions) and reports the kernel's physical work next to
the logical work:

  - uniform:   every slot at the same mid length (the aligned baseline —
               zero ragged win, pure q_block padding slack),
  - bimodal:   half the slots short, half long (continuous batching after
               a wave of admissions),
  - one_long:  one long slot, the rest short (the straggler pattern that
               scalar-length kernels pay worst-case kv work for).

For each point: wall time of one kernel call (compiled on a TPU; in the
Pallas interpreter on CPU, where it is relative, not absolute), executed
vs grid kv tiles (the per-row skip win), and query-row utilization inside
the q_block tile (the M_attn slack the NFP principle prices; rows =
slots * q_block physically).

Run:  PYTHONPATH=src python -m benchmarks.ragged_decode [--widths 1,2,4,8,16]
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.decode_attention.ops import (decode_attention_ragged,
                                                slack_report)

from benchmarks.common import emit

B = 8            # slots
S_MAX = 512      # allocated cache length
H, KV, DH = 8, 2, 64


def slot_mixes(s_max: int, b: int):
    short, long_ = 32, s_max - 32
    mid = s_max // 2
    return {
        "uniform": np.full(b, mid, np.int64),
        "bimodal": np.asarray([short, long_] * (b // 2), np.int64),
        "one_long": np.asarray([long_] + [short] * (b - 1), np.int64),
    }


def _time_call(q, kc, vc, lens, iters: int = 3) -> float:
    out = decode_attention_ragged(q, kc, vc, lens)
    out.block_until_ready()                       # compile + warm
    ts = []
    for _ in range(iters):
        t0 = time.perf_counter()
        decode_attention_ragged(q, kc, vc, lens).block_until_ready()
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts)) * 1e6


def run(widths) -> None:
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    kc = jax.random.normal(ks[1], (B, S_MAX, KV, DH), jnp.float32)
    vc = jax.random.normal(ks[2], (B, S_MAX, KV, DH), jnp.float32)
    for dist, lens_np in slot_mixes(S_MAX, B).items():
        lens = jnp.asarray(lens_np, jnp.int32)
        for n in widths:
            q = jax.random.normal(ks[0], (B, n, H, DH), jnp.float32)
            us = _time_call(q, kc, vc, lens)
            rep = slack_report(n, lens_np, S_MAX, head_dim=DH)
            emit(f"ragged_decode/{dist}/n{n}", us,
                 f"q_block={rep['q_block']};row_util={rep['row_utilization']:.4f};"
                 f"tiles_exec={rep['kv_tiles_executed']};"
                 f"tiles_grid={rep['kv_tiles_grid']};"
                 f"tiles_skipped={rep['kv_tiles_skipped']}")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--widths", default=",".join(str(i) for i in range(1, 17)))
    args = ap.parse_args()
    print("name,us_per_call,derived")
    run([int(w) for w in args.widths.split(",")])


if __name__ == "__main__":
    main()
