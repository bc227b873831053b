"""Trace-driven serving benchmark: replay the pinned production-shape
trace (``repro.loadgen.pinned_spec``) through the real ``ServingLoop``
and emit the schema-versioned ``BENCH_serving.json`` scorecard.

The replay is fully deterministic on a CPU host: the trace is seeded,
and the clock is the roofline simulator's FULL-SIZE-config forward
latency (``repro.core.simulate.decode_forward_cost`` at ``TPU_V5E``)
injected as the loop's ``step_clock`` — the same pattern as
``benchmarks.calibration``.  Two same-seed runs must produce
byte-identical JSON (``--check`` asserts it; CI runs it per PR, so the
committed BENCH file tracks serving-latency drift across PRs).

The pinned serving config exercises every load-pressure policy at
once: a paged engine with a DELIBERATELY tight block pool (preemption
fires), ``AdmissionConfig`` backpressure + SLO-priority admission, and
a shared-prefix fleet tenant (prefix-cache hits).

Run:  PYTHONPATH=src python -m benchmarks.load_harness --requests 8 --out /tmp/BENCH_serving.json
"""
from __future__ import annotations

import argparse
import json

import jax

from repro.configs import get_config
from repro.core import GranularitySpec, TPU_V5E
from repro.core.simulate import decode_forward_cost
from repro.launch.compile_cache import enable_compile_cache
from repro.loadgen import generate_trace, pinned_spec, replay_trace
from repro.loadgen.stats import itls, percentile, ttft
from repro.models import init_model
from repro.serving import (AdmissionConfig, DecodeEngine, PagedKVConfig,
                           ServingLoop)

from benchmarks.common import emit

SCHEMA_VERSION = 1
ARCH = "stablelm_3b"
MODE = "speculative"
SLOTS = 4
MAX_LEN = 256
KV_BLOCK = 16            # XLA reference path: block = paging granularity
KV_BLOCKS = 24           # tight pool: ~38% of dense parity -> preemption
MAX_WAITING = 6
EPS = 0.2

CSV_HEADER = ("rid,tenant,slo_class,arrival_s,ttft_s,itl_p95_s,"
              "n_tokens,preemptions,rejected")

SERVING_KEYS = ("requests", "tokens", "forwards", "tokens_per_forward",
                "preemptions", "resumes", "rejections",
                "prefill_forwards", "prefill_positions_computed",
                "prefill_positions_saved", "kv_preemptions",
                "kv_preempt_blocks_freed")


def _clock(cfg_full):
    """Roofline TPU-v5e latency of one (SLOTS, width) forward at
    context ell — the virtual clock every replay second comes from."""
    g = GranularitySpec.for_backend(
        cfg_full.ffn.n_experts,
        head_dim=(cfg_full.attention.head_dim if cfg_full.attention
                  else 128))

    def clock(width: int, ell: int) -> float:
        return decode_forward_cost(
            cfg_full, SLOTS, width, max(int(ell), 1), g).time(TPU_V5E)
    return clock


def build_loop(seed: int = 0) -> ServingLoop:
    """The pinned serving stack (reduced engine for CPU-runnable
    weights, full-size config for the clock)."""
    cfg = get_config(ARCH, reduced=True)
    params = init_model(jax.random.PRNGKey(seed), cfg)
    eng = DecodeEngine(cfg, params, batch=SLOTS, max_len=MAX_LEN,
                       paged=PagedKVConfig(block_size=KV_BLOCK,
                                           n_blocks=KV_BLOCKS))
    return ServingLoop(
        eng, mode=MODE, eps=EPS, step_clock=_clock(get_config(ARCH)),
        admission=AdmissionConfig(max_waiting=MAX_WAITING,
                                  preemption=True))


def run_harness(n_requests: int = 32, seed: int = 20260808) -> dict:
    """One replay -> the BENCH payload dict (sorted-key serializable)."""
    trace = generate_trace(pinned_spec(seed=seed, n_requests=n_requests))
    report = replay_trace(build_loop(), trace)
    serving = report["serving"]
    payload = {
        "schema_version": SCHEMA_VERSION,
        "bench": "serving_load_harness",
        "clock": report["clock"],
        "hardware": "tpu_v5e",
        "pinned": {
            "arch": ARCH, "mode": MODE, "slots": SLOTS,
            "max_len": MAX_LEN, "kv_block_size": KV_BLOCK,
            "kv_blocks": KV_BLOCKS, "max_waiting": MAX_WAITING,
            "preemption": True, "eps": EPS,
            "trace_seed": seed, "trace_requests": n_requests,
        },
        "trace_fingerprint": report["trace_fingerprint"],
        "makespan_s": report["makespan_s"],
        "metrics": report["metrics"],
        "serving": {k: serving[k] for k in SERVING_KEYS if k in serving},
    }
    payload["records"] = report["records"]       # stripped before dump
    return payload


def to_json(payload: dict) -> str:
    slim = {k: v for k, v in payload.items() if k != "records"}
    return json.dumps(slim, sort_keys=True, indent=1) + "\n"


def csv_rows(payload: dict) -> list:
    rows = [CSV_HEADER]
    for r in payload["records"]:
        gaps = itls(r)
        t = ttft(r)
        p95 = f"{percentile(gaps, 95):.9f}" if gaps else ""
        rows.append(f"{r.rid},{r.tenant},{r.slo_class},{r.arrival_s:.9f},"
                    f"{'' if t is None else f'{t:.9f}'},{p95},"
                    f"{r.n_tokens},{r.preemptions},{int(r.rejected)}")
    return rows


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="BENCH_serving.json",
                    help="scorecard path (repo root by convention)")
    ap.add_argument("--csv", default=None,
                    help="also write the per-request CSV here (nightly "
                         "artifact)")
    ap.add_argument("--requests", type=int, default=32)
    ap.add_argument("--seed", type=int, default=20260808)
    ap.add_argument("--check", action="store_true",
                    help="replay twice and assert byte-identical JSON "
                         "(the determinism gate CI runs)")
    args = ap.parse_args()
    print("name,us_per_call,derived")
    payload = run_harness(args.requests, args.seed)
    text = to_json(payload)
    if args.check:
        again = to_json(run_harness(args.requests, args.seed))
        if text != again:
            raise SystemExit("NON-DETERMINISTIC: same-seed replays "
                             "produced different BENCH JSON")
    m = payload["metrics"]
    emit("load_harness/ttft_p95", m.get("ttft_p95_s", 0.0) * 1e6,
         f"p50={m.get('ttft_p50_s', 0):.6f};p99={m.get('ttft_p99_s', 0):.6f};"
         f"completed={m['completed']};rejected={m['rejected']}")
    emit("load_harness/goodput", m["goodput_tok_s"],
         f"throughput={m['throughput_tok_s']:.2f};"
         f"attainment={m['slo_attainment']};"
         f"preemptions={m['preemptions']}")
    with open(args.out, "w") as f:
        f.write(text)
    print(f"wrote {args.out}")
    if args.csv:
        with open(args.csv, "w") as f:
            f.write("\n".join(csv_rows(payload)) + "\n")
        print(f"wrote {args.csv}")


if __name__ == "__main__":
    main()
