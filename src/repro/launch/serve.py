"""Production serving launcher: multi-position decode with the NFP budget.

Single-request (algorithm drivers):
  PYTHONPATH=src python -m repro.launch.serve --arch stablelm_3b --tiny \
      --algorithm speculative --tokens 48

Multi-request (budget-aware continuous batching):
  PYTHONPATH=src python -m repro.launch.serve --arch stablelm_3b --tiny \
      --requests 8 --slots 4 --serve-mode speculative --tokens 32

Trace replay (production-shaped traffic on the simulated clock):
  PYTHONPATH=src python -m repro.launch.serve --arch stablelm_3b --tiny \
      --trace pinned --requests 8 --slots 4 --serve-mode speculative

Loads (or random-inits) a model, builds the decode engine, selects the
parallelism level from the NFP principle for the current hardware +
batch + context, and serves generation — one request through a
parallel-decoding driver, or many through the ServingLoop scheduler
that splits the budget across concurrent requests.
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.autotune import (BudgetController, calibrate_engine, load_table,
                            save_table, spec_fingerprint)
from repro.checkpoint import latest_step, restore
from repro.configs import get_config
from repro.core import get_hardware
from repro.launch.compile_cache import enable_compile_cache
from repro.models import init_model
from repro.serving import (DecodeEngine, DiffusionBlockDecoder,
                           MTPDecoder, PagedKVConfig, ServingLoop,
                           SpeculativeDecoder, init_mtp_heads)


def _single_request(args, cfg, params) -> None:
    eng = DecodeEngine(cfg, params, batch=args.batch, max_len=args.max_len,
                       hardware=get_hardware(args.hardware),
                       use_kernel=args.use_kernel)
    prompt = jax.random.randint(jax.random.PRNGKey(1),
                                (args.batch, args.prompt_len), 0,
                                cfg.vocab_size)
    t0 = time.time()
    if args.algorithm == "greedy":
        out = np.asarray(eng.greedy_generate(prompt, args.tokens)[0])
        stats = {"tokens": args.tokens, "forwards": args.tokens}
    elif args.algorithm == "speculative":
        out, stats = SpeculativeDecoder(eng).generate(prompt, args.tokens)
    elif args.algorithm == "mtp":
        heads = init_mtp_heads(jax.random.PRNGKey(5), cfg.d_model,
                               cfg.vocab_size, n_heads=4)
        out, stats = MTPDecoder(eng, heads).generate(prompt, args.tokens)
    else:
        out, stats = DiffusionBlockDecoder(eng).generate(prompt, args.tokens)
    dt = time.time() - t0
    print(f"arch={cfg.name} algo={args.algorithm} "
          f"nfp_budget={eng.nfp_budget()}")
    print(f"generated {stats['tokens']} tokens in {dt:.2f}s "
          f"({stats.get('forwards', '?')} forwards, "
          f"{stats.get('tokens_per_forward', 1):.2f} tok/fwd)")
    print("tokens:", out[:32], "...")


def _calibration_controller(args, eng):
    """--calibration {run,load}: produce/load the calibration artifact
    and wrap it in a BudgetController for the serving loop."""
    key = spec_fingerprint(eng.cfg, eng.hardware, eng.gran,
                           (eng.use_kernel,), eng.batch, eps=0.2)
    if args.calibration == "run":
        table = calibrate_engine(eng, modes=(args.serve_mode,))
        save_table(table, args.calibration_path)
        print(f"calibration: swept {len(table.buckets())} context buckets "
              f"via {table.backend} backend -> {args.calibration_path} "
              f"(key {table.key})")
    else:
        table = load_table(args.calibration_path, expect_key=key)
        print(f"calibration: loaded {args.calibration_path} "
              f"({table.backend} backend, key {table.key})")
    for e in sorted(table.entries, key=lambda e: e.ell):
        if e.mode == args.serve_mode and e.use_kernel == eng.use_kernel:
            print(f"  L<={e.ell}: analytic={e.analytic_nmax} "
                  f"measured={e.measured_nmax} "
                  f"calibrated={e.calibrated_budget} "
                  f"over-prediction={e.overprediction:.2f}x "
                  f"(limit={e.limiting})")
    return BudgetController(table=table)


def _multi_request(args, cfg, params) -> None:
    paged = None
    if args.kv_block_size > 0:
        paged = PagedKVConfig(block_size=args.kv_block_size,
                              n_blocks=args.kv_blocks or None)
    eng = DecodeEngine(cfg, params, batch=args.slots, max_len=args.max_len,
                       hardware=get_hardware(args.hardware),
                       use_kernel=args.use_kernel, paged=paged)
    kwargs = {}
    if args.serve_mode == "mtp":
        kwargs["mtp_heads"] = init_mtp_heads(
            jax.random.PRNGKey(5), cfg.d_model, cfg.vocab_size, n_heads=4)
    controller = None
    if args.calibration != "off":
        controller = _calibration_controller(args, eng)
    loop = ServingLoop(eng, mode=args.serve_mode, controller=controller,
                       **kwargs)
    for i in range(args.requests):
        prompt = jax.random.randint(jax.random.PRNGKey(100 + i),
                                    (args.prompt_len,), 0, cfg.vocab_size)
        loop.submit(np.asarray(prompt), args.tokens)
    t0 = time.time()
    results = loop.run()
    dt = time.time() - t0
    s = loop.stats()
    # serving-time budget: run() released the slots, so read it from the
    # step log rather than recomputing at an empty cache
    budgets = [e["budget"] for e in loop.step_log] or [loop.budget()]
    print(f"arch={cfg.name} mode={args.serve_mode} slots={args.slots} "
          f"requests={args.requests} "
          f"nfp_budget={min(budgets)}..{max(budgets)}")
    print(f"served {s['requests']} requests / {s['tokens']} tokens in "
          f"{dt:.2f}s  ({s['forwards']} forwards, "
          f"{s['tokens_per_forward']:.2f} tok/fwd, "
          f"max {s['max_positions_per_forward']} positions/fwd)")
    print(f"throughput: {s['tokens'] / max(dt, 1e-9):.1f} tok/s")
    if controller is not None:
        cs = s["controller"]
        line = (f"budget control: analytic~{s['mean_budget_analytic']:.1f} "
                f"applied~{s['mean_budget']:.1f}")
        if "mean_budget_calibrated" in s:
            line += f" calibrated~{s['mean_budget_calibrated']:.1f}"
        if "max_latency_ratio" in s:
            line += (f"  latency ratio mean={s['mean_latency_ratio']:.2f} "
                     f"max={s['max_latency_ratio']:.2f}")
        line += (f"  (shrinks={cs['shrinks']} probes={cs['probes']} "
                 f"gated={cs['gated']})")
        print(line)
    if paged is not None:
        print(f"paged kv: block_size={s['kv_block_size']} "
              f"blocks={s['kv_blocks']} peak_used={s['kv_blocks_peak']}  "
              f"prefix: {s['prefix_hits']}/{s['prefix_lookups']} hits, "
              f"{s['prefill_positions_saved']} prefill positions saved, "
              f"{s['cow_copies']} cow, {s['prefix_evictions']} evictions")
    for rid, toks in list(results.items())[:4]:
        print(f"  req {rid}: {toks[:16]} ...")


def _trace_replay(args, cfg, params) -> None:
    """--trace: replay a loadgen trace (the pinned BENCH spec or a
    trace JSON file) through the ServingLoop on the roofline-simulated
    clock of the FULL-SIZE --arch config, with backpressure + SLO-
    priority admission and preemption enabled."""
    from repro.core import GranularitySpec
    from repro.core.simulate import decode_forward_cost
    from repro.loadgen import (Trace, generate_trace, pinned_spec,
                               replay_trace)
    from repro.serving import AdmissionConfig

    if args.trace == "pinned":
        n = args.requests if args.requests > 0 else 32
        trace = generate_trace(pinned_spec(n_requests=n))
    else:
        with open(args.trace) as f:
            trace = Trace.from_json(f.read())
    cfg_full = get_config(args.arch)
    gran = GranularitySpec.for_backend(
        cfg_full.ffn.n_experts,
        head_dim=(cfg_full.attention.head_dim if cfg_full.attention
                  else 128))
    hw = get_hardware(args.hardware)

    def clock(width: int, ell: int) -> float:
        return decode_forward_cost(cfg_full, args.slots, width,
                                   max(int(ell), 1), gran).time(hw)

    paged = None
    if args.kv_block_size > 0:
        paged = PagedKVConfig(block_size=args.kv_block_size,
                              n_blocks=args.kv_blocks or None)
    eng = DecodeEngine(cfg, params, batch=args.slots, max_len=args.max_len,
                       hardware=hw, use_kernel=args.use_kernel, paged=paged)
    kwargs = {}
    if args.serve_mode == "mtp":
        kwargs["mtp_heads"] = init_mtp_heads(
            jax.random.PRNGKey(5), cfg.d_model, cfg.vocab_size, n_heads=4)
    loop = ServingLoop(
        eng, mode=args.serve_mode, step_clock=clock,
        admission=AdmissionConfig(
            max_waiting=args.max_waiting or None, preemption=True),
        **kwargs)
    report = replay_trace(loop, trace)
    m = report["metrics"]
    s = report["serving"]
    print(f"arch={cfg.name} mode={args.serve_mode} slots={args.slots} "
          f"trace={trace.fingerprint()} ({len(trace.requests)} requests)")
    print(f"replayed {m['completed']} requests / {m['tokens']} tokens in "
          f"{report['makespan_s'] * 1e3:.2f} virtual ms "
          f"({report['clock']} clock)")
    if m["completed"]:
        print(f"ttft p50/p95/p99: {m['ttft_p50_s'] * 1e3:.2f} / "
              f"{m['ttft_p95_s'] * 1e3:.2f} / "
              f"{m['ttft_p99_s'] * 1e3:.2f} ms")
    print(f"goodput {m['goodput_tok_s']:.1f} tok/s of "
          f"{m['throughput_tok_s']:.1f} tok/s "
          f"(SLO attainment {m['slo_attainment']})")
    print(f"pressure: {s['preemptions']} preemptions, {s['resumes']} "
          f"resumes, {s['rejections']} rejections")
    for name, g in m["per_class"].items():
        print(f"  [{name}] {g['completed']}/{g['requests']} completed, "
              f"{g['rejected']} rejected, "
              f"attainment={g['slo_attainment']}")


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="stablelm_3b")
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--algorithm", default="speculative",
                    choices=["greedy", "speculative", "diffusion", "mtp"])
    ap.add_argument("--tokens", type=int, default=48)
    ap.add_argument("--batch", type=int, default=1)
    ap.add_argument("--max-len", type=int, default=512)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--hardware", default=None,
                    help="hardware spec preset for the NFP budget; on a TPU "
                         "the attached chip's spec is used and a different "
                         "name is an error (default: tpu_v5e off-TPU)")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--use-kernel", action="store_true",
                    help="Pallas kernels: compiled on a TPU, run in the "
                         "Pallas interpreter elsewhere (CPU tests)")
    ap.add_argument("--requests", type=int, default=0,
                    help="multi-request mode: serve N concurrent requests "
                         "through the budget-aware scheduler")
    ap.add_argument("--slots", type=int, default=4,
                    help="cache slots (max concurrent requests)")
    ap.add_argument("--serve-mode", default="greedy",
                    choices=["greedy", "speculative", "diffusion", "mtp"],
                    help="scheduler mode for --requests")
    ap.add_argument("--kv-block-size", type=int, default=0,
                    help="paged KV cache block size in positions "
                         "(0 = dense per-slot cache); must divide "
                         "--max-len; multi-request mode only")
    ap.add_argument("--kv-blocks", type=int, default=0,
                    help="paged KV pool size in blocks (0 = dense-"
                         "parity default: slots * max_len / block)")
    ap.add_argument("--calibration", default="off",
                    choices=["off", "load", "run"],
                    help="empirical NFP calibration for the scheduler: "
                         "'run' sweeps T(N) on the engine (roofline-"
                         "simulator fallback without an accelerator), "
                         "saves the artifact, and serves with the "
                         "BudgetController; 'load' serves with a saved "
                         "artifact (refusing a stale spec hash)")
    ap.add_argument("--calibration-path", default="nfp_calibration.json",
                    help="calibration artifact path for --calibration")
    ap.add_argument("--trace", default=None,
                    help="replay a loadgen trace through the scheduler: "
                         "'pinned' (the BENCH spec, sized by --requests) "
                         "or a trace JSON path (repro.loadgen.Trace)")
    ap.add_argument("--max-waiting", type=int, default=0,
                    help="trace mode: bound the waiting queue "
                         "(backpressure; 0 = unbounded)")
    args = ap.parse_args()
    if args.trace is not None:
        cfg = get_config(args.arch, reduced=args.tiny)
        params = init_model(jax.random.PRNGKey(0), cfg)
        _trace_replay(args, cfg, params)
        return
    if args.kv_block_size > 0 and args.requests <= 0:
        ap.error("--kv-block-size serves the multi-request scheduler; "
                 "add --requests N")
    if args.kv_blocks > 0 and args.kv_block_size <= 0:
        ap.error("--kv-blocks sizes the paged pool; add --kv-block-size")
    if args.calibration != "off" and args.requests <= 0:
        ap.error("--calibration tunes the multi-request scheduler; "
                 "add --requests N")

    cfg = get_config(args.arch, reduced=args.tiny)
    params = init_model(jax.random.PRNGKey(0), cfg)
    if args.ckpt_dir and latest_step(args.ckpt_dir) is not None:
        (restored, _) = restore(args.ckpt_dir, {"params": params})
        params = restored["params"]
        print(f"loaded checkpoint from {args.ckpt_dir}")

    if args.requests > 0:
        _multi_request(args, cfg, params)
    else:
        _single_request(args, cfg, params)


if __name__ == "__main__":
    main()
