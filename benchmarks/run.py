"""Benchmark harness entry point — one module per paper table/figure.

``PYTHONPATH=src python -m benchmarks.run``
Prints ``name,us_per_call,derived`` CSV.  Sections:
  dense_ffn     — paper Fig. 1 / 5-7
  moe_ffn       — paper Fig. 2 / 8-19, Tables 20-23
  attention     — paper Fig. 3 / 20-25, Tables 18-19
  model_nfp     — paper Fig. 4 / 26-37
  sensitivity   — paper App. I Tables 17-23
  lookup        — paper Table 24 (+ TPU v5e / 10-arch extension)
  roofline      — brief deliverable (g), from dry-run artifacts
  cpu_wallclock — real-silicon sanity sweeps
  serving_throughput — scheduler tokens/s vs concurrency (NFP budget)
  calibration   — empirical NFP calibration + budget-controlled serving
"""
from __future__ import annotations

import sys
import traceback


def main() -> None:
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    from benchmarks import (attention, calibration, cpu_wallclock,
                            dense_ffn, lookup, model_nfp, moe_ffn,
                            roofline, sensitivity, serving_throughput)
    print("name,us_per_call,derived")
    sections = [
        ("dense_ffn", dense_ffn.run),
        ("moe_ffn", moe_ffn.run),
        ("attention", attention.run),
        ("model_nfp", model_nfp.run),
        ("sensitivity", sensitivity.run),
        ("lookup", lookup.run),
        ("roofline", roofline.run),
        ("cpu_wallclock", cpu_wallclock.run),
        ("serving_throughput", serving_throughput.run),
        ("calibration", calibration.run),
    ]
    failed = []
    for name, fn in sections:
        try:
            fn()
        except Exception as e:                                # noqa: BLE001
            failed.append(name)
            print(f"{name}/ERROR,0,{type(e).__name__}:{e}", file=sys.stderr)
            traceback.print_exc()
    if failed:
        raise SystemExit(f"benchmark sections failed: {failed}")


if __name__ == "__main__":
    main()
