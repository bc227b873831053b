#!/usr/bin/env python3
"""Readings that a cell's correctness limit is set from.

    python3 chipbench/control.py --workload <cell> --seconds <s> \
        --seeds <n>[,<n>...]

For each seed, in one process (programs compile once): a run of the
cell at its own load for ``--seconds``, then the gaps of the served
tokens against the float32 reference (the program's readings), and the
gaps of the tokens that the reference rounded to float8 puts first at
the same positions (the control's readings), each as the widest and
the mean gap.  The last line is a JSON summary: for each number, the
largest program reading and the smallest control reading over the
seeds.  The benchmark's own runs never run
the control.
"""
import argparse
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

import run  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)
    rows = []
    for seed in (int(s) for s in args.seeds.split(",")):
        one = argparse.Namespace(workload=args.workload, seed=seed,
                                 seconds=args.seconds, trace=0)
        try:
            res = run.run_cell(one, control=True)
        except run.Refused as e:
            print(f"control: {e}", file=sys.stderr)
            return 2
        row = {"seed": seed, "correct": res["correct"],
               "program": res.get("readings"), "control": res.get("control"),
               "attempted": res["attempted"]}
        print(json.dumps(row), flush=True)
        rows.append(row)
    summary = {"workload": args.workload, "seeds": len(rows)}
    for name in ("max_logit_gap", "mean_logit_gap"):
        progs = [r["program"][name] for r in rows if r["program"]]
        ctrls = [r["control"][name] for r in rows if r["control"]]
        summary[name] = {"program_max": max(progs) if progs else None,
                         "control_min": min(ctrls) if ctrls else None}
    print(json.dumps(summary))
    return 0


if __name__ == "__main__":
    sys.exit(main())
