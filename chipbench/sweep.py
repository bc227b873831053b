#!/usr/bin/env python3
"""Find an open-loop cell's knee: serve it at a list of Poisson rates.

    python3 chipbench/sweep.py --workload <cell> --seconds <s> \
        --seed <n> --rates <r>[,<r>...]

One process, programs compiled once.  Per rate, one JSON line: the
offered and completed request rates, the requests left unfinished after
the drain, and the 90th-percentile TTFT of the requests due in the first
and in the second half of the window.  At a rate the cell sustains all
requests finish and the second half's tail is no worse than the
first's; past the knee the queue grows through the window.  The cell's
traffic file then fixes its rate at about 0.8 x the knee.
"""
import argparse
import json
import math
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

import run  # noqa: E402
import stats  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    base = run.load_cell(args.workload)["mix"]
    for rate in (float(r) for r in args.rates.split(",")):
        arrivals = {**base["arrivals"], "kind": "poisson", "rate_rps": rate}
        one = argparse.Namespace(workload=args.workload, seed=args.seed,
                                 seconds=args.seconds, trace=0)
        seen = {}

        def keep(run_obj):
            seen["run"] = run_obj
        try:
            res = run.run_cell(one, mix_override={"arrivals": arrivals},
                               on_run=keep)
        except run.Refused as e:
            print(f"sweep: {e}", file=sys.stderr)
            return 2
        r = seen["run"]
        due = r.due_in_window()
        mid = (r.w0 + r.w1) / 2

        def p90(rs):
            t = [x.first - x.due if not math.isnan(x.first) else math.inf
                 for x in rs]
            return stats.percentile(t, 90) if t else None
        done = [x for x in due if x.n == x.max_tokens]
        print(json.dumps({
            "rate_rps": rate, "due": len(due),
            "completed_rps": len(done) / args.seconds,
            "unfinished": len(due) - len(done),
            "ttft_p90_first_half_s": p90([x for x in due if x.due < mid]),
            "ttft_p90_second_half_s": p90([x for x in due if x.due >= mid]),
            "output_tok_per_s": res["metrics"]["output_tok_per_s"]["value"],
            "correct": res["correct"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
