#!/usr/bin/env python3
"""Find a cell's knee: the highest request rate at which the backlog does
not grow over the window.  Not run by the benchmark; it sets the rate that
a traffic file then states as a number.

    python bench/sweep.py --workload <cell> --seconds <s> --rates 0.5,1,2

Each rate is one run of ``run.py``'s window at that rate (the file's own
rate is replaced for the sweep only), one after another in this process.
A rate sustains when the queue at the window's close is no longer than at
its opening and the median TTFT of the window's last third is under twice
that of its first third.  Open-loop mixes only.
"""
from __future__ import annotations

import argparse
import json
import sys

import run


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seconds", type=float, default=30.0)
    p.add_argument("--seed", type=int, default=1)
    p.add_argument("--rates", required=True,
                   help="comma-separated requests per second")
    a = p.parse_args(argv)
    spec = run.load_spec(a.workload)
    traffic = spec["traffic"]
    if "documents" in traffic:
        asks = sum(traffic["documents"]["asks"]) / 2
        key, scale = "session_rate_per_s", 1.0 / asks
    else:
        key, scale = "rate_per_s", 1.0
    rows = []
    for i, rate in enumerate(float(r) for r in a.rates.split(",")):
        traffic[key] = rate * scale
        args = run.parse(["--workload", a.workload, "--seed",
                          str(a.seed + i), "--seconds", str(a.seconds)])
        d = {}
        out = run.run(args, spec, diag=d)
        first = d.get("ttft_p50_first_third", float("nan"))
        last = d.get("ttft_p50_last_third", float("nan"))
        row = {"rate_per_s": rate, "offered_per_s": d["offered_per_s"],
               "queued_open": d["queued_open"],
               "queued_close": d["queued_close"],
               "ttft_p50_first_third": first, "ttft_p50_last_third": last,
               "sustained": d["queued_close"] <= d["queued_open"]
               and last < 2 * first,
               "correct": out["correct"],
               "max_logit_gap": out["check"]["max_logit_gap"]["value"],
               **{k: v["value"] for k, v in out["metrics"].items()}}
        rows.append(row)
        print(json.dumps(row), flush=True)
    ok = [r["rate_per_s"] for r in rows if r["sustained"]]
    print(json.dumps({"knee_per_s": max(ok) if ok else None}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
