"""Find a serving cell's knee once, by a sweep on the chip: run the cell
at each rate, each in a process of its own, and print for each whether
the backlog grew.  The benchmark's own runs never search for a rate;
the traffic file holds the one this sweep chose.

  python3 bench/tools/sweep.py --workload phi4-mini.chat \\
      --rates 2,3,4,5,6 --seconds 30 --seed 9200000020

A rate holds when the median time to first token of the last third of
its requests is at most twice that of the first third (a queue that
grows all through the window fails this).  The parent never imports
JAX: each rate's child holds the chip alone.
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def one(workload: str, rate: float, seconds: float, seed: int) -> dict:
    """Child: run the cell at ``rate``, return its numbers."""
    t0 = time.perf_counter()
    sys.path[0:1] = [ROOT, os.path.join(ROOT, "src")]
    import numpy as np

    from bench import harness
    cell = harness.Cell.find(workload)
    cell.traffic["rate_per_s"] = rate
    store = {}

    def keep(kind):
        window = kind.Runner.window

        def w(self, s):
            store["rec"] = window(self, s)
            return store["rec"]
        kind.Runner.window = w
    out = harness.run(cell, seed, seconds, False, t_start=t0, patch=keep)
    rec = store["rec"]
    ttft = [f - d for f, d in zip(rec["first"], rec["due"]) if f is not None]
    third = len(ttft) // 3
    first, last = ttft[:third], ttft[-third:] if third else []
    return {"rate": rate,
            "metrics": {k: v["value"] for k, v in out["metrics"].items()},
            "attempted": out["attempted"], "failed": out["failed"],
            "checks": {k: v["value"] for k, v in out["checks"].items()},
            "ttft_first_third_med": float(np.median(first)) if first else None,
            "ttft_last_third_med": float(np.median(last)) if last else None,
            "compiles_in_window": len(rec["compiles_in_window"]),
            "late_max_s": max(rec["late_s"]) if rec["late_s"] else None,
            "memory_peak_bytes": out["device"]["memory_peak_bytes"],
            "setup": out["setup"]}


def holds(r: dict) -> bool:
    a, b = r["ttft_first_third_med"], r["ttft_last_third_med"]
    return r["failed"] == 0 and a is not None and b is not None \
        and b <= max(2 * a, a + 0.5)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--child", type=float, default=None,
                    help=argparse.SUPPRESS)
    args = ap.parse_args()
    if args.child is not None:
        print(json.dumps(one(args.workload, args.child, args.seconds,
                             args.seed)), flush=True)
        return
    knee = None
    for rate in (float(r) for r in args.rates.split(",")):
        p = subprocess.run(
            [sys.executable, __file__, "--workload", args.workload,
             "--rates", args.rates, "--seconds", str(args.seconds),
             "--seed", str(args.seed), "--child", str(rate)],
            capture_output=True, text=True)
        if p.returncode != 0:
            print(json.dumps({"rate": rate, "error": p.stderr[-2000:]}))
            break
        r = json.loads(p.stdout.strip().splitlines()[-1])
        r["holds"] = holds(r)
        print(json.dumps(r), flush=True)
        if not r["holds"]:
            break
        knee = rate
    print(json.dumps({"knee": knee,
                      "rate": None if knee is None else 0.8 * knee}))


if __name__ == "__main__":
    main()
