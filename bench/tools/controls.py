"""Readings that set the limits of ``correct``, on the chip, at a cell's
own size: the program's numbers over many seeds, the lower-precision
control's, and the planted faults'.  The benchmark's own runs never run
this.

  python3 bench/tools/controls.py --workload <cell> --seeds 1,2,3 \\
      --seconds 15 [--fault half_batch] [--rate 1.6]

One JSON line per seed on stdout.  Serving: ``program`` is the widest
logit gap of the served tokens (the number ``correct`` compares),
``control`` the same gap for the tokens that the reference computed in
fp8 puts first.  Training: ``program`` holds the run's three numbers,
``control`` the fp8 reference's against the float32 reference, and
``fault`` the program's with the fault planted.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[0:1] = [ROOT, os.path.join(ROOT, "src")]

from bench import harness  # noqa: E402


def capture(store):
    def patch(kind):
        window = kind.Runner.window

        def keep(self, seconds):
            rec = window(self, seconds)
            store["runner"], store["record"] = self, rec
            return rec
        kind.Runner.window = keep
    return patch


def half_batch(kind):
    build = kind.Runner.build_step

    def broken(self):
        step = build(self)
        return lambda state, batch: step(
            state, {k: v[:v.shape[0] // 2] for k, v in batch.items()})
    kind.Runner.build_step = broken


FAULTS = {"half_batch": half_batch}


def serve_control(cell, seed, store) -> dict:
    from bench.kinds.serve_open_loop import pick_sample, served_gap
    rec = store["record"]
    sample = pick_sample(rec, seed, cell.traffic["check"])
    pairs = [(rec["prompts"][i], rec["served"][i]) for i in sample]
    return {"control": served_gap(harness.decoder_spec(cell.config), seed,
                                  cell.traffic, pairs, "fp8", control=True),
            "checked_tokens": sum(len(p[1]) for p in pairs)}


def train_control(cell, seed, store) -> dict:
    from bench.kinds.train_steps import compare, reference_readings
    r = store["runner"]
    spec, opt = r.spec, r.config["optimizer"]
    rows = r.traffic["check"]["rows_per_block"]
    ref = reference_readings(spec, opt, seed, r.check_batches, rows)
    low = reference_readings(spec, opt, seed, r.check_batches, rows, "fp8")
    return {"control": {k: v["value"] for k, v in
                        compare(low, ref, r.limits()).items()},
            "program_readings": r.readings, "reference_readings": ref}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=15.0)
    ap.add_argument("--fault", choices=sorted(FAULTS), default=None)
    ap.add_argument("--rate", type=float, default=None,
                    help="serving: arrival rate in place of the traffic "
                         "file's (for readings taken before it is set)")
    ap.add_argument("--control-first", type=int, default=1 << 30,
                    help="run the control on the first N seeds only")
    ap.add_argument("--fault-first", type=int, default=1 << 30,
                    help="plant the fault on the first N seeds only")
    args = ap.parse_args()
    for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
        cell = harness.Cell.find(args.workload)
        if args.rate:
            cell.traffic["rate_per_s"] = args.rate
        store: dict = {}
        t0 = time.perf_counter()
        res = harness.run(cell, seed, args.seconds, False, t_start=t0,
                          patch=capture(store))
        line = {"seed": seed, "correct": res["correct"],
                "program": {k: v["value"] for k, v in res["checks"].items()},
                "metrics": {k: v["value"] for k, v in res["metrics"].items()},
                "memory_peak_bytes": res["device"]["memory_peak_bytes"]}
        if i < args.control_first:
            kind = cell.traffic["kind"]
            line.update(serve_control(cell, seed, store)
                        if kind == "serve_open_loop"
                        else train_control(cell, seed, store))
        if args.fault and i < args.fault_first:
            fres = harness.run(cell, seed, args.seconds, False,
                               t_start=time.perf_counter(),
                               patch=FAULTS[args.fault])
            line["fault"] = {k: v["value"] for k, v in fres["checks"].items()}
        print(json.dumps(line), flush=True)
        store.clear()


if __name__ == "__main__":
    main()
