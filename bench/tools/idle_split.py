"""Where the device idles and where the decode program's time goes, from
the last traced run's profile (``bench/.traces``): device 0's idle time
in the ``bench.window`` span, split by the innermost ``engine.*`` span
the host was in, and the decode program's leaf-op time by the model
scope that names each op (``unscoped`` lists its heaviest ops).

  python3 bench/tools/idle_split.py [--top 12]
"""
from __future__ import annotations

import argparse
import collections
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def scope_of(tf_op: str, scopes) -> str:
    """The innermost model scope on an op's path, or ``unscoped``."""
    found = [p for p in tf_op.split("/") if p in scopes]
    return found[-1] if found else "unscoped"


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--top", type=int, default=12)
    args = ap.parse_args()
    sys.path[0:1] = [ROOT, os.path.join(ROOT, "src")]
    from bench import harness, program_spans
    from bench.trace import Trace, busy_intervals, leaf_ops, union_length
    tr = Trace.load(harness.TRACE_DIR)
    window = next((s, e) for n, s, e in tr.host if n == "bench.window")
    lo, hi = window
    dev_id = min(tr.devices)
    dev = tr.devices[dev_id]
    busy = busy_intervals(dev)
    spans = program_spans.load()
    split = program_spans.idle_by_span(spans, busy, window)
    steps = program_spans.named(spans, "engine.step", window)
    busy_s = union_length([iv for iv in busy if iv[1] > lo and iv[0] < hi])
    out = {"window_s": hi - lo, "idle_s": hi - lo - busy_s,
           "engine_steps": len(steps),
           "engine_step_mean_ms": 1e3 * sum(e - s for _, s, e, _ in steps)
           / max(len(steps), 1),
           "idle_by_span_s": dict(sorted(split.items(),
                                         key=lambda kv: -kv[1]))}

    metric = harness.load_module(
        os.path.join(ROOT, "bench", "layer_metrics",
                     "unscoped_share.decode.py"), "unscoped_share_decode")
    paths = program_spans.op_scopes(metric.PROGRAM).get(dev_id, {})
    by_scope: dict = collections.Counter()
    unscoped: dict = collections.Counter()
    for name, s, e, prog, _ in leaf_ops(dev):
        if metric.PROGRAM in prog and lo <= s <= hi:
            scope = scope_of(paths.get(name, ""), metric.SCOPES)
            by_scope[scope] += e - s
            if scope == "unscoped":
                unscoped[f"{name} [{paths.get(name, '')}]"] += e - s
    out["decode_op_s_by_scope"] = dict(by_scope.most_common())
    out["decode_unscoped_top_s"] = dict(unscoped.most_common(args.top))
    print(json.dumps(out, indent=1))


if __name__ == "__main__":
    main()
