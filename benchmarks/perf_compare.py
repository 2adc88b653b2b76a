"""Perf-loop helper: diff two dry-run artifacts (baseline vs variant).

  PYTHONPATH=src python -m benchmarks.perf_compare \\
      artifacts/dryrun/llama3-405b__train_4k__2x16x16.json \\
      artifacts/dryrun/llama3-405b__train_4k__2x16x16__sp.json
"""
from __future__ import annotations

import json
import sys

from repro import hw


def load(path):
    return json.load(open(path))


def terms(rec):
    chip = hw.chip_spec(rec.get("device_kind"))
    h = rec["hlo"]
    return {
        "compute_s": h["flops"] / chip.peak_bf16_flops,
        "memory_s": h["bytes"] / chip.hbm_bw,
        "collective_s": (h["intra_pod_bytes"] / chip.ici_bw_per_link
                         + h["cross_pod_bytes"] / chip.dci_bw_per_chip
                         if rec["mesh"] != "16x16" else
                         h["collective_total_bytes"] / chip.ici_bw_per_link),
        "cross_pod_gb": h["cross_pod_bytes"] / 1e9,
        "coll_gb": h["collective_total_bytes"] / 1e9,
        "hbm_args_gb": rec["memory"].get("argument_size_in_bytes", 0) / 1e9,
        "hbm_temp_gb": rec["memory"].get("temp_size_in_bytes", 0) / 1e9,
        "flops": h["flops"],
        "bytes": h["bytes"],
    }


def main():
    a, b = load(sys.argv[1]), load(sys.argv[2])
    ta, tb = terms(a), terms(b)
    print(f"{'metric':18s} {'baseline':>14s} {'variant':>14s} {'delta':>9s}")
    for k in ta:
        va, vb = ta[k], tb[k]
        d = (vb - va) / va * 100 if va else float("inf")
        print(f"{k:18s} {va:14.4g} {vb:14.4g} {d:+8.1f}%")
    print("\ntop collectives (baseline -> variant):")
    for tag, rec in (("base", a), ("var ", b)):
        for t in rec["hlo"].get("top_collectives", [])[:6]:
            print(f"  {tag} {t['op']:<20s} {t['bytes'] / 1e6:10.1f} MB "
                  f"x{t['count']:<4d} cross_pod={t['cross_pod']}")


if __name__ == "__main__":
    main()
