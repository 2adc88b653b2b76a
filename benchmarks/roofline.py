"""Roofline analysis from dry-run artifacts (EXPERIMENTS.md §Roofline).

Per (arch x shape), from the compiled single-pod (16x16) module's
trip-count-corrected per-chip HLO stats:

  compute term    = HLO_FLOPs_per_chip / peak_bf16
  memory term     = HLO_bytes_per_chip / HBM_bw
  collective term = intra-pod collective bytes / ICI link bw
                    (+ cross-pod bytes / DCI bw on the 2x16x16 mesh rows)

plus MODEL_FLOPS = 6*N*D (6*N_active*D for MoE) and the useful-compute
ratio MODEL_FLOPS / HLO_FLOPs.  Emits CSV rows and writes a markdown table
to artifacts/roofline.md for EXPERIMENTS.md.
"""
from __future__ import annotations

import glob
import json
import os

from benchmarks.common import emit
from repro import hw
from repro.configs.registry import get_arch, get_shape

ART = os.environ.get("REPRO_DRYRUN_DIR", "artifacts/dryrun")


def model_flops_per_chip(arch: str, shape_name: str, n_chips: int) -> float:
    cfg = get_arch(arch)
    shape = get_shape(shape_name)
    n_active = cfg.active_param_count()
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        if cfg.is_encoder_decoder:
            tokens *= 2      # encoder + decoder streams
        return 6.0 * n_active * tokens / n_chips
    if shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        if cfg.is_encoder_decoder:
            tokens *= 2
        return 2.0 * n_active * tokens / n_chips
    if shape.kind == "chunk":
        # a prefill chunk: shape.chunk tokens per sequence per step
        return 2.0 * n_active * shape.global_batch * shape.chunk / n_chips
    # decode: one token per sequence
    return 2.0 * n_active * shape.global_batch / n_chips


def analyze_cell(rec: dict) -> dict:
    chip = hw.chip_spec(rec.get("device_kind"))
    h = rec["hlo"]
    compute_s = h["flops"] / chip.peak_bf16_flops
    memory_s = h["bytes"] / chip.hbm_bw
    intra = h.get("intra_pod_bytes", 0.0) or (
        h["collective_total_bytes"] - h.get("cross_pod_bytes", 0.0))
    coll_s = (h["collective_total_bytes"] / chip.ici_bw_per_link
              if rec["mesh"] == "16x16" else
              intra / chip.ici_bw_per_link
              + h.get("cross_pod_bytes", 0.0) / chip.dci_bw_per_chip)
    terms = {"compute": compute_s, "memory": memory_s,
             "collective": coll_s}
    dominant = max(terms, key=terms.get)
    mf = model_flops_per_chip(rec["arch"], rec["shape"], rec["n_devices"])
    useful = mf / h["flops"] if h["flops"] else 0.0
    bound = max(terms.values())
    frac = {k: v / bound for k, v in terms.items()}
    suggestion = {
        "compute": "cut recompute (remat policy) / shed dispatch-einsum "
                   "overhead — compiled FLOPs exceed model FLOPs",
        "memory": "fuse/cast to bf16, larger per-chip tiles, fewer "
                  "loop-carried copies",
        "collective": "reshard to keep gathers intra-pod, bucket/compress "
                      "the cross-pod phase (HFReduce rules)",
    }[dominant]
    return {**terms, "dominant": dominant, "model_flops": mf,
            "useful_ratio": useful, "suggestion": suggestion,
            "frac": frac}


def run(write_md: bool = True):
    rows = []
    for path in sorted(glob.glob(os.path.join(ART, "*.json"))):
        rec = json.load(open(path))
        if not rec.get("ok") or "__" not in os.path.basename(path):
            continue
        if rec.get("hlo") is None:
            continue
        tag = os.path.basename(path).replace(".json", "")
        if tag.count("__") > 2:      # perf-loop variants excluded here
            continue
        a = analyze_cell(rec)
        rows.append((rec, a))
        emit(f"roofline.{rec['arch']}.{rec['shape']}.{rec['mesh']}", 0,
             f"compute={a['compute'] * 1e3:.2f}ms "
             f"memory={a['memory'] * 1e3:.2f}ms "
             f"collective={a['collective'] * 1e3:.2f}ms "
             f"dom={a['dominant']} useful={a['useful_ratio']:.2f}")

    if write_md and rows:
        os.makedirs("artifacts", exist_ok=True)
        with open("artifacts/roofline.md", "w") as f:
            f.write("| arch | shape | mesh | compute (ms) | memory (ms) | "
                    "collective (ms) | dominant | MODEL_FLOPS/chip | "
                    "useful ratio | next move |\n")
            f.write("|---|---|---|---|---|---|---|---|---|---|\n")
            for rec, a in rows:
                f.write(
                    f"| {rec['arch']} | {rec['shape']} | {rec['mesh']} | "
                    f"{a['compute'] * 1e3:.2f} | {a['memory'] * 1e3:.2f} | "
                    f"{a['collective'] * 1e3:.2f} | {a['dominant']} | "
                    f"{a['model_flops']:.3g} | {a['useful_ratio']:.2f} | "
                    f"{a['suggestion']} |\n")
        emit("roofline.table_written", 0,
             f"artifacts/roofline.md({len(rows)}rows)")
    if not rows:
        emit("roofline.skipped", 0, "no dry-run artifacts (run dryrun --all)")
    return rows


if __name__ == "__main__":
    run()
