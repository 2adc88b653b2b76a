"""Cells of the benchmark at a size a CPU test can hold: the committed
configurations and traffic files with every width cut down."""
from __future__ import annotations

import copy
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench import harness  # noqa: E402

TINY = {"layers": 2, "d_model": 64, "heads": 4, "kv_heads": 2,
        "head_dim": 16, "d_ff": 128, "vocab": 256}


def config(name: str) -> dict:
    cfg = harness.read_json(os.path.join(ROOT, "bench", "configs",
                                         name + ".json"))
    cfg["decoder"] = dict(cfg["decoder"], **TINY)
    if cfg["decoder"]["norm"] == "layernorm":      # gpt2: no GQA
        cfg["decoder"]["kv_heads"] = cfg["decoder"]["heads"]
    if "engine" in cfg:
        cfg["engine"] = dict(cfg["engine"], n_blocks=128, max_slots=4)
    return cfg


def traffic(name: str) -> dict:
    tr = harness.read_json(os.path.join(ROOT, "bench", "traffic",
                                        name + ".json"))
    if tr["kind"] == "serve_open_loop":
        tr.update(rate_per_s=4.0,
                  prompt=dict(tr["prompt"], median=24, min=8, max=64),
                  output=dict(tr["output"], median=6, min=2, max=12))
    else:
        tr.update(seq=16, feed_batches=4)
    return tr


def cell(config_name: str, traffic_name: str, chips: int = 1,
         limits: dict | None = None) -> harness.Cell:
    cfg, tr = config(config_name), traffic(traffic_name)
    if limits is not None:
        tr = copy.deepcopy(tr)
        tr.setdefault("limits", {})[cfg["name"]] = limits
    e2e = [{"name": "setup_s", "unit": "s"},
           {"name": "tpot_p95_ms", "unit": "ms"},
           {"name": "train_tok_s", "unit": "tokens/s"}]
    return harness.Cell(f"{config_name}.{traffic_name}", chips, cfg, tr,
                        e2e, [])


def run(c: harness.Cell, seed: int = 5, seconds: float = 2.0, **kw) -> dict:
    import time
    return harness.run(c, seed, seconds, False, t_start=time.perf_counter(),
                       require_chip=False, **kw)
