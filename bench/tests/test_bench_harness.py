"""The harness end to end on the CPU at a tiny size, its refusals, and
a traffic mix that is found by name."""
import json
import os
import shutil
import subprocess
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import tiny_cells  # noqa: E402
from tiny_cells import ROOT  # noqa: E402

from bench import harness  # noqa: E402


def test_refuses_without_a_tpu(capsys):
    rc = harness.main(["--workload", "phi4-mini.chat", "--seed", "1",
                       "--seconds", "1"])
    out = capsys.readouterr()
    assert rc != 0 and out.out == ""
    assert "no TPU" in out.err


def test_refuses_in_a_checkout_without_the_system(tmp_path):
    """Only BENCHMARK.json and bench/: no src/, no result."""
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", ".traces"))
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH="")
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "phi4-mini.chat",
         "--seed", "3", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0 and p.stdout == ""


def keep_record(store):
    def patch(kind):
        window = kind.Runner.window

        def keep(self, seconds):
            store["record"] = window(self, seconds)
            return store["record"]
        kind.Runner.window = keep
    return patch


def test_serving_cell_runs_and_checks():
    store = {}
    res = tiny_cells.run(tiny_cells.cell("phi4-mini", "chat"),
                         seed=2**33 + 1, seconds=2.0,
                         patch=keep_record(store))
    assert res["correct"] and res["failed"] == 0
    # set-up warmed every shape: nothing compiles in the window
    assert store["record"]["compiles_in_window"] == []
    assert res["setup"]["distinct_lengths"] >= 2
    assert res["attempted"] == 8
    assert set(res["metrics"]) == {"setup_s", "tpot_p95_ms"}
    assert list(res)[-1] == "checks"
    assert res["checks"]["logit_gap"]["value"] < 1e-2


def test_training_cell_runs_and_checks():
    res = tiny_cells.run(tiny_cells.cell("gpt2-medium", "train-1chip"),
                         seed=2**40 + 3, seconds=1.0)
    assert res["correct"] and res["attempted"] >= 1
    assert set(res["metrics"]) == {"setup_s", "train_tok_s"}
    assert set(res["checks"]) == {"loss_gap", "grad_norm_gap",
                                  "update_norm_gap"}


def test_a_new_traffic_file_is_found_by_name(tmp_path):
    """A mix of an existing kind is one JSON file: nothing else changes."""
    (tmp_path / "bench" / "configs").mkdir(parents=True)
    (tmp_path / "bench" / "traffic").mkdir()
    cfg = tiny_cells.config("phi4-mini")
    (tmp_path / "bench" / "configs" / "phi4-mini.json").write_text(
        json.dumps(cfg))
    mix = dict(tiny_cells.traffic("chat"), rate_per_s=6.0)
    (tmp_path / "bench" / "traffic" / "burst.json").write_text(
        json.dumps(mix))
    bench = harness.read_json(os.path.join(ROOT, "BENCHMARK.json"))
    bench["workloads"].append({"name": "phi4-mini.burst",
                               "config": "phi4-mini", "traffic": "burst",
                               "chips": 1, "why": "test"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = harness.Cell.find("phi4-mini.burst", root=str(tmp_path))
    assert cell.traffic["rate_per_s"] == 6.0
    assert [m["name"] for m in cell.end_to_end] == ["setup_s"]
    res = tiny_cells.run(cell, seed=11, seconds=1.0)
    assert res["correct"] and res["attempted"] == 6
