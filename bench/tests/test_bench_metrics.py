"""Each per-layer reader on a small synthetic trace and run record."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from tiny_cells import ROOT  # noqa: E402

import pytest  # noqa: E402

from bench import harness, work  # noqa: E402
from bench.trace import (Device, Trace, assign_modules, breakdown,  # noqa: E402
                         gaps, leaf_ops, split_op, union_length)

MS = 1e-3


def load(name):
    return harness.load_module(
        os.path.join(ROOT, "bench", "layer_metrics", name + ".py"),
        "test_metric_" + name.replace(".", "_"))


def cell_of(name):
    """A cell of BENCHMARK.json, or one built from its files (a mix that
    is ready but not yet a cell)."""
    try:
        return harness.Cell.find(name)
    except harness.Refused:
        config, mix = name.split(".", 1)
        return harness.Cell(
            name, 4, harness.read_json(os.path.join(
                ROOT, "bench", "configs", config + ".json")),
            harness.read_json(os.path.join(ROOT, "bench", "traffic",
                                           mix + ".json")), [], [])


def reading(cell_name, record, devices, host=(), window=(0.0, 1.0)):
    cell = cell_of(cell_name)
    def typed(op):
        return (op[0], op[3] if len(op) > 3 else "", op[1], op[2])
    tr = Trace({i: Device(mods, assign_modules(
        sorted(map(typed, ops), key=lambda o: o[2]), mods))
        for i, (mods, ops) in enumerate(devices)}, list(host))
    return harness.Reading(cell, record, tr, window, (100.0, 101.0),
                           harness.decoder_spec(cell.config), "TPU v5 lite",
                           cell.chips)


def test_interval_helpers():
    ivs = [(0.0, 0.2), (0.1, 0.3), (0.5, 0.6)]
    assert union_length(ivs) == pytest.approx(0.4)
    assert gaps(ivs, 0.0, 1.0) == [(0.3, 0.5), (0.6, 1.0)]
    ops = assign_modules([("a", "f32[2]", 0.1, 0.2), ("b", "", 0.6, 0.7)],
                         [("jit_f", 0.0, 0.3), ("jit_g", 0.5, 0.8)])
    assert ops == [("a", 0.1, 0.2, "jit_f", "f32[2]"),
                   ("b", 0.6, 0.7, "jit_g", "")]
    assert split_op("%fusion.3 = (bf16[8]{0}, f32[8]{0}) fusion(x)") == \
        ("fusion.3", "(bf16[8]{0},")
    # a loop op spans its body's ops: only the body's count
    dev = Device([], [("while.1", 0.0, 1.0, "", ""),
                      ("fusion.1", 0.1, 0.2, "", ""),
                      ("fusion.2", 0.3, 0.4, "", "")])
    assert [o[0] for o in leaf_ops(dev)] == ["fusion.1", "fusion.2"]


def test_breakdown_names_ops_and_idle_spans():
    mods = [("jit__decode_fn", 0.0, 0.4)]
    ops = [("fusion.1", 0.0, 0.3), ("paged_chunk_attention", 0.3, 0.4)]
    host = [("bench.engine_step", 0.0, 0.45), ("bench.idle", 0.45, 1.0)]
    r = reading("phi4-mini.chat", {}, [(mods, ops)], host)
    b = breakdown(r.trace, (0.0, 1.0))
    assert b["device_ops"][0] == ["jit__decode_fn/fusion.1", pytest.approx(0.3)]
    assert dict(b["idle_gaps"])["bench.idle"] == pytest.approx(0.6)


SERVE_REC = {
    "due": [0.0, 1.0, 2.0],
    "compiles_in_window": [0.05, 0.02, 0.01],
    # (host start, host end, keys of each decoding request)
    "steps": [(100.1, 100.2, [1000, 2000]), (100.3, 100.4, [1001]),
              (99.0, 99.5, [5])],
}


def test_compiles_in_window():
    r = reading("phi4-mini.chat", SERVE_REC, [([], [])])
    assert load("compiles_in_window.serve").read(r) == 3.0


def test_prefill_and_decode_readers():
    # the traced window is host 100..101 -> trace 0..1
    mods = [("jit__chunk_fn", 0.00, 0.04), ("jit__decode_fn", 0.1, 0.15),
            ("jit__decode_fn", 0.3, 0.32), ("jit__chunk_fn", 0.5, 0.56)]
    ops = [("paged_chunk_attention.3", 0.11, 0.12),
           ("fusion.2", 0.12, 0.15),
           ("paged_chunk_attention.3", 0.30, 0.305)]
    r = reading("phi4-mini.chat", SERVE_REC, [(mods, ops)])
    assert load("prefill_chunk_ms.serve").read(r) == pytest.approx(50.0)
    flops = sum(work.decode_flops(r.spec, k) for k in (1000, 2000, 1001))
    assert load("mfu.decode").read(r) == pytest.approx(
        100 * flops / (0.07 * 197e12))
    least = sum(work.roofline_seconds(*work.paged_attention(r.spec, k),
                                      r.peaks)[0] for k in (1000, 2000, 1001))
    assert load("paged_attention_roofline").read(r) == pytest.approx(
        100 * least / 0.015)
    assert load("device_idle_share.serve").read(r) == pytest.approx(
        100 * (1 - 0.045))


def test_readers_that_find_nothing_return_none():
    empty = {"due": [], "steps": []}
    r = reading("phi4-mini.chat", empty, [([], [])])
    for name in ("prefill_chunk_ms.serve", "mfu.decode",
                 "paged_attention_roofline"):
        assert load(name).read(r) is None
    r = reading("gpt2-medium.train-1chip", {}, [([], [])])
    assert load("flash_attention_roofline.train").read(r) is None
    assert load("exposed_collective_ms.ddp").read(r) is None


def test_training_readers():
    rec = {"tokens": 8 * 1024 * 10, "t0": 0.0, "t1": 2.0}
    r = reading("gpt2-medium.train-1chip", rec, [([], [])])
    want = 100 * work.train_flops_per_token(r.spec, 1024) * 40960 / 197e12
    assert load("mfu.train").read(r) == pytest.approx(want)
    mods = [("jit_train_step", 0.0, 0.5)]
    ops = [("flash_attention.36", 0.0, 0.01, "(bf16[8,16,1024,64]{3,2,1,0},"),
           ("flash_attention.37", 0.1, 0.13, "(f32[8,16,1024,64]{3,2,1,0},"),
           ("flash_attention.38", 0.2, 0.22, "f32[8,16,1024,64]{3,2,1,0}"),
           ("fusion.9", 0.3, 0.4, "bf16[8]")]
    r = reading("gpt2-medium.train-1chip", rec, [(mods, ops)])
    shape = dict(batch=8, heads=16, kv_heads=16, seq=1024, head_dim=64)
    least = sum(work.roofline_seconds(*work.flash_attention(k, **shape),
                                      r.peaks)[0]
                for k in ("fwd", "dkv", "dq"))
    assert load("flash_attention_roofline.train").read(r) == pytest.approx(
        100 * least / 0.06)


def test_exposed_collective():
    # 6 ms of all-reduce waits on the op line over 2 steps
    mods = [("jit_local_step", 0.0, 0.4), ("jit_local_step", 0.5, 0.9)]
    ops = [("fusion.1", 0.100, 0.104), ("all-reduce-done.1", 0.104, 0.108),
           ("fusion.2", 0.6, 0.7), ("all-reduce.2", 0.7, 0.702)]
    r = reading("gpt2-medium.ddp-4chip", {}, [(mods, ops)])
    assert load("exposed_collective_ms.ddp").read(r) == pytest.approx(3.0)
