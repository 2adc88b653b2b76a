"""The traffic generator: one schedule per seed, the same work for
every seed."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from tiny_cells import ROOT  # noqa: E402,F401

import numpy as np  # noqa: E402

from bench import harness, traffic_gen  # noqa: E402

CHAT = harness.read_json(os.path.join(ROOT, "bench", "traffic", "chat.json"))
BIG = 2**33 + 12345


def _sizes(sched):
    return sorted((len(r.prompt), r.max_new_tokens) for r in sched)


def test_schedule_is_a_function_of_the_seed():
    a = traffic_gen.open_loop(CHAT, BIG, 51, 200064)
    b = traffic_gen.open_loop(CHAT, BIG, 51, 200064)
    assert [r.due for r in a] == [r.due for r in b]
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))


def test_seeds_reorder_the_same_work():
    a = traffic_gen.open_loop(CHAT, BIG, 51, 200064)
    b = traffic_gen.open_loop(CHAT, BIG + 1, 51, 200064)
    assert _sizes(a) == _sizes(b)
    assert [len(r.prompt) for r in a] != [len(r.prompt) for r in b]
    assert not np.array_equal(a[0].prompt[:8], b[0].prompt[:8])
    # the same gaps between arrivals (and to the window's end), in
    # another order
    ga = np.sort(np.diff([0.0] + [r.due for r in a] + [51.0]))
    gb = np.sort(np.diff([0.0] + [r.due for r in b] + [51.0]))
    assert np.allclose(ga, gb, atol=1e-9)


def test_schedule_fills_the_window_within_the_ranges():
    s = traffic_gen.open_loop(CHAT, 7, 51, 200064)
    assert len(s) == round(CHAT["rate_per_s"] * 51)
    due = [r.due for r in s]
    assert due == sorted(due) and 0 < due[0] and due[-1] < 51
    p, o = CHAT["prompt"], CHAT["output"]
    assert all(p["min"] <= len(r.prompt) <= p["max"] for r in s)
    assert all(o["min"] <= r.max_new_tokens <= o["max"] for r in s)
    assert all(r.prompt.dtype == np.int32 and r.prompt.max() < 200064
               for r in s)


def test_train_batches_are_fresh_rows_per_seed():
    tr = {"seq": 64, "zipf_exponent": 1.1}
    a = traffic_gen.train_batches(tr, BIG, 3, 8, 50257)
    b = traffic_gen.train_batches(tr, BIG, 3, 8, 50257)
    c = traffic_gen.train_batches(tr, BIG + 1, 3, 8, 50257)
    assert all(np.array_equal(x["tokens"], y["tokens"]) for x, y in zip(a, b))
    assert not np.array_equal(a[0]["tokens"], c[0]["tokens"])
    rows = np.concatenate([x["tokens"] for x in a])
    assert len({r.tobytes() for r in rows}) == len(rows)
    assert np.array_equal(a[0]["tokens"][:, 1:], a[0]["labels"][:, :-1])
    # heavy-tailed: the most frequent id is far above uniform
    counts = np.bincount(rows.ravel(), minlength=50257)
    assert counts.max() > 50 * rows.size / 50257
