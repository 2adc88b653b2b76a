"""The program's spans and op scopes (bench/program_spans.py) and the
readers built on them, on synthetic spans and devices and on a recorded
CPU trace."""
import os
import sys
import warnings

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from tiny_cells import ROOT  # noqa: E402,F401

import pytest  # noqa: E402

from bench import program_spans  # noqa: E402
from test_bench_metrics import load, reading  # noqa: E402

MS = 1e-3


def spans_of(monkeypatch, spans):
    monkeypatch.setattr(program_spans, "load", lambda directory=None: spans)


def step_tree(t0, active, width=4):
    """One engine step at ``t0``: 10 ms, with its prepare (1 ms), decode
    tick (2 ms) and token fetch (6 ms)."""
    return [("engine.step", t0, t0 + 10 * MS, {"step": 0}),
            ("engine.prepare_tick", t0, t0 + 1 * MS, {"width": width}),
            ("engine.decode_tick", t0 + 1 * MS, t0 + 3 * MS,
             {"active": active, "width": width}),
            ("engine.fetch_tokens", t0 + 3 * MS, t0 + 9 * MS,
             {"active": active})]


def test_innermost_and_idle_by_span():
    spans = step_tree(0.0, 2) + step_tree(0.020, 2)
    segs = program_spans.innermost(spans)
    assert [n for _, _, n in segs[:5]] == [
        "engine.prepare_tick", "engine.decode_tick", "engine.fetch_tokens",
        "engine.step", "engine.prepare_tick"]
    assert segs[3][:2] == pytest.approx((9 * MS, 10 * MS))
    # busy from 2 ms to 8 ms of each step: idle 0-2 ms (prepare 1 ms,
    # decode tick 1 ms), 8-10 ms (fetch 1 ms, step 1 ms) and 10-20 ms
    # between the steps (outside)
    busy = [(2 * MS, 8 * MS), (22 * MS, 28 * MS)]
    split = program_spans.idle_by_span(spans, busy, (0.0, 30 * MS))
    assert split == pytest.approx({
        "engine.prepare_tick": 2 * MS, "engine.decode_tick": 2 * MS,
        "engine.fetch_tokens": 2 * MS, "engine.step": 2 * MS,
        "outside": 10 * MS})


def test_idle_in_engine_step_reader(monkeypatch):
    r = reading("phi4-mini.chat", {},
                [([("jit__decode_fn", 0.0, 1.0)],
                  [("a", 0.1, 0.3), ("b", 0.6, 0.9)])])
    m = load("idle_in_engine_step.serve")
    spans_of(monkeypatch, [])
    assert m.read(r) is None                 # a program without spans
    # idle: 0-0.1, 0.3-0.6, 0.9-1.0; steps cover 0.25-0.45 and 0.95-1.2
    spans_of(monkeypatch, [("engine.step", 0.25, 0.45, {}),
                           ("engine.decode_tick", 0.3, 0.4, {}),
                           ("engine.step", 0.95, 1.2, {})])
    assert m.read(r) == pytest.approx(100 * (0.15 + 0.05))
    idle = load("device_idle_share.serve").read(r)
    assert m.read(r) <= idle == pytest.approx(50.0)


def test_decode_occupancy_reader(monkeypatch):
    r = reading("phi4-mini.chat", {}, [([], [])], window=(0.0, 1.0))
    m = load("decode_occupancy.serve")
    spans_of(monkeypatch, step_tree(5.0, 32))
    assert m.read(r) is None                 # no tick in the window
    slots = r.cell.config["engine"]["max_slots"]
    spans_of(monkeypatch, step_tree(0.1, 8) + step_tree(0.5, 16)
             + step_tree(2.0, 32))
    assert m.read(r) == pytest.approx(100 * 12 / slots)


def test_unscoped_share_reader(monkeypatch):
    ops = [("fusion.1", 0.10, 0.30), ("copy.7", 0.30, 0.35),
           ("custom-call.2", 0.35, 0.65), ("copy.7", 0.70, 0.75),
           ("dynamic-update-slice.3", 0.75, 0.80),
           ("fusion.1", 1.5, 1.6)]                    # after the window
    r = reading("phi4-mini.chat", {},
                [([("jit__decode_fn", 0.05, 2.0)], ops)])
    m = load("unscoped_share.decode")
    paths = {"fusion.1": "jit(_decode_fn)/layers/while/body/mlp/dot",
             "custom-call.2":
                 "jit(_decode_fn)/layers/while/body/attention/pallas_call",
             "dynamic-update-slice.3":
                 "jit(_decode_fn)/layers/while/body/dynamic_update_slice",
             "copy.7": ""}
    monkeypatch.setattr(program_spans, "op_scopes",
                        lambda pattern, directory=None: {0: paths})
    # unscoped: both copies and the scan's stacking (0.15 of 0.65)
    assert m.read(r) == pytest.approx(100 * 0.15 / 0.65)
    unscoped = {k: "jit(_decode_fn)/while/body/" + k for k in paths}
    monkeypatch.setattr(program_spans, "op_scopes",
                        lambda pattern, directory=None: {0: unscoped})
    assert m.read(r) is None                 # a program without scopes


def test_plane_scopes_reads_tf_op_from_event_metadata():
    """The TPU keeps an op's scope path in its event metadata: a plane
    built with the XPlane schema, as the chip writes it."""
    xplane = program_spans._xplane_schema()
    assert xplane is not None, "the XPlane protobuf schema is installed"
    plane = xplane.XPlane(name="/device:TPU:0")
    for i, name in enumerate(("program_id", "tf_op")):
        plane.stat_metadata[i + 1].name = name
    plane.stat_metadata[3].name = "jit(_decode_fn)/layers/mlp/dot:"

    def op(mid, text, program, tf_op=None):
        md = plane.event_metadata[mid]
        md.id, md.name = mid, text
        md.stats.add(metadata_id=1, uint64_value=program)
        if tf_op is not None:
            md.stats.add(metadata_id=2, ref_value=tf_op)
    op(1, "%fusion.3 = bf16[8]{0} fusion(x)", 77, tf_op=3)
    op(2, "%copy.4 = bf16[8]{0} copy(y)", 77)
    op(3, "%fusion.3 = bf16[8]{0} fusion(z)", 88, tf_op=3)
    plane.event_metadata[10].name = "jit__decode_fn(77)"
    plane.event_metadata[11].name = "jit__chunk_fn(88)"
    line = plane.lines.add(name="XLA Modules")
    line.events.add(metadata_id=10)
    line.events.add(metadata_id=11)
    assert program_spans._plane_scopes(plane, "_decode_fn") == {
        "fusion.3": "jit(_decode_fn)/layers/mlp/dot:", "copy.4": ""}


def test_load_reads_program_spans_from_a_cpu_trace(tmp_path):
    import jax

    from repro.telemetry import span
    with jax.profiler.trace(str(tmp_path)):
        with jax.profiler.TraceAnnotation("bench.engine_step"):
            with span("engine.step", step=3, queue=1) as s:
                with span("engine.decode_tick", active=4, width=8):
                    jax.numpy.ones(4).block_until_ready()
                s.set_metadata(free_blocks=9)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        spans = program_spans.load(str(tmp_path))
    names = [n for n, *_ in spans]
    assert "bench.engine_step" not in names
    [step] = program_spans.named(spans, "engine.step")
    [tick] = program_spans.named(spans, "engine.decode_tick",
                                 (step[1], step[2]))
    assert step[3] == {"step": 3, "queue": 1, "free_blocks": 9}
    assert tick[3] == {"active": 4, "width": 8}
    assert step[1] <= tick[1] and tick[2] <= step[2]
    assert program_spans.load(str(tmp_path)) is spans     # cached
    # a CPU trace has no TPU plane: no op paths, and no failure
    assert program_spans.op_scopes("_decode_fn", str(tmp_path)) == {}
    split = program_spans.idle_by_span(spans, [], (step[1], step[2]))
    assert sum(split.values()) == pytest.approx(step[2] - step[1])
    assert split["outside"] == pytest.approx(0.0, abs=1e-12)
