"""Telemetry layer (DESIGN.md §10): registry exactness, spans as
profiler annotations (read back from a recorded CPU trace), event-log
routing, the engine's span tree, the model's named scopes, per-request
engine percentiles, and the zero-extra-jit-traces + one-clock guards."""
import dataclasses as dc
import glob
import gzip
import json
import pathlib
import re
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import smoke_config
from repro.data.synthetic import batch_for_model
from repro.models import build_model
from repro.serving import ServingEngine
from repro.telemetry import (Counter, EventLog, Gauge, Histogram, Registry,
                             set_enabled, span)


@pytest.fixture(autouse=True)
def _clean_telemetry():
    """Enablement is a process global — leave it as found."""
    yield
    set_enabled(True)


def _host_events(directory) -> list:
    """[(name, start_ns, end_ns, {stat: value})] of the host plane of the
    profile recorded under ``directory``, by start."""
    from jax.profiler import ProfileData
    paths = glob.glob(str(pathlib.Path(directory) / "**" / "*.xplane.pb"),
                      recursive=True)
    assert len(paths) == 1, paths
    with warnings.catch_warnings():
        # jaxlib builds the stats view's type on first use, and warns
        warnings.simplefilter("ignore", DeprecationWarning)
        out = [(e.name, e.start_ns, e.end_ns, dict(e.stats))
               for plane in ProfileData.from_file(paths[0]).planes
               if plane.name.startswith("/host:")
               for line in plane.lines for e in line.events]
    return sorted(out, key=lambda e: (e[1], -e[2]))


def _recorded(tmp_path, fn) -> list:
    """Run ``fn`` under a CPU profiler trace; its host events."""
    with jax.profiler.trace(str(tmp_path)):
        fn()
    return _host_events(tmp_path)


def _inside(inner, outer) -> bool:
    return outer[1] <= inner[1] and inner[2] <= outer[2]


def _build(arch="codeqwen1.5-7b", **over):
    cfg = dc.replace(smoke_config(arch), n_layers=2,
                     compute_dtype="float32", **over)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


# ------------------------------ registry -----------------------------------


def test_histogram_percentiles_exact_vs_numpy():
    rng = np.random.default_rng(0)
    vals = rng.lognormal(mean=-6, sigma=1.5, size=3000)
    h = Histogram("t_s")
    for v in vals:
        h.record(v)
    for q in (0, 10, 50, 95, 99, 100):
        assert h.percentile(q) == pytest.approx(
            float(np.percentile(vals, q)), rel=1e-9)
    assert h.count == len(vals)
    assert h.mean == pytest.approx(float(vals.mean()), rel=1e-9)


def test_histogram_bucket_fallback_bounded_error():
    rng = np.random.default_rng(1)
    vals = rng.lognormal(mean=-4, sigma=1.0, size=4000)
    h = Histogram("t_s", max_samples=16)      # force the CDF-walk path
    for v in vals:
        h.record(v)
    assert h.count > len(h._samples)
    for q in (50, 95, 99):
        # geometric-mean interpolation: error bounded by sqrt(growth)-1
        assert h.percentile(q) == pytest.approx(
            float(np.percentile(vals, q)), rel=0.2)
    assert h.percentile(0) <= h.percentile(50) <= h.percentile(99)


def test_counter_gauge_and_type_mismatch():
    reg = Registry("t_mismatch")
    c = reg.counter("a.count")
    c.inc()
    c.inc(4)
    assert c.value == 5
    g = reg.gauge("a.level")
    g.set(2.5)
    assert g.value == 2.5
    assert reg.counter("a.count") is c       # get-or-create
    with pytest.raises(TypeError):
        reg.histogram("a.count")
    snap = reg.snapshot()
    assert snap["a.count"] == 5 and snap["a.level"] == 2.5


def test_registry_singletons_and_in_place_reset():
    a1, a2 = Registry.get("t_shared"), Registry.get("t_shared")
    assert a1 is a2
    assert Registry("t_shared") is not a1     # standalone constructor
    c = a1.counter("n")
    h = a1.histogram("lat_s")
    c.inc(3)
    h.record(0.5)
    a1.reset()
    # the *objects* survive the reset — held references keep working
    assert a2.counter("n") is c and c.value == 0
    assert h.count == 0
    c.inc()
    assert a2.snapshot()["n"] == 1


# ------------------------------ spans + traces ------------------------------


def test_span_nesting_and_exception_safety(tmp_path):
    def work():
        with span("outer", step=1, mode="gspmd"):
            with span("inner") as s:
                s.set_metadata(width=8)
        with pytest.raises(ValueError):
            with span("boom"):
                raise ValueError("x")

    evs = {e[0]: e for e in _recorded(tmp_path, work)
           if e[0] in ("outer", "inner", "boom")}
    assert set(evs) == {"outer", "inner", "boom"}    # boom still closed
    outer, inner, boom = evs["outer"], evs["inner"], evs["boom"]
    assert _inside(inner, outer) and boom[1] >= outer[2]
    assert outer[3] == {"step": 1, "mode": "gspmd"}
    assert inner[3] == {"width": 8}                  # set at the end


def test_disabled_spans_are_shared_null_and_writer_silent(tmp_path):
    def work():
        set_enabled(False)
        s1, s2 = span("a"), span("b", x=1)
        assert s1 is s2                   # one shared null object
        with s1 as s:
            s.set_metadata(y=2)
        EventLog().emit("ckpt", step=1)
        set_enabled(True)

    names = {e[0] for e in _recorded(tmp_path, work)}
    assert not names & {"a", "b", "event.ckpt"}
    assert span("a") is not span("a")


def test_chrome_trace_schema_roundtrip(tmp_path):
    """An EventLog record lands in the trace as a zero-length
    ``event.<kind>`` annotation inside the enclosing span, in the
    .xplane.pb and in the Chrome-trace JSON the profiler converts it
    to."""
    log = EventLog()

    def work():
        with span("phase.work", k=2):
            log.emit("failure", node=3, cls="sw_xid43")

    evs = {e[0]: e for e in _recorded(tmp_path, work)}
    work_ev, fail = evs["phase.work"], evs["event.failure"]
    assert _inside(fail, work_ev) and fail[3] == {"node": 3,
                                                  "cls": "sw_xid43"}
    assert log.events[0]["kind"] == "failure"

    with jax.profiler.trace(str(tmp_path / "json"),
                            create_perfetto_trace=True):
        work()
    [path] = glob.glob(str(tmp_path / "json" / "**" /
                           "perfetto_trace.json.gz"), recursive=True)
    doc = json.loads(gzip.open(path).read())
    xs = {e["name"]: e for e in doc["traceEvents"]
          if e.get("ph") == "X"
          and e["name"] in ("phase.work", "event.failure")}
    assert set(xs) == {"phase.work", "event.failure"}
    for e in xs.values():
        assert {"name", "ph", "ts", "dur", "pid", "tid"} <= set(e)
    x, i = xs["phase.work"], xs["event.failure"]
    assert x["ts"] <= i["ts"] <= x["ts"] + x["dur"]


def test_event_log_jsonl_roundtrip(tmp_path):
    log = EventLog()
    r1 = log.emit("ckpt", step=10, blocking=False)
    r2 = log.emit("straggler", step=11, dt=2.0)
    assert r1["kind"] == "ckpt" and r2["t"] >= r1["t"] >= 0
    path = log.write(str(tmp_path / "events.jsonl"))
    lines = pathlib.Path(path).read_text().splitlines()
    assert [json.loads(ln) for ln in lines] == log.events


def test_one_clock_guard_mirrors_ci():
    """`telemetry.now` is the only sanctioned clock in src/: no raw
    time.perf_counter (spans must be nullable by set_enabled(False)) and
    no raw time.time (checkpoint policies must take an injectable clock)."""
    src = pathlib.Path(__file__).resolve().parent.parent / "src"
    offenders = [
        str(p.relative_to(src))
        for p in src.rglob("*.py")
        if "repro/telemetry" not in p.as_posix()
        and ("time.perf_counter" in p.read_text()
             or "time.time" in p.read_text())
    ]
    assert not offenders, f"raw clock calls outside telemetry: {offenders}"


# ------------------------------ engine metrics ------------------------------


def _run_staggered(model, cfg, params, *, gen=6, stagger=2):
    prompts = np.asarray(
        batch_for_model(cfg, "prefill", 0, 3, 18)["tokens"], np.int32)
    eng = ServingEngine(model, params, n_blocks=24, block_size=16,
                        max_slots=3)
    rids = [eng.submit(row, gen, arrival=i * stagger)
            for i, row in enumerate(prompts)]
    outs = eng.run()
    return eng, rids, outs


def test_engine_request_metrics_staggered_arrivals():
    cfg, model, params = _build()
    eng, rids, outs = _run_staggered(model, cfg, params)
    m = eng.request_metrics()
    assert m["completed"] == len(rids)
    for key in ("ttft", "tpot", "queue_wait"):
        d = m[key]
        assert d["count"] > 0
        assert 0 <= d["p50_s"] <= d["p95_s"] <= d["p99_s"]
        assert d["mean_s"] > 0
    assert m["ttft"]["count"] == len(rids)
    assert m["tpot"]["count"] == sum(len(t) - 1 for t in outs.values())
    recs = m["requests"]
    assert len(recs) == len(rids)
    for r in recs:
        assert r["ttft_s"] is not None and r["queue_wait_s"] is not None
        assert r["n_tokens"] >= 1
    # metrics survive run()'s drain (which clears _done) — satellite 1
    assert eng._done == {} and m["completed"] == len(rids)
    assert eng.stats["requests_completed"] == len(rids)


def test_engine_zero_extra_jit_traces_from_telemetry(tmp_path):
    """Spans recording into a profiler trace must not change what gets
    compiled: trace counters are incremented at jit trace time."""
    cfg, model, params = _build()

    with jax.profiler.trace(str(tmp_path)):
        eng_on, _, _ = _run_staggered(model, cfg, params)
    on = (eng_on.prefill_traces, eng_on.decode_traces)

    set_enabled(False)
    eng_off, _, _ = _run_staggered(model, cfg, params)
    off = (eng_off.prefill_traces, eng_off.decode_traces)
    set_enabled(True)

    assert on == off
    assert "engine.decode_tick" in {e[0] for e in _host_events(tmp_path)}


ENGINE_TREE = ["engine.step", "engine.admit", "engine.prefill_chunk",
               "engine.write_prompt", "engine.first_token",
               "engine.prepare_tick", "engine.decode_tick",
               "engine.fetch_tokens", "engine.retire"]


def test_engine_step_span_tree(tmp_path):
    """One ``step()`` that admits a request and decodes: the spans of
    DESIGN.md §10 in order, nested under ``engine.step``, the
    request's spans carrying its rid, and the counters at their
    boundaries."""
    cfg, model, params = _build()
    eng = ServingEngine(model, params, n_blocks=24, block_size=16,
                        max_slots=2)
    prompt = np.asarray(batch_for_model(cfg, "prefill", 0, 1, 18)["tokens"],
                        np.int32)[0]
    rid = eng.submit(prompt, 4)

    evs = [e for e in _recorded(tmp_path, eng.step)
           if e[0].startswith("engine.")]
    assert [e[0] for e in evs] == ENGINE_TREE
    ev = dict((e[0], e) for e in evs)
    step = ev["engine.step"]
    assert all(_inside(e, step) for e in evs)
    for name in ENGINE_TREE[2:5]:
        assert _inside(ev[name], ev["engine.admit"])
    for name in ENGINE_TREE[1:5]:
        assert ev[name][3]["rid"] == rid
    assert step[3] == {"step": 0, "queue": 1, "active": 0,
                       "free_blocks": 23}
    assert ev["engine.admit"][3]["prompt_len"] == 18
    assert ev["engine.admit"][3]["prefix_hit"] == 0
    assert ev["engine.write_prompt"][3]["blocks"] == 2
    assert ev["engine.prepare_tick"][3] == {"width": 2, "evicted": 0}
    assert ev["engine.decode_tick"][3] == {"step": 0, "active": 1,
                                           "width": 2}
    assert ev["engine.fetch_tokens"][3] == {"active": 1}
    assert ev["engine.retire"][3] == {"finished": 0}


MODEL_SCOPES = ("layers", "embed", "norm", "qkv", "kv_write", "attention",
                "out_proj", "mlp", "lm_head")


def test_decode_program_op_metadata_holds_model_scopes():
    """The compiled decode program names the layer that produced each
    op: its op_name metadata (the TPU trace's ``tf_op``) carries the
    model's named scopes."""
    cfg, model, params = _build()
    eng = ServingEngine(model, params, n_blocks=24, block_size=16,
                        max_slots=2)
    state = {"k": eng.cache.k, "v": eng.cache.v,
             "block_tables": jnp.zeros((2, 2), jnp.int32),
             "lengths": jnp.zeros(2, jnp.int32),
             "rng": jnp.zeros((2, 2), jnp.uint32)}
    toks = jnp.zeros((2, 1), jnp.int32)
    text = eng._step.lower(params, state, toks, toks).compile().as_text()
    paths = re.findall(r'op_name="([^"]*)"', text)
    parts = {p for path in paths for p in path.split("/")}
    assert set(MODEL_SCOPES) <= parts
    assert any(p.startswith("jit(_decode_fn)/layers/") and "/qkv/" in p
               for p in paths)


# ------------------------------ FT runner routing ---------------------------


def test_ftrunner_routes_every_event_through_one_log(tmp_path):
    from repro.ckpt import CheckpointManager
    from repro.platform.failures import EVENT_KINDS, FailureInjector
    from repro.platform.runner import FTRunner

    def make_step(world):
        def step_fn(state, batch):
            s = {"x": state["x"] + np.float32(world)}
            return s, {"loss": np.float32(1.0)}
        return step_fn

    seen = []
    runner = FTRunner(
        make_step, lambda step: None,
        CheckpointManager(str(tmp_path / "ckpt")),
        {"x": np.float32(0)},
        world_size=2, min_world=1, ckpt_every=2,
        injector=FailureInjector({3: "uncorrectable"}),
        on_event=lambda kind, kw: seen.append(kind))
    report = runner.run(6)

    assert report.failures == 1 and report.restores == 1
    assert report.rescales == 1
    # single source of truth: the report holds the *same* records the
    # runner's EventLog does — the two views cannot drift
    assert report.events == runner.event_log.events
    assert all(any(r is e for e in runner.event_log.events)
               for r in report.events)
    kinds = [e["kind"] for e in report.events]
    assert set(kinds) <= set(EVENT_KINDS)
    assert {"ckpt", "failure", "restore", "rescale"} <= set(kinds)
    assert seen == kinds                      # on_event saw each emit once
    # the stream persists as JSONL
    path = runner.event_log.write(str(tmp_path / "events.jsonl"))
    lines = pathlib.Path(path).read_text().splitlines()
    assert [json.loads(ln)["kind"] for ln in lines] == kinds


# ------------------------------ launcher system -----------------------------


def test_serve_launcher_trace_flag_writes_chrome_json(tmp_path):
    """``--trace DIR`` records a profiler trace of the run: the engine's
    spans on the host plane, and a Perfetto (Chrome-trace JSON) copy."""
    from repro.launch import serve

    out = tmp_path / "serve_trace"
    serve.main(["--arch", "codeqwen1.5-7b", "--smoke",
                "--decode-impl", "paged", "--batch", "2",
                "--prompt-len", "12", "--gen", "4",
                "--trace", str(out)])
    names = {e[0] for e in _host_events(out)}
    assert {"engine.step", "engine.decode_tick",
            "engine.prefill_chunk"} <= names
    [path] = glob.glob(str(out / "**" / "perfetto_trace.json.gz"),
                       recursive=True)
    xs = [e for e in json.loads(gzip.open(path).read())["traceEvents"]
          if e.get("ph") == "X"]
    assert any(e["name"] == "engine.decode_tick" for e in xs)
    for e in xs:
        assert {"name", "ph", "ts", "dur", "pid", "tid"} <= set(e)
        assert e["dur"] >= 0
    # the launcher stopped its trace: a new one can start
    with jax.profiler.trace(str(tmp_path / "again")):
        pass
