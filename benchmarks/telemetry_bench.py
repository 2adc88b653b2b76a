"""Telemetry overhead suite (DESIGN.md §10 budget: < 2 % step time).

Two halves:

  * micro: ns/op for the primitives — ``Counter.inc``,
    ``Histogram.record``, and a ``span`` enter/exit (a profiler
    annotation with four attributes) under three regimes: the profiler
    off, the profiler recording a trace, and telemetry disabled
    (shared null span).
  * engine: wall-clock per ``ServingEngine.step`` with spans on vs
    ``set_enabled(False)``, once with the profiler off (what an
    untraced run pays) and once with it recording (what a traced run
    pays for the program's spans on top of the runtime's own events).
    One long-lived engine runs *paired adjacent steps* — one per
    regime, order alternating — and the median of the pairwise deltas
    is the overhead: adjacent pairing cancels slow machine drift, the
    median discards scheduler outliers (raw A/B pass averages on a
    noisy shared CPU swing ±10 %, two orders of magnitude above the
    true span cost).  The JSON records both overheads vs the 2 %
    target.

Emits CSV rows and writes ``BENCH_telemetry.json``.  Off-TPU the
engine timings measure XLA CPU dispatch — the overhead *ratio* is the
point, not the absolute step time.
"""
from __future__ import annotations

import contextlib
import dataclasses
import json
import os
import tempfile
import time

import jax
import numpy as np

from benchmarks.common import emit

OUT_PATH = os.environ.get("REPRO_BENCH_TELEMETRY", "BENCH_telemetry.json")
OVERHEAD_TARGET_PCT = 2.0


def _cases():
    if jax.default_backend() == "tpu" and \
            os.environ.get("REPRO_BENCH_SMOKE") != "1":
        return dict(n_micro=200_000, batch=4, prompt=24, block=16,
                    n_layers=2, pairs=200, warmup=20)
    return dict(n_micro=50_000, batch=2, prompt=12, block=8,
                n_layers=2, pairs=200, warmup=10)


@contextlib.contextmanager
def _profiling():
    """A JAX profiler trace recording into a throwaway directory."""
    with tempfile.TemporaryDirectory() as d:
        jax.profiler.start_trace(d)
        try:
            yield
        finally:
            jax.profiler.stop_trace()


def _span_ns(span, n: int) -> float:
    t0 = time.perf_counter()
    for i in range(n):
        with span("bench.span", step=i, active=4, queue=0, free_blocks=9):
            pass
    return (time.perf_counter() - t0) / n * 1e9


def _micro(n: int) -> dict:
    from repro.telemetry import Registry, set_enabled, span

    reg = Registry("telemetry_bench")
    c = reg.counter("bench.count")
    h = reg.histogram("bench.lat_s")

    t0 = time.perf_counter()
    for _ in range(n):
        c.inc()
    counter_ns = (time.perf_counter() - t0) / n * 1e9

    t0 = time.perf_counter()
    for i in range(n):
        h.record(1e-6 * (i % 1000 + 1))
    record_ns = (time.perf_counter() - t0) / n * 1e9

    n_span = max(n // 10, 1)
    span("bench.span")                 # imports the annotation type
    span_ns = _span_ns(span, n_span)
    with _profiling():
        span_traced_ns = _span_ns(span, n_span)

    set_enabled(False)
    try:
        span_off_ns = _span_ns(span, n)
    finally:
        set_enabled(True)

    out = {"counter_inc_ns": counter_ns, "histogram_record_ns": record_ns,
           "span_ns": span_ns, "span_traced_ns": span_traced_ns,
           "span_disabled_ns": span_off_ns}
    for k, v in out.items():
        emit(f"telemetry.micro.{k}", v / 1e3, f"{v:.0f}ns")
    return out


def _engine_overhead(c) -> dict:
    import statistics

    from repro.configs.registry import smoke_config
    from repro.data.synthetic import batch_for_model
    from repro.models import build_model
    from repro.serving import ServingEngine
    from repro.telemetry import set_enabled

    cfg = dataclasses.replace(smoke_config("codeqwen1.5-7b"),
                              n_layers=c["n_layers"],
                              compute_dtype="float32")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))

    b, prompt, block = c["batch"], c["prompt"], c["block"]
    budget = 4 * c["pairs"] + 2 * c["warmup"] + 40   # decode steps needed
    batch = batch_for_model(cfg, "prefill", 0, b, prompt)
    max_blocks = -(-(prompt + budget + 4) // block)
    eng = ServingEngine(model, params, n_blocks=b * max_blocks + 1,
                        block_size=block, max_slots=b,
                        min_table_width=max_blocks)
    for row in np.asarray(batch["tokens"]):
        eng.submit(row, budget + 4)
    eng.step()                                       # admit + compile

    def one(enabled: bool) -> float:
        set_enabled(enabled)
        t0 = time.perf_counter()
        eng.step()
        return time.perf_counter() - t0

    def paired() -> tuple[float, float]:
        """(median step time with spans off, median on - off delta)."""
        try:
            for _ in range(c["warmup"]):
                eng.step()
            deltas, offs = [], []
            for k in range(c["pairs"]):
                if k % 2:
                    off = one(False)
                    on = one(True)
                else:
                    on = one(True)
                    off = one(False)
                deltas.append(on - off)
                offs.append(off)
        finally:
            set_enabled(True)
        return statistics.median(offs), statistics.median(deltas)

    base, delta = paired()
    with _profiling():
        base_tr, delta_tr = paired()

    overhead_pct = delta / base * 100.0
    traced_pct = delta_tr / base_tr * 100.0
    emit("telemetry.engine.base", base * 1e6, "set_enabled(False)")
    emit("telemetry.engine.overhead", delta * 1e6,
         f"pct={overhead_pct:.2f} (profiler off)")
    emit("telemetry.engine.overhead_traced", delta_tr * 1e6,
         f"pct={traced_pct:.2f} (profiler recording)")
    return {"us_per_step_disabled": base * 1e6,
            "overhead_us_per_step": delta * 1e6,
            "overhead_pct": overhead_pct,
            "us_per_step_disabled_traced": base_tr * 1e6,
            "overhead_us_per_step_traced": delta_tr * 1e6,
            "overhead_traced_pct": traced_pct,
            "pairs": c["pairs"]}


def run():
    c = _cases()
    micro = _micro(c["n_micro"])
    engine = _engine_overhead(c)
    ok = max(engine["overhead_pct"],
             engine["overhead_traced_pct"]) < OVERHEAD_TARGET_PCT
    data = {
        "backend": jax.default_backend(),
        "smoke": os.environ.get("REPRO_BENCH_SMOKE") == "1",
        "micro_ns": micro,
        "engine": engine,
        "overhead_target_pct": OVERHEAD_TARGET_PCT,
        "ok": ok,
    }
    with open(OUT_PATH, "w") as f:
        json.dump(data, f, indent=2)
    emit("telemetry.ok", 0, f"ok={ok} -> {OUT_PATH}")
    return data
