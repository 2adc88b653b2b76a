"""Run one cell of ``BENCHMARK.json`` and print its result line.

The cell names a configuration and a traffic mix; the traffic names its
kind.  Each is found by name (``bench/configs/<file>``,
``bench/traffic/<mix>.json``, ``bench/kinds/<kind>.py``), and each
per-layer metric by its reader ``bench/layer_metrics/<metric>.py``, so a
new cell, mix or metric is new files only.

A run: set-up (the kind builds the system from the seed and warms every
shape its traffic uses), the measured window of ``--seconds``, the peak
memory, then the comparison with the reference that decides ``correct``.
With ``--trace 1`` the profiler records the whole window and the
per-layer metrics are read from it; otherwise the end-to-end metrics
are reported.
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import gc
import importlib.util
import json
import math
import os
import shutil
import sys
import time

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
TRACE_DIR = os.path.join(BENCH, ".traces")
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class Refused(Exception):
    """The run cannot be made here (no chip, wrong files): no result."""


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or not os.path.exists(path):
        raise Refused(f"no such file: {os.path.relpath(path, ROOT)}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def read_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    """A cell of BENCHMARK.json with its files read."""
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list       # metric entries that this cell reports
    per_layer: list

    @classmethod
    def find(cls, workload: str, root: str = ROOT) -> "Cell":
        """The cell ``workload`` of ``<root>/BENCHMARK.json``, its
        configuration file and ``<root>/bench/traffic/<mix>.json``."""
        bench_file = os.path.join(root, "BENCHMARK.json")
        if not os.path.exists(bench_file):
            raise Refused("no BENCHMARK.json")
        bench = read_json(bench_file)
        cells = {w["name"]: w for w in bench["workloads"]}
        if workload not in cells:
            raise Refused(f"no workload {workload!r}; have {sorted(cells)}")
        w = cells[workload]
        cfg_entry = next(c for c in bench["configs"] if c["name"] == w["config"])
        config = read_json(os.path.join(root, cfg_entry["file"]))
        path = os.path.join(root, "bench", "traffic", w["traffic"] + ".json")
        if not os.path.exists(path):
            raise Refused(f"no traffic file {w['traffic']}.json")
        traffic = read_json(path)

        def mine(m):
            return workload in m.get("workloads", [workload])
        return cls(workload, int(w["chips"]), config, traffic,
                   [m for m in bench["end_to_end"] if mine(m)],
                   [m for m in bench["per_layer"] if mine(m)])


class CompileLog:
    """Every XLA backend compile of the process, with its end time."""

    def __init__(self):
        import jax
        self.events: list[tuple[float, float]] = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == COMPILE_EVENT:
            self.events.append((time.perf_counter(), duration))

    def between(self, t0: float, t1: float) -> list[float]:
        return [d for t, d in self.events if t0 <= t <= t1]


@dataclasses.dataclass
class Context:
    """What a kind is given: the cell's files, the seed, the devices and
    the hooks for the traced run."""
    cell: Cell
    seed: int
    seconds: float
    devices: list
    compiles: CompileLog
    tracing: bool = False
    _trace_on: bool = False
    trace_bounds: tuple = ()

    def span(self, name: str):
        """A profiler annotation in the traced run, nothing otherwise."""
        if not self.tracing:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation("bench." + name)

    def poll(self) -> None:
        """Called by the window loop at each turn: the traced run starts
        the profiler on the first."""
        if self.tracing and not self._trace_on and not self.trace_bounds:
            import jax
            shutil.rmtree(TRACE_DIR, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0     # it taxes every Python call
            jax.profiler.start_trace(TRACE_DIR, profiler_options=opts)
            self._trace_on = True
            self._window_span = self.span("window")
            self._window_span.__enter__()
            self._t_trace = time.perf_counter()

    def stop_trace(self) -> None:
        if self._trace_on:
            import jax
            t1 = time.perf_counter()
            self._window_span.__exit__(None, None, None)
            jax.profiler.stop_trace()
            self._trace_on = False
            self.trace_bounds = (self._t_trace, t1)


def decoder_spec(config: dict):
    from bench.reference.weights import DecoderSpec
    return DecoderSpec.from_block(config["decoder"])


def model_config(config: dict):
    """The system's ModelConfig for a configuration file: the registry
    entry with the file's decoder block and program settings applied."""
    import dataclasses as dc

    from repro.configs.registry import get_arch
    d = config["decoder"]
    fields = dict(n_layers=d["layers"], d_model=d["d_model"],
                  n_heads=d["heads"], n_kv_heads=d["kv_heads"],
                  d_head=d["head_dim"], d_ff=d["d_ff"],
                  vocab_size=d["vocab"], norm=d["norm"],
                  activation=d["activation"], rope_theta=d["rope_theta"],
                  tie_embeddings=True)
    fields.update(config.get("program", {}))
    return dc.replace(get_arch(config["registry"]), **fields)


def check_devices(chips: int) -> list:
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise Refused(f"no TPU: JAX sees {devs[0].platform} devices; "
                      f"this benchmark measures the chip only")
    if len(devs) < chips:
        raise Refused(f"cell needs {chips} chips, JAX sees {len(devs)}")
    return devs


def memory_peak(devices) -> int | None:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def run(cell: Cell, seed: int, seconds: float, trace: bool, *,
        t_start: float, require_chip: bool = True, patch=None) -> dict:
    """One run of ``cell``; returns the result object.  ``patch`` (tests
    only) is called with the kind's module before its runner is built,
    to break the timed path underneath."""
    import jax

    if require_chip:
        devices = check_devices(cell.chips)[:cell.chips]
        from repro.launch.compile_cache import enable_compile_cache
        enable_compile_cache()
    else:
        devices = jax.devices()[:cell.chips]
    compiles = CompileLog()
    kind = load_module(os.path.join(BENCH, "kinds",
                                    cell.traffic["kind"] + ".py"),
                       "bench_kind_" + cell.traffic["kind"])
    if patch is not None:
        patch(kind)
    ctx = Context(cell, seed, seconds, devices, compiles, tracing=trace)
    runner = kind.Runner(ctx)
    runner.setup()
    t_window = time.perf_counter()
    setup_s = t_window - t_start
    record = runner.window(seconds)
    ctx.stop_trace()
    t_end = time.perf_counter()
    record["compiles_in_window"] = compiles.between(t_window, t_end)
    mem = memory_peak(devices)
    e2e = runner.end_to_end(record)
    e2e["setup_s"] = setup_s
    runner.release()
    gc.collect()
    checks = runner.check(record)
    correct = bool(checks) and all(
        math.isfinite(c["value"]) and c["value"] <= c["limit"]
        for c in checks.values())

    dev = devices[0]
    result = {"correct": correct,
              "attempted": int(record["attempted"]),
              "failed": int(record["failed"]),
              "metrics": {},
              "device": {"platform": dev.platform, "kind": dev.device_kind,
                         "count": len(jax.devices()),
                         "memory_peak_bytes": mem}}
    if not trace:
        for m in cell.end_to_end:
            if m["name"] in e2e:
                result["metrics"][m["name"]] = {"value": e2e[m["name"]],
                                                "unit": m["unit"]}
    else:
        from bench.trace import Trace, breakdown, busy_intervals, union_length
        tr = Trace.load(TRACE_DIR)
        window = next(((s, e) for n, s, e in tr.host
                       if n == "bench.window"), None)
        if window is None or not tr.devices:
            raise RuntimeError("the trace holds no window or no device")
        wlen = window[1] - window[0]
        busy = [union_length([iv for iv in busy_intervals(d)
                              if iv[1] > window[0] and iv[0] < window[1]])
                for d in tr.devices.values()]
        result["device"]["busy_s"] = sum(busy) / len(busy)
        result["device"]["window_s"] = wlen
        reading = Reading(cell, record, tr, window, ctx.trace_bounds,
                          decoder_spec(cell.config), dev.device_kind,
                          cell.chips)
        for m in cell.per_layer:
            reader = load_module(os.path.join(BENCH, "layer_metrics",
                                              m["name"] + ".py"),
                                 "bench_metric_" + m["name"].replace(".", "_"))
            value = reader.read(reading)
            if value is not None:
                result["metrics"][m["name"]] = {"value": value,
                                                "unit": m["unit"]}
        result["breakdown"] = breakdown(tr, window)
    result["setup"] = record.get("setup", {})
    result["checks"] = checks
    return result


@dataclasses.dataclass
class Reading:
    """What a per-layer metric reader is given."""
    cell: Cell
    record: dict
    trace: object          # bench.trace.Trace
    window: tuple          # the traced window on the trace's clock
    host_window: tuple     # the same window on the host's perf_counter
    spec: object           # DecoderSpec
    device_kind: str
    chips: int

    @property
    def peaks(self) -> dict:
        from bench.peaks import peak
        return peak(self.device_kind)

    def to_trace(self, t: float) -> float:
        """A host perf_counter time on the trace's clock."""
        return t - self.host_window[0] + self.window[0]

    def host_steps(self, steps) -> list:
        """The (start, end, ...) host records that lie inside the traced
        window."""
        lo, hi = self.host_window
        return [s for s in steps if s[0] >= lo and s[1] <= hi]

    def devices(self) -> list:
        return list(self.trace.devices.values())


def main(argv=None, *, t_start: float | None = None) -> int:
    t_start = time.perf_counter() if t_start is None else t_start
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        cell = Cell.find(args.workload)
        result = run(cell, args.seed, args.seconds, bool(args.trace),
                     t_start=t_start)
    except Refused as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    for name, c in result["checks"].items():
        print(f"check {name} = {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0
