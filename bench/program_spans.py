"""What the program under test put in the traced run's profile.

``bench/trace.py`` keeps the benchmark's own ``bench.*`` spans; this
module reads the rest of the same ``.xplane.pb`` (under
``bench.harness.TRACE_DIR``):

* ``load()``: the host-plane events whose names do not start with
  ``bench.``, as ``(name, start, end, args)``, times in seconds on the
  trace's clock, ``args`` the event's metadata: the attributes of a
  ``repro.telemetry.span`` (``engine.step``, ``engine.decode_tick``, ...).
  A program whose spans never reach the profiler leaves none of its
  names here, and the readers of those names return None.
* ``op_scopes(pattern)``: per device, each operation of the programs
  whose name matches ``pattern``, with its ``tf_op`` path: the
  ``jax.named_scope`` stack it was traced under, e.g.
  ``jit(_decode_fn)/layers/while/body/closed_call/mlp/dot_general``.  The
  TPU keeps that path in the op's event metadata, which
  ``jax.profiler.ProfileData`` does not show, so this reads the file with
  the XPlane protobuf schema that the installed TensorFlow ships.

Both are cached per file, so the readers of one run load it once.
``idle_by_span`` splits a device's idle time by the program's spans.
"""
from __future__ import annotations

import functools
import glob
import importlib.util
import os
import re

from bench.trace import HOST_PREFIX, gaps, split_op

MODULE_LINE = "XLA Modules"
_DEVICE = re.compile(r"/device:TPU:(\d+)$")
_PROGRAM = re.compile(r"(.*)\((\d+)\)$")
_cache: dict = {}


def trace_files(directory: str | None = None) -> list[str]:
    if directory is None:
        from bench.harness import TRACE_DIR
        directory = TRACE_DIR
    return sorted(glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                            recursive=True))


def load(directory: str | None = None) -> list:
    """[(name, start, end, args)] of the program's host events, by start."""
    files = trace_files(directory)
    key = ("spans", *files)
    if key not in _cache:
        from jax.profiler import ProfileData
        out = []
        for path in files:
            for plane in ProfileData.from_file(path).planes:
                if not plane.name.startswith("/host:"):
                    continue
                for line in plane.lines:
                    for e in line.events:
                        if not e.name.startswith(HOST_PREFIX):
                            out.append((e.name, e.start_ns * 1e-9,
                                        e.end_ns * 1e-9, dict(e.stats)))
        out.sort(key=lambda s: s[1])
        _cache[key] = out
    return _cache[key]


def named(spans, name: str, window=None) -> list:
    """The spans called ``name`` (that start inside ``window``)."""
    return [s for s in spans if s[0] == name
            and (window is None or window[0] <= s[1] <= window[1])]


def op_scopes(pattern: str, directory: str | None = None) -> dict:
    """{device id: {op name: tf_op path}} for the ops of the programs
    whose name matches ``pattern``; {} without the XPlane schema."""
    files = trace_files(directory)
    key = ("scopes", pattern, *files)
    if key not in _cache:
        xplane = _xplane_schema()
        out: dict = {}
        for path in files if xplane is not None else ():
            space = xplane.XSpace()
            with open(path, "rb") as f:
                space.ParseFromString(f.read())
            for plane in space.planes:
                m = _DEVICE.match(plane.name)
                if m:
                    out[int(m.group(1))] = _plane_scopes(plane, pattern)
        _cache[key] = out
    return _cache[key]


def _plane_scopes(plane, pattern: str) -> dict:
    rx = re.compile(pattern)
    stat_names = {k: v.name for k, v in plane.stat_metadata.items()}

    def stats(md) -> dict:
        out = {}
        for st in md.stats:
            kind = st.WhichOneof("value")
            v = getattr(st, kind) if kind else None
            if kind == "ref_value":
                v = stat_names.get(v, "")
            out[stat_names.get(st.metadata_id)] = v
        return out

    programs = set()
    for line in plane.lines:
        if line.name != MODULE_LINE:
            continue
        for ev in line.events:
            m = _PROGRAM.match(plane.event_metadata[ev.metadata_id].name)
            if m and rx.search(m.group(1)):
                programs.add(m.group(2))
    names = {}
    for md in plane.event_metadata.values():
        st = stats(md)
        if str(st.get("program_id")) in programs:
            names[split_op(md.name)[0]] = str(st.get("tf_op") or "")
    return names


@functools.cache
def _xplane_schema():
    """The generated XPlane protobuf module that TensorFlow installs,
    loaded from its file: importing ``tensorflow`` would start its own
    runtime.  None where it is not installed."""
    spec = importlib.util.find_spec("tensorflow")
    roots = (spec.submodule_search_locations or []) if spec else []
    paths = [os.path.join(r, "tsl", "profiler", "protobuf", "xplane_pb2.py")
             for r in roots]
    paths = [p for p in paths if os.path.exists(p)]
    if not paths:
        return None
    spec = importlib.util.spec_from_file_location("bench_xplane_pb2",
                                                  paths[0])
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def innermost(spans) -> list:
    """[(start, end, name)]: each instant that nested ``spans``
    [(name, start, end, ...)] cover, by the innermost span open then."""
    out, stack, t = [], [], None
    for name, s, e, *_ in sorted(spans, key=lambda x: (x[1], -x[2])):
        while stack and stack[-1][2] <= s:
            n, _, end = stack.pop()
            out.append((t, end, n))
            t = end
        if stack and t < s:
            out.append((t, s, stack[-1][0]))
        stack.append((name, s, e))
        t = s
    while stack:
        n, _, end = stack.pop()
        out.append((t, end, n))
        t = end
    return [seg for seg in out if seg[1] > seg[0]]


def overlap_by(idle, segments) -> dict:
    """{name: seconds} of the intervals ``idle`` [(start, end)] that lie
    inside each of the ``segments`` [(start, end, name)]; both sorted by
    start and each free of overlaps."""
    out: dict = {}
    i = 0
    for gs, ge in idle:
        while i < len(segments) and segments[i][1] <= gs:
            i += 1
        j = i
        while j < len(segments) and segments[j][0] < ge:
            s, e, name = segments[j]
            ov = min(e, ge) - max(s, gs)
            if ov > 0:
                out[name] = out.get(name, 0.0) + ov
            j += 1
    return out


def idle_by_span(spans, busy, window) -> dict:
    """{span name: seconds} of the device's idle time in ``window`` (the
    gaps between the ``busy`` intervals) during which the innermost open
    serving-engine span (``engine.*``) was that span; the idle time under
    none of them is under ``"outside"``."""
    segs = innermost([s for s in spans if s[0].startswith("engine.")])
    idle = gaps(busy, *window)
    out = overlap_by(idle, segs)
    out["outside"] = sum(e - s for s, e in idle) - sum(out.values())
    return out
