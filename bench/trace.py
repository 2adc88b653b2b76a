"""The profiler's trace, reduced to intervals the metric readers use.

``Trace.load(dir)`` reads the ``.xplane.pb`` that ``jax.profiler``
wrote under ``dir`` with nothing but JAX.  Each device plane
(``/device:TPU:<n>``) gives its programs (line ``XLA Modules``) and its
operations (line ``XLA Ops``); the host's threads give the benchmark's
own ``TraceAnnotation`` spans.  Times are seconds on the trace's clock.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re

MODULE_LINE = "XLA Modules"
OPS_LINE = "XLA Ops"
HOST_PREFIX = "bench."
_SUFFIX = re.compile(r"\(\d+\)$")


@dataclasses.dataclass
class Device:
    modules: list      # [(name, start, end)], name without its "(id)"
    ops: list          # [(name, start, end, module, result type)]


@dataclasses.dataclass
class Trace:
    devices: dict      # device id -> Device
    host: list         # [(name, start, end)] benchmark spans

    @classmethod
    def load(cls, directory: str) -> "Trace":
        from jax.profiler import ProfileData
        files = sorted(glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                                 recursive=True))
        if not files:
            raise FileNotFoundError(f"no .xplane.pb under {directory}")
        devices, host = {}, []
        for path in files:
            for plane in ProfileData.from_file(path).planes:
                m = re.match(r"/device:TPU:(\d+)$", plane.name)
                if m:
                    devices[int(m.group(1))] = _device(plane)
                elif plane.name.startswith("/host:"):
                    for line in plane.lines:
                        host.extend(
                            (e.name, e.start_ns * 1e-9, e.end_ns * 1e-9)
                            for e in line.events
                            if e.name.startswith(HOST_PREFIX))
        host.sort(key=lambda e: e[1])
        return cls(devices, host)


def _device(plane) -> Device:
    modules, ops = [], []
    for line in plane.lines:
        if line.name == MODULE_LINE:
            modules = [(_SUFFIX.sub("", e.name), e.start_ns * 1e-9,
                        e.end_ns * 1e-9) for e in line.events]
        elif line.name == OPS_LINE:
            ops = [(*split_op(e.name), e.start_ns * 1e-9, e.end_ns * 1e-9)
                   for e in line.events]
    modules.sort(key=lambda e: e[1])
    ops.sort(key=lambda e: e[2])
    return Device(modules, assign_modules(ops, modules))


def split_op(text: str) -> tuple[str, str]:
    """An op event is named by its HLO text, ``%name = type op(...)``:
    (name without the %, result type)."""
    name, _, rest = text.partition(" = ")
    return name.lstrip("%"), rest.split(" ", 1)[0] if rest else ""


def assign_modules(ops, modules) -> list:
    """[(name, result type, start, end)] -> [(name, start, end, program,
    result type)], each op tagged with the program whose run contains its
    start."""
    out, j = [], 0
    for name, rtype, s, e in ops:
        while j < len(modules) and modules[j][2] < s:
            j += 1
        mod = modules[j][0] if j < len(modules) and modules[j][1] <= s else ""
        out.append((name, s, e, mod, rtype))
    return out


def leaf_ops(dev: Device) -> list:
    """The ops that hold no other op: a loop or call op on the op line
    spans the ops of its body, which would otherwise count twice."""
    ops = dev.ops
    return [op for i, op in enumerate(ops)
            if not (i + 1 < len(ops) and ops[i + 1][1] < op[2])]


def union_length(intervals) -> float:
    """Total length covered by ``[(start, end, ...)]``."""
    total, cur_s, cur_e = 0.0, None, None
    for iv in sorted(intervals, key=lambda x: x[0]):
        s, e = iv[0], iv[1]
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def gaps(intervals, start: float, end: float) -> list:
    """[(gap start, gap end)] inside [start, end] that no interval covers."""
    out, t = [], start
    for iv in sorted(intervals, key=lambda x: x[0]):
        s, e = max(iv[0], start), min(iv[1], end)
        if e <= s:
            continue
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if t < end:
        out.append((t, end))
    return out


def busy_intervals(dev: Device) -> list:
    """What ran on the device: its operations, or its programs where the
    trace has no operation line."""
    return [(op[1], op[2]) for op in dev.ops] or \
        [(s, e) for _, s, e in dev.modules]


def module_time(dev: Device, pattern: str) -> tuple[float, int]:
    """(seconds, runs) of the programs whose name matches ``pattern``."""
    rx = re.compile(pattern)
    runs = [e - s for n, s, e in dev.modules if rx.search(n)]
    return sum(runs), len(runs)


def breakdown(trace: Trace, window: tuple, top: int = 10) -> dict:
    """The device operations that took most time (summed over devices,
    per device on average), and the longest idle gaps of device 0 by
    the benchmark span that covered them."""
    n = max(len(trace.devices), 1)
    per_op: dict = {}
    for dev in trace.devices.values():
        for name, s, e, mod, _ in leaf_ops(dev):
            key = f"{mod}/{name}" if mod else name
            per_op[key] = per_op.get(key, 0.0) + (e - s) / n
    ops = sorted(per_op.items(), key=lambda kv: -kv[1])[:top]
    idle: dict = {}
    dev0 = trace.devices.get(min(trace.devices)) if trace.devices else None
    if dev0 is not None:
        for gs, ge in gaps(busy_intervals(dev0), *window):
            idle_name = _covering_span(trace.host, gs, ge)
            idle[idle_name] = idle.get(idle_name, 0.0) + (ge - gs)
    gaps_top = sorted(idle.items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[k, v] for k, v in ops],
            "idle_gaps": [[k, v] for k, v in gaps_top]}


def _covering_span(host, s: float, e: float) -> str:
    """Name of the innermost benchmark span that overlaps [s, e] most."""
    best, best_key = "host:other", None
    for name, hs, he in host:
        ov = min(he, e) - max(hs, s)
        if ov <= 0:
            continue
        key = (ov, -(he - hs))
        if best_key is None or key > best_key:
            best, best_key = name, key
    return best
