"""Host-side spans on the profiler's clock, and the structured event log.

``span("name", **attrs)`` wraps a host-side region — a prefill chunk, a
decode tick, a train step, a checkpoint write — as a
``jax.profiler.TraceAnnotation``: while a profiler trace is recording
(``jax.profiler.start_trace``, or a launcher's ``--trace DIR``), the
region lands on the host plane of that trace with its attributes as
metadata, on the same clock as the device's operations.  While nothing
records, a span costs the annotation's construction and an inactive
check.  Spans wrap jit *boundaries* and never run inside a traced
function, so instrumentation cannot change what XLA compiles (the
zero-extra-traces guard in tests/test_telemetry.py pins this).

A count known only when the region ends goes in through the span's
``set_metadata(**attrs)``::

    with span("engine.prepare_tick") as s:
        ...
        s.set_metadata(width=width, evicted=n)

:class:`EventLog` carries the *discrete* event stream (failure /
straggler / rescale / ckpt — the paper's Table-6 taxonomy) as JSONL and
mirrors each record into the trace as a zero-length ``event.<kind>``
annotation carrying its fields.

``set_enabled(False)`` (or env ``REPRO_TELEMETRY=0``) swaps ``span``
for a shared no-op object.  Code that needs a *measurement* (validator
bandwidth, straggler detection) uses :func:`now` directly: spans are
observability, not control flow.

This module (with ``registry.py``) is the one place in ``src/`` allowed
to call ``time.perf_counter`` — the CI guard lane greps everything else.
"""
from __future__ import annotations

import json
import os
import threading
import time

now = time.perf_counter

_enabled = os.environ.get("REPRO_TELEMETRY", "1") != "0"
# jax.profiler.TraceAnnotation, imported at the first span: importing
# this module must not import JAX (nor query a backend)
_annotation = None


def enabled() -> bool:
    return _enabled


def set_enabled(on: bool) -> None:
    global _enabled
    _enabled = bool(on)


class _NullSpan:
    """Shared no-op span when telemetry is disabled."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, exc_type, exc, tb):
        return None

    def set_metadata(self, **attrs) -> None:
        return None


_NULL_SPAN = _NullSpan()


def span(name: str, /, **attrs):
    """``with span("engine.decode_tick", active=4): ...`` — a profiler
    annotation named ``name`` with ``attrs`` as its metadata."""
    global _annotation
    if not _enabled:
        return _NULL_SPAN
    if _annotation is None:
        from jax.profiler import TraceAnnotation
        _annotation = TraceAnnotation
    return _annotation(name, **attrs)


class EventLog:
    """Structured discrete-event stream (JSONL on disk).

    One ``emit`` per platform event — failure, straggler, rescale,
    ckpt, restore — so the FT runner's report, its ``on_event``
    callback, and the persisted log all read the *same* record (they
    cannot drift).  Records carry ``t`` (seconds since the log's
    creation, monotonic) plus whatever fields the caller attaches;
    ``kind`` is the taxonomy key (paper Table 6).
    """

    def __init__(self):
        self.events: list[dict] = []
        self._t0 = now()
        self._lock = threading.Lock()

    def emit(self, kind: str, **fields) -> dict:
        rec = {"kind": kind, "t": now() - self._t0, **fields}
        with self._lock:
            self.events.append(rec)
        with span("event." + kind, **fields):
            pass
        return rec

    def write(self, path: str) -> str:
        with self._lock:
            lines = [json.dumps(e, default=str) for e in self.events]
        with open(path, "w") as f:
            f.write("\n".join(lines) + ("\n" if lines else ""))
        return path
