"""Metrics + tracing substrate (DESIGN.md §10).

  * ``registry`` — typed Counter / Gauge / Histogram in named
    registries; exact p50/p95/p99 export, reset-for-tests.
  * ``trace`` — nestable host-side ``span``s at jit boundaries, as
    JAX profiler annotations (on the device trace's clock), and the
    structured ``EventLog`` the platform's failure taxonomy rides on.

``now()`` is the sanctioned monotonic clock: the CI guard lane keeps
``time.perf_counter`` out of every other module under ``src/``.
"""
from repro.telemetry.registry import (Counter, Gauge, Histogram, Registry,
                                      get_registry)
from repro.telemetry.trace import EventLog, enabled, now, set_enabled, span

__all__ = [
    "Counter", "Gauge", "Histogram", "Registry", "get_registry",
    "EventLog", "enabled", "now", "set_enabled", "span",
]
