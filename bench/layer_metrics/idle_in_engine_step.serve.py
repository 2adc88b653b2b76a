"""Share of the traced window in which device 0 sat idle while the host
was inside the serving engine's ``step()`` (its ``engine.step`` span):
the part of ``device_idle_share.serve`` that the engine's own host code
causes, such as building block tables, admitting, and waiting for a
token.  The rest of the idle time is arrivals and the benchmark's loop.
Reads None where the program's spans are not in the trace.  Layer:
serving scheduler.  Moves tpot_p95_ms."""
from bench import program_spans
from bench.trace import busy_intervals, gaps

SPAN = "engine.step"


def read(r):
    steps = [(s, e, SPAN) for _, s, e, _ in
             program_spans.named(program_spans.load(), SPAN)]
    if not steps or not r.trace.devices:
        return None
    lo, hi = r.window
    dev0 = r.trace.devices[min(r.trace.devices)]
    idle = program_spans.overlap_by(gaps(busy_intervals(dev0), lo, hi),
                                    steps).get(SPAN, 0.0)
    return 100.0 * idle / (hi - lo)
