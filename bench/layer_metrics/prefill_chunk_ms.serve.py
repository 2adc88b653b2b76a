"""Mean device time of one run of the prefill program (the engine's
jitted ``_chunk_fn``; with whole-prompt prefill, one run per prompt),
from the trace.  Layer: model step.  Moves tpot_p95_ms: a prefill
admitted in a step holds up that step's decode tick for every running
request."""
from bench.trace import module_time

PROGRAM = r"_chunk_fn"


def read(r):
    total = runs = 0
    for dev in r.devices():
        t, n = module_time(dev, PROGRAM)
        total, runs = total + t, runs + n
    return total / runs * 1e3 if runs else None
