"""How full the decode batch runs: the mean number of running requests a
decode tick carried (the ``active`` count of each ``engine.decode_tick``
span that starts in the traced window, taken where the engine builds the
batch) over the engine's ``max_slots``.  Reads None where the program's
spans are not in the trace.  Layer: serving scheduler.  Moves
tpot_p95_ms."""
from bench import program_spans

SPAN = "engine.decode_tick"


def read(r):
    ticks = program_spans.named(program_spans.load(), SPAN, r.window)
    active = [t[3]["active"] for t in ticks if "active" in t[3]]
    if not active:
        return None
    slots = r.cell.config["engine"]["max_slots"]
    return 100.0 * sum(active) / (len(active) * slots)
