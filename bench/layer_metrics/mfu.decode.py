"""The decode tick's share of the chip's bf16 peak: the model FLOPs of
every decode tick in the traced window (2 x matmul weights per decoded
token plus attention over its keys, bench/work.py) over the device
time of the decode program (``_decode_fn``) in that window times the
peak.  Layer: model step (the whole decode tick).  Moves tpot_p95_ms."""
from bench import work
from bench.trace import module_time

PROGRAM = r"_decode_fn"


def read(r):
    steps = [s for s in r.host_steps(r.record["steps"]) if s[2]]
    if not steps:
        return None
    lo, hi = r.to_trace(steps[0][0]), r.to_trace(steps[-1][1])
    flops = sum(work.decode_flops(r.spec, k) for s in steps for k in s[2])
    dev = r.devices()[0]
    t = sum(e - s for n, s, e in dev.modules
            if PROGRAM in n and lo <= s <= hi)
    if t <= 0:
        return None
    return 100.0 * flops / (t * r.peaks["bf16_flops"])
