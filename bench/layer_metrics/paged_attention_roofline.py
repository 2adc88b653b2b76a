"""The paged chunk-attention kernel's share of its roofline inside the
decode program: the least time the chip needs for what the decode ticks
in the traced window ask of attention (each query against its keys:
q.k and p.v for every head, each key and value read once, bench/work.py)
over the kernel's device time there.  Every tick is bound by memory at
these shapes.  Layer: kernels.  Moves tpot_p95_ms."""
from bench import work
from bench.trace import leaf_ops

PROGRAM = "_decode_fn"
KERNEL = "paged_chunk_attention"


def read(r):
    steps = [s for s in r.host_steps(r.record["steps"]) if s[2]]
    if not steps:
        return None
    least = 0.0
    for s in steps:
        for k in s[2]:
            f, b = work.paged_attention(r.spec, k)
            least += work.roofline_seconds(f, b, r.peaks)[0]
    dev = r.devices()[0]
    lo, hi = r.to_trace(steps[0][0]), r.to_trace(steps[-1][1])
    t = sum(e - s for n, s, e, m, _ in leaf_ops(dev)
            if PROGRAM in m and lo <= s <= hi and n.startswith(KERNEL))
    return 100.0 * least / t if t > 0 else None
