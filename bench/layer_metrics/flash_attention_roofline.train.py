"""The flash-attention kernels' share of their roofline in training:
for every forward, dK/dV and dQ kernel call in the traced window, the
least time its shapes need (bench/work.py: the products its outputs
need, the bytes it must move), over the kernels' device time.  Layer:
kernels.  Moves train_tok_s.

The three kernels are custom calls named ``flash_attention.<n>``; they
are told apart by their result: the forward returns (bf16 out, f32
log-sum-exp), dK/dV a pair of f32 arrays, dQ one f32 array."""
from bench import work
from bench.trace import leaf_ops

KERNEL = "flash_attention"


def kind(rtype):
    if rtype.startswith("(bf16"):
        return "fwd"
    if rtype.startswith("(f32"):
        return "dkv"
    if rtype.startswith("f32"):
        return "dq"
    return None


def read(r):
    tr = r.cell.traffic
    shape = dict(batch=tr["batch_per_chip"], heads=r.spec.heads,
                 kv_heads=r.spec.kv_heads, seq=tr["seq"],
                 head_dim=r.spec.head_dim)
    least = spent = 0.0
    lo, hi = r.window
    for dev in r.devices():
        for n, s, e, _, rtype in leaf_ops(dev):
            k = kind(rtype) if n.startswith(KERNEL) else None
            if k is None or not lo <= s <= hi:
                continue
            f, b = work.flash_attention(k, **shape)
            least += work.roofline_seconds(f, b, r.peaks)[0]
            spent += e - s
    return 100.0 * least / spent if spent > 0 else None
