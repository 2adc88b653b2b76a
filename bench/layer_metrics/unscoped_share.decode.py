"""Share of the decode program's (``_decode_fn``) device time, over its
leaf operations in the traced window on device 0, spent in operations
that carry none of the model's layer scopes in their ``tf_op`` path:
copies the compiler inserts and the layer scan's own stacking and
slicing, outside any layer's work.  ``layers``, the scope around the
scan, does not count as a layer's scope.  Reads None where no op of the
program carries a scope (a program without them, or a trace without
the op paths).  Layer: model step.  Moves tpot_p95_ms."""
from bench import program_spans
from bench.trace import leaf_ops

PROGRAM = "_decode_fn"
SCOPES = frozenset({"embed", "norm", "qkv", "kv_write", "attention",
                    "out_proj", "mlp", "moe", "lm_head"})


def scoped(tf_op: str) -> bool:
    return not SCOPES.isdisjoint(tf_op.split("/"))


def read(r):
    if not r.trace.devices:
        return None
    dev_id = min(r.trace.devices)
    paths = program_spans.op_scopes(PROGRAM).get(dev_id, {})
    if not any(scoped(p) for p in paths.values()):
        return None
    lo, hi = r.window
    total = unscoped = 0.0
    for name, s, e, prog, _ in leaf_ops(r.trace.devices[dev_id]):
        if PROGRAM in prog and lo <= s <= hi:
            total += e - s
            if not scoped(paths.get(name, "")):
                unscoped += e - s
    return 100.0 * unscoped / total if total > 0 else None
