"""Collective time per training step that holds up the chip, averaged
over the chips: the collective ops on the device's op line (a
synchronous collective, or the ``-done`` of an asynchronous one waiting
for its transfer), during which that core runs nothing else.  Transfers
in flight behind compute sit on the async line and do not count: they
are hidden.  Layer: collectives.  Moves train_tok_s."""
import re

from bench.trace import leaf_ops

COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all")
STEP = re.compile(r"train_step|local_step|shard_map")


def read(r):
    lo, hi = r.window
    per_chip = []
    for dev in r.devices():
        coll = sum(e - s for n, s, e, _, _ in leaf_ops(dev)
                   if COLLECTIVE.search(n) and lo <= s <= hi)
        steps = sum(1 for n, s, e in dev.modules
                    if STEP.search(n) and lo <= s <= hi)
        if steps:
            per_chip.append(coll / steps)
    return 1e3 * sum(per_chip) / len(per_chip) if per_chip else None
