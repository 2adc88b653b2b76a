"""Gradient bucketing — the HaiScale DDP overlap unit (paper §V-A).

HaiScale DDP launches allreduce asynchronously per gradient bucket as soon
as backprop produces it, overlapping the weak-link transfer with remaining
backward compute.  In XLA the async overlap itself is the latency-hiding
scheduler's job; what we control is the *structure*: gradients are packed
into fixed-byte buckets in reverse-layer order (ready-first), each bucket
synced by its own collective, so the compiled HLO has many independent
all-reduces that can interleave with compute instead of one monolithic
end-of-step collective.

The flat concat travels in ``wire_dtype`` — by default the promoted dtype
of the leaves, so an all-bf16 gradient tree stays bf16 on the wire
(upcasting to fp32 would double cross-pod bytes and silently negate
``compress="bf16"``).  Leaf dtypes are restored on unflatten.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

DEFAULT_BUCKET_BYTES = 25 * 1024 * 1024   # torch-DDP-style default


@dataclasses.dataclass(frozen=True)
class BucketPlan:
    treedef: object
    shapes: tuple
    dtypes: tuple
    sizes: tuple
    bucket_slices: tuple     # list of (start, end) into the flat concat
    wire_dtype: object       # dtype of the flat concat on the wire


def _promoted_dtype(dtypes):
    if not dtypes:
        return jnp.dtype(jnp.float32)
    return jnp.dtype(functools.reduce(jnp.promote_types, dtypes))


def plan_buckets(tree, bucket_bytes=DEFAULT_BUCKET_BYTES,
                 wire_dtype=None) -> BucketPlan:
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    shapes = tuple(l.shape for l in leaves)
    dtypes = tuple(l.dtype for l in leaves)
    sizes = tuple(int(np.prod(s)) for s in shapes)
    wire_dtype = jnp.dtype(wire_dtype) if wire_dtype is not None \
        else _promoted_dtype(dtypes)
    # reverse order: last-produced grads (first layers... reverse of forward)
    # are bucketed first so their sync can start earliest during backward.
    # Bucket byte budgets count *wire* bytes — what the collective moves.
    slices = []
    total = sum(sizes)
    start = total
    cur = 0
    end = total
    for sz in sizes[::-1]:
        b = sz * wire_dtype.itemsize
        if cur + b > bucket_bytes and cur > 0:
            slices.append((start, end))
            end = start
            cur = 0
        start -= sz
        cur += b
    slices.append((start, end))
    return BucketPlan(treedef, shapes, dtypes, sizes, tuple(slices),
                      wire_dtype)


def bucket_leaf_ranges(plan: BucketPlan) -> tuple:
    """Map each bucket's flat slice back to the leaf range it covers.

    Buckets always contain whole leaves, so every ``(start, end)`` in
    ``plan.bucket_slices`` lands exactly on leaf boundaries; the returned
    ``(i0, i1)`` pairs (leaf indices, forward flatten order) let a caller
    sync a bucket without materializing the full flat concat — the overlap
    hook in ``core/ddp.py`` hangs one custom_vjp per range off these.
    """
    offsets = np.cumsum((0,) + plan.sizes)
    ranges = []
    for start, end in plan.bucket_slices:
        i0 = int(np.searchsorted(offsets, start))
        i1 = int(np.searchsorted(offsets, end))
        assert offsets[i0] == start and offsets[i1] == end, \
            (start, end, tuple(offsets))
        ranges.append((i0, i1))
    return tuple(ranges)


def flatten_tree(tree, wire_dtype=None) -> jax.Array:
    leaves = jax.tree_util.tree_leaves(tree)
    if wire_dtype is None:
        wire_dtype = _promoted_dtype([l.dtype for l in leaves])
    return jnp.concatenate([l.reshape(-1).astype(wire_dtype)
                            for l in leaves])


def unflatten_leaves(flat: jax.Array, shapes, dtypes, sizes) -> list:
    """Split a flat concat back into leaves (restoring leaf dtypes).

    A sub-32-bit flat is split from an fp32 copy (exact round trip):
    slicing a packed 1-D array at leaf offsets off its tile (2048
    elements in bf16; fp32 tiles hold 1024) makes the TPU compile time
    grow with the element count.  gpt2-medium's ZeRO-1 step for a
    v5e:2x2 host took 1069 s to compile without this, 48 s with it.
    The barrier keeps XLA from fusing the cast back into the slices.
    """
    if flat.dtype.itemsize < 4:
        flat = jax.lax.optimization_barrier(flat.astype(jnp.float32))
    out, off = [], 0
    for shape, dtype, size in zip(shapes, dtypes, sizes):
        out.append(flat[off:off + size].reshape(shape).astype(dtype))
        off += size
    return out


def unflatten_tree(plan: BucketPlan, flat: jax.Array):
    leaves = unflatten_leaves(flat, plan.shapes, plan.dtypes, plan.sizes)
    return jax.tree_util.tree_unflatten(plan.treedef, leaves)


def bucketed_apply(plan: BucketPlan, tree, fn):
    """Apply ``fn`` (a collective) per bucket of the flattened tree."""
    flat = flatten_tree(tree, plan.wire_dtype)
    parts = [fn(flat[s:e]) for s, e in plan.bucket_slices]
    # bucket_slices cover [0, total) in reverse contiguous order
    ordered = sorted(zip(plan.bucket_slices, parts), key=lambda t: t[0][0])
    flat = jnp.concatenate([p for _, p in ordered])
    return unflatten_tree(plan, flat)
