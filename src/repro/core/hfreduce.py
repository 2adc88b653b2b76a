"""HFReduce on TPU: hierarchical allreduce that minimizes weak-link bytes.

Paper §IV: Fire-Flyer reduces *inside the node first* (8 GPUs -> 1 buffer),
then runs a double-binary-tree allreduce across nodes over the single
200 Gbps NIC, then broadcasts back.  Per unit of gradient data, the weak
link carries 1/8 of what a flat ring would push through it.

TPU mapping (DESIGN.md §2): the weak link is the pod boundary ("pod" mesh
axis); the strong fabric is intra-pod ICI ("data"/"model" axes).  The
schedule is:

  phase 1  psum_scatter over the strong axis   (intra-pod reduce-scatter)
  phase 2  psum over the weak axis             (cross-pod allreduce of 1/N)
  phase 3  all_gather over the strong axis     (intra-pod broadcast)

Cross-pod bytes per chip: 2 * |x| / strong_size   (vs 2 * |x| for a flat
allreduce over ("pod","data") — the paper's (2n-1)/n PCIe argument restated
for the pod boundary).  Phase 2 optionally compresses its payload
(core/compression.py — the analogue of HFReduce's FP16/BF16/FP8 CPU reduce).

These functions are *collectives*: call them inside ``shard_map``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax


def _pad_to(x, mult):
    n = x.shape[0]
    pad = (-n) % mult
    if pad:
        x = jnp.concatenate([x, jnp.zeros((pad,) + x.shape[1:], x.dtype)])
    return x, pad


def hfreduce(x, *, strong_axis="data", weak_axis="pod",
             weak_psum=None, prescale=None):
    """Hierarchical allreduce of ``x`` (any shape) over strong+weak axes.

    ``weak_psum(x, axis_name)``: override for the cross-pod phase (e.g. a
    compressed or tree-scheduled allreduce).  Defaults to ``lax.psum``.

    ``prescale``: optional scalar multiplied into the intra-pod shard
    *before* the weak-axis phase.  Gradient means (1/n_shards) belong here
    rather than after decompression: a compressed phase-2 wire format
    (fp8/int8/bf16) then quantizes mean-magnitude values instead of
    pod-sum-magnitude ones, which both avoids overflow of narrow formats
    (fp8 e4m3 saturates at 448) and keeps the quantization step size — and
    therefore the absolute error — 1/n_shards smaller (DESIGN.md §3).
    """
    weak_psum = weak_psum or (lambda v, ax: lax.psum(v, ax))
    strong = lax.axis_size(strong_axis)
    shape = x.shape
    flat = x.reshape(-1)
    flat, pad = _pad_to(flat, strong)
    # phase 1: intra-pod reduce-scatter (strong fabric)
    shard = lax.psum_scatter(flat, strong_axis, scatter_dimension=0,
                             tiled=True)
    if prescale is not None:
        shard = shard * jnp.asarray(prescale, shard.dtype)
    # phase 2: cross-pod allreduce on the 1/strong shard (weak link)
    shard = weak_psum(shard, weak_axis)
    # phase 3: intra-pod all-gather
    full = lax.all_gather(shard, strong_axis, axis=0, tiled=True)
    if pad:
        full = full[:-pad]
    return full.reshape(shape)


def flat_allreduce(x, *, axes=("pod", "data")):
    """Baseline: one flat psum over all axes (the 'NCCL ring' analogue)."""
    return lax.psum(x, axes)


def hfreduce_tree(x, *, strong_axis="data", weak_axis="pod"):
    """HFReduce with the paper's double-binary-tree cross-pod phase."""
    from repro.core.tree_allreduce import tree_allreduce
    return hfreduce(x, strong_axis=strong_axis, weak_axis=weak_axis,
                    weak_psum=lambda v, ax: tree_allreduce(v, ax))


def hfreduce_pytree(tree, **kw):
    """Apply hfreduce leaf-wise to a gradient pytree."""
    return jax.tree_util.tree_map(lambda g: hfreduce(g, **kw), tree)


# ---------------------------------------------------------------------------
# Cost model (napkin math used by benchmarks + EXPERIMENTS.md §Perf):
# bytes each chip pushes across the pod boundary per allreduce of V bytes.
# ---------------------------------------------------------------------------


def crosspod_bytes_flat(v_bytes: int, pods: int, intra: int) -> float:
    """Flat ring allreduce over pods*intra ranks: every byte crosses the
    boundary ~2x (reduce + gather phases pass the cut once each way)."""
    if pods == 1:
        return 0.0
    return 2.0 * v_bytes * (pods - 1) / pods


def crosspod_bytes_hier(v_bytes: int, pods: int, intra: int) -> float:
    """Hierarchical: only the 1/intra shard crosses, twice."""
    if pods == 1:
        return 0.0
    return 2.0 * (v_bytes / intra) * (pods - 1) / pods
