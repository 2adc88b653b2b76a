"""Double-binary-tree allreduce (paper §IV Algorithm 2, Sanders et al. [65])
expressed as a ``ppermute`` schedule, plus a ring reference.

The paper's HFReduce runs its inter-node phase as a double binary tree over
RDMA: the data is split in two halves, each reduced up (and broadcast down)
a different binary tree so that every rank is an interior node in at most
one tree — full bandwidth use.  Here each tree round becomes one
``lax.ppermute``; the schedule is computed in Python from the static axis
size at trace time.

XLA's ``psum`` already lowers to near-optimal collectives on ICI; the tree
schedule exists (a) as the paper-faithful algorithm, validated numerically
against psum on fake devices, and (b) as the cross-pod phase option of
``hfreduce_tree`` where latency (not bandwidth) dominates: a tree is
2·log2(n) rounds vs a ring's 2·(n-1).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax


# ------------------------- schedule construction ---------------------------


def _inorder_tree(ranks):
    """In-order binary tree; returns {child: (parent, side)} and depths."""
    parent, depth = {}, {}

    def build(lo, hi, d, par, side):
        if lo > hi:
            return
        mid = (lo + hi) // 2
        r = ranks[mid]
        parent[r] = (par, side)
        depth[r] = d
        build(lo, mid - 1, d + 1, r, "L")
        build(mid + 1, hi, d + 1, r, "R")

    build(0, len(ranks) - 1, 0, -1, "")
    return parent, depth


def tree_schedule(n: int, shift: int = 0):
    """Rounds of (perm_pairs, recv_mask) for reduce & broadcast phases."""
    ranks = [(i + shift) % n for i in range(n)]
    parent, depth = _inorder_tree(ranks)
    maxd = max(depth.values())
    reduce_rounds, bcast_rounds = [], []
    for d in range(maxd, 0, -1):
        for side in ("L", "R"):
            pairs = [(c, p) for c, (p, s) in parent.items()
                     if depth[c] == d and s == side and p >= 0]
            if pairs:
                reduce_rounds.append(pairs)
    for d in range(1, maxd + 1):
        for side in ("L", "R"):
            pairs = [(p, c) for c, (p, s) in parent.items()
                     if depth[c] == d and s == side and p >= 0]
            if pairs:
                bcast_rounds.append(pairs)
    return reduce_rounds, bcast_rounds


def _masks(pairs, n):
    recv = [False] * n
    for _, dst in pairs:
        recv[dst] = True
    return jnp.asarray(recv)


# ------------------------------ collectives --------------------------------


def _tree_allreduce_one(x, axis_name, shift):
    n = lax.axis_size(axis_name)
    if n == 1:
        return x
    reduce_rounds, bcast_rounds = tree_schedule(n, shift)
    idx = lax.axis_index(axis_name)
    acc = x
    for pairs in reduce_rounds:
        recvd = lax.ppermute(acc, axis_name, pairs)
        # non-receivers get zeros from ppermute -> unconditional add is safe
        acc = acc + recvd
    for pairs in bcast_rounds:
        recvd = lax.ppermute(acc, axis_name, pairs)
        mask = _masks(pairs, n)[idx]
        acc = jnp.where(mask, recvd, acc)
    return acc


def tree_allreduce(x, axis_name="pod"):
    """Double binary tree: two complementary trees, half the data each."""
    n = lax.axis_size(axis_name)
    if n == 1:
        return x
    shape = x.shape
    flat = x.reshape(-1)
    pad = (-flat.shape[0]) % 2
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros((pad,), flat.dtype)])
    h1, h2 = jnp.split(flat, 2)
    r1 = _tree_allreduce_one(h1, axis_name, shift=0)
    r2 = _tree_allreduce_one(h2, axis_name, shift=n // 2 or 1)
    out = jnp.concatenate([r1, r2])
    if pad:
        out = out[:-pad]
    return out.reshape(shape)


def ring_allreduce(x, axis_name="data"):
    """Reference ring (reduce-scatter + all-gather), the 'NCCL' analogue."""
    n = lax.axis_size(axis_name)
    if n == 1:
        return x
    shape = x.shape
    flat = x.reshape(-1)
    pad = (-flat.shape[0]) % n
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros((pad,), flat.dtype)])
    chunks = flat.reshape(n, -1)
    idx = lax.axis_index(axis_name)
    fwd = [(i, (i + 1) % n) for i in range(n)]

    # reduce-scatter: after n-1 steps rank r owns the full sum of chunk r+1
    send_idx = idx
    acc = chunks
    send = jnp.take(acc, send_idx, axis=0)
    for step in range(n - 1):
        recvd = lax.ppermute(send, axis_name, fwd)
        recv_idx = (send_idx - 1) % n
        updated = jnp.take(acc, recv_idx, axis=0) + recvd
        acc = acc.at[recv_idx].set(updated)
        send_idx = recv_idx
        send = updated

    # all-gather ring
    own_idx = send_idx
    send = jnp.take(acc, own_idx, axis=0)
    for step in range(n - 1):
        recvd = lax.ppermute(send, axis_name, fwd)
        recv_idx = (own_idx - 1 - step) % n
        acc = acc.at[recv_idx].set(recvd)
        send = recvd

    out = acc.reshape(-1)
    if pad:
        out = out[:-pad]
    return out.reshape(shape)
