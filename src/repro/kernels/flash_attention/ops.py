"""Differentiable jitted wrapper: flash kernels on TPU, oracle elsewhere.

``flash_attention`` is wired through ``jax.custom_vjp``:

* primal / fwd: the Pallas forward kernel; the vjp-fwd variant also saves
  the per-row logsumexp residual, so the backward never needs the
  (sq, skv) score matrix;
* bwd: ``delta = rowsum(o * do)`` is precomputed once in jnp and shared by
  the two recompute kernels (dKV then dQ) — O(S) memory on both passes.

Block sizes: with no explicit ``bq``/``bk``, ``block_sizes`` picks them
from the shape.  Each axis takes the whole axis, padded to a 16-row
tile, if that fits in ``MAX_BLOCK``, else the largest power-of-two
multiple of 128 up to ``MAX_BLOCK`` that pads it by at most an eighth.
Then, while the largest of the three kernels' ``kernel.vmem_bytes``
(tiles, scratch, fp32 temporaries, the dK/dV kernel's ``group`` rows
included) exceeds ``VMEM_BUDGET``, the larger block halves.  Large
blocks make each grid step MXU-sized work.  The rule does not look at
``causal``: the kernels skip dead blocks above the diagonal, and at the
causal training shape 512-blocks (3 of 4 pairs live) beat 256-blocks
(10 of 16) on the chip, so the masked half of a diagonal block costs
less than the steps smaller blocks add.

Sequence lengths that are not block multiples are handled here by padding
sq/skv up to the block size: padded keys are masked inside the kernels
via ``kv_len``; padded query rows produce garbage that is sliced off, and
contribute exactly zero to dK/dV because their ``do`` rows are
zero-padded.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.common import round_up as _round_up
from repro.kernels.flash_attention.kernel import (LANES,
                                                  flash_attention_bwd_dkv,
                                                  flash_attention_bwd_dq,
                                                  flash_attention_fwd,
                                                  vmem_bytes)
from repro.kernels.flash_attention.ref import attention_ref

_SUBLANE = 16    # sequence-block padding granularity (bf16-safe tile)
MAX_BLOCK = 512       # 1024 ran no faster on v5e at the training shape
VMEM_BUDGET = 24 << 20


def _pad_axis(x, axis: int, target: int):
    if x.shape[axis] == target:
        return x
    pad = [(0, 0)] * x.ndim
    pad[axis] = (0, target - x.shape[axis])
    return jnp.pad(x, pad)


def _axis_block(n: int, cap: int) -> int:
    """Block for an axis of length n, cap a power-of-two multiple of 128:
    the whole axis padded to 16 rows if it fits, else the largest
    power-of-two multiple of 128 up to cap that pads n by at most an
    eighth."""
    whole = _round_up(n, _SUBLANE)
    if whole <= cap:
        return whole
    b = cap
    while b > LANES and _round_up(n, b) - n > n // 8:
        b //= 2
    return b


def block_sizes(sq: int, skv: int, head_dim: int, group: int = 1,
                itemsize: int = 2) -> tuple[int, int]:
    """(bq, bk) for the three kernels at this shape (module docstring)."""
    cq = ck = MAX_BLOCK
    while True:
        bq, bk = _axis_block(sq, cq), _axis_block(skv, ck)
        need = max(vmem_bytes(kind, bq, bk, head_dim, group, itemsize)
                   for kind in ("fwd", "dkv", "dq"))
        if need <= VMEM_BUDGET or max(bq, bk) <= LANES:
            return bq, bk
        if bq >= bk:
            while cq >= bq:
                cq //= 2
        else:
            while ck >= bk:
                ck //= 2


def _block_geometry(q, k, bq, bk):
    """Blocks (picked when not given; clamped to the aligned lengths) and
    the padded lengths."""
    sq, skv, d = q.shape[2], k.shape[2], q.shape[3]
    if bq is None or bk is None:
        pq, pk = block_sizes(sq, skv, d, q.shape[1] // k.shape[1],
                             q.dtype.itemsize)
        bq, bk = bq or pq, bk or pk
    bq = min(bq, _round_up(sq, _SUBLANE))
    bk = min(bk, _round_up(skv, _SUBLANE))
    return bq, bk, _round_up(sq, bq), _round_up(skv, bk)


def _fwd(q, k, v, causal, q_offset, interpret, bq, bk, save_residuals):
    sq, skv = q.shape[2], k.shape[2]
    bq, bk, sq_p, skv_p = _block_geometry(q, k, bq, bk)
    qp = _pad_axis(q, 2, sq_p)
    kp = _pad_axis(k, 2, skv_p)
    vp = _pad_axis(v, 2, skv_p)
    kv_len = skv if skv_p != skv else None
    out = flash_attention_fwd(qp, kp, vp, causal=causal, bq=bq, bk=bk,
                              interpret=interpret, q_offset=q_offset,
                              kv_len=kv_len, save_residuals=save_residuals)
    if save_residuals:
        o, lse = out
        return o[:, :, :sq], lse[:, :, :sq]
    return out[:, :, :sq]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash_attention(q, k, v, causal, q_offset, interpret, bq, bk):
    return _fwd(q, k, v, causal, q_offset, interpret, bq, bk, False)


def _flash_attention_fwd_rule(q, k, v, causal, q_offset, interpret, bq, bk):
    o, lse = _fwd(q, k, v, causal, q_offset, interpret, bq, bk, True)
    return o, (q, k, v, o, lse)


def _flash_attention_bwd_rule(causal, q_offset, interpret, bq, bk, res, do):
    q, k, v, o, lse = res
    sq, skv = q.shape[2], k.shape[2]
    bq, bk, sq_p, skv_p = _block_geometry(q, k, bq, bk)
    delta = jnp.sum(o.astype(jnp.float32) * do.astype(jnp.float32), axis=-1)
    qp = _pad_axis(q, 2, sq_p)
    dop = _pad_axis(do, 2, sq_p)        # zero rows -> padded q contributes 0
    lsep = _pad_axis(lse, 2, sq_p)
    deltap = _pad_axis(delta, 2, sq_p)
    kp = _pad_axis(k, 2, skv_p)
    vp = _pad_axis(v, 2, skv_p)
    kv_len = skv if skv_p != skv else None
    kw = dict(causal=causal, bq=bq, bk=bk, q_offset=q_offset, kv_len=kv_len,
              interpret=interpret)
    dk, dv = flash_attention_bwd_dkv(qp, kp, vp, dop, lsep, deltap, **kw)
    dq = flash_attention_bwd_dq(qp, kp, vp, dop, lsep, deltap, **kw)
    return (dq[:, :, :sq].astype(q.dtype),
            dk[:, :, :skv].astype(k.dtype),
            dv[:, :, :skv].astype(v.dtype))


_flash_attention.defvjp(_flash_attention_fwd_rule, _flash_attention_bwd_rule)


@functools.partial(jax.jit,
                   static_argnames=("causal", "impl", "bq", "bk", "q_offset"))
def flash_attention(q, k, v, *, causal=True, impl="auto", bq=None, bk=None,
                    q_offset=None):
    """impl: 'auto' (kernel on TPU, ref otherwise) | 'kernel' | 'interpret'
    | 'ref'.  Differentiable on every path: kernel/interpret use the fused
    Pallas custom_vjp, ref uses jax autodiff of the jnp oracle.

    ``bq``/``bk``: block sizes; ``block_sizes`` picks any not given.  On
    the chip a block is a multiple of 128 or the whole aligned axis.

    ``q_offset``: absolute position of q[0] among the keys (static);
    defaults to skv - sq (end-aligned). The ref path always uses the
    end-aligned convention.
    """
    if impl == "auto":
        impl = "kernel" if jax.default_backend() == "tpu" else "ref"
    if impl == "ref":
        return attention_ref(q, k, v, causal=causal)
    if q_offset is None:
        q_offset = k.shape[2] - q.shape[2]
    return _flash_attention(q, k, v, causal, q_offset,
                            impl == "interpret", bq, bk)
