"""Flash attention forward + backward kernels (Pallas TPU).

Forward: VMEM-tiled online-softmax attention with GQA: the grid walks
(batch, q_head, q_block, kv_block) with the kv_block axis innermost and
sequential on TPU, so the (m, l, acc) running stats live in VMEM scratch
across kv blocks.  GQA is free: the K/V BlockSpec index_map folds the
q_head -> kv_head mapping (h // group), so grouped K/V are never
materialized at full head count in HBM.  With ``save_residuals=True`` the
kernel also emits the per-row softmax log-normalizer ``lse = m + log(l)``
(shape (b, h, sq), fp32) — the only residual the backward needs beyond
q/k/v/o/do.

Backward: two recompute kernels in the FlashAttention-2 style, neither of
which ever materializes the (sq, skv) score matrix:

* ``flash_attention_bwd_dkv`` — grid (batch, kv_head, kv_block, q_block),
  q innermost.  dK/dV for one kv block accumulate in VMEM scratch across
  all q blocks AND across the whole query-head group (a static loop over
  ``group`` inside the kernel), so GQA gradients are reduced on-chip
  instead of via a post-hoc jnp sum over broadcast heads.
* ``flash_attention_bwd_dq`` — grid (batch, q_head, q_block, kv_block),
  kv innermost, accumulating dQ for one q block in VMEM scratch.

Both recompute p = exp(s - lse) from the saved logsumexp, then
ds = p * (dp - delta) * scale with delta = rowsum(o * do) precomputed by
the caller (ops.py), shared between the two kernels.

``kv_len`` masks key positions >= kv_len so callers can pad skv up to a
block multiple (ops.py does this for non-multiple-of-block lengths).

Block sizes default to (128, 128) — MXU-aligned (128 lanes) and small
enough that the per-step tiles fit VMEM (see ops.py for the budget).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG_INF = -1e30


def _mask_scores(s, *, causal, kv_len, q_offset, qi, ki, bq, bk):
    """Causal + key-padding masks on a (bq, bk) score block."""
    kpos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    if causal:
        qpos = q_offset + qi * bq + \
            jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        s = jnp.where(qpos >= kpos, s, NEG_INF)
    if kv_len is not None:
        s = jnp.where(kpos < kv_len, s, NEG_INF)
    return s


# ------------------------------- forward -----------------------------------


def _attn_fwd_kernel(q_ref, k_ref, v_ref, o_ref, *rest, causal: bool,
                     bq: int, bk: int, scale: float, nk: int, q_offset: int,
                     kv_len, save_lse: bool):
    if save_lse:
        lse_ref, m_sc, l_sc, acc_sc = rest
    else:
        m_sc, l_sc, acc_sc = rest
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_sc[...] = jnp.full_like(m_sc, NEG_INF)
        l_sc[...] = jnp.zeros_like(l_sc)
        acc_sc[...] = jnp.zeros_like(acc_sc)

    q = q_ref[0, 0].astype(jnp.float32)            # (bq, d)
    k = k_ref[0, 0].astype(jnp.float32)            # (bk, d)
    v = v_ref[0, 0].astype(jnp.float32)            # (bk, d)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    s = _mask_scores(s, causal=causal, kv_len=kv_len, q_offset=q_offset,
                     qi=qi, ki=ki, bq=bq, bk=bk)

    m_prev = m_sc[...]
    l_prev = l_sc[...]
    m_cur = jnp.max(s, axis=1)
    m_new = jnp.maximum(m_prev, m_cur)
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new[:, None])
    l_new = l_prev * alpha + jnp.sum(p, axis=1)
    acc_sc[...] = acc_sc[...] * alpha[:, None] + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_sc[...] = m_new
    l_sc[...] = l_new

    @pl.when(ki == nk - 1)
    def _finish():
        l_safe = jnp.maximum(l_sc[...], 1e-30)
        o_ref[0, 0] = (acc_sc[...] / l_safe[:, None]).astype(o_ref.dtype)
        if save_lse:
            lse_ref[0, 0] = (m_sc[...] + jnp.log(l_safe))[:, None]


def flash_attention_fwd(q, k, v, *, causal=True, bq=128, bk=128,
                        interpret=False, q_offset=None, kv_len=None,
                        save_residuals=False):
    """q (b, h, sq, d); k/v (b, kvh, skv, d) with h % kvh == 0.

    ``q_offset``: absolute position of q[0] among the keys; defaults to
    skv - sq (end-aligned, the decode/prefill-continuation convention).
    ``kv_len``: number of valid keys (< skv masks padded key positions).
    ``save_residuals``: also return the per-row logsumexp (b, h, sq) fp32.
    The kernel writes it as (b, h, sq, 1) so each (bq, 1) block keeps a
    full trailing dim, the tiling the TPU compiler accepts.
    """
    b, h, sq, d = q.shape
    kvh, skv = k.shape[1], k.shape[2]
    assert h % kvh == 0, (h, kvh)
    group = h // kvh
    bq = min(bq, sq)
    bk = min(bk, skv)
    assert sq % bq == 0 and skv % bk == 0
    nq, nk = sq // bq, skv // bk
    scale = d ** -0.5
    if q_offset is None:
        q_offset = skv - sq

    kernel = functools.partial(_attn_fwd_kernel, causal=causal, bq=bq, bk=bk,
                               scale=scale, nk=nk, q_offset=q_offset,
                               kv_len=kv_len, save_lse=save_residuals)
    out_shape = [jax.ShapeDtypeStruct((b, h, sq, d), q.dtype)]
    out_specs = [pl.BlockSpec((1, 1, bq, d),
                              lambda b_, h_, i, j: (b_, h_, i, 0))]
    if save_residuals:
        out_shape.append(jax.ShapeDtypeStruct((b, h, sq, 1), jnp.float32))
        out_specs.append(pl.BlockSpec((1, 1, bq, 1),
                                      lambda b_, h_, i, j: (b_, h_, i, 0)))
    out = pl.pallas_call(
        kernel,
        grid=(b, h, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, bq, d), lambda b_, h_, i, j: (b_, h_, i, 0)),
            pl.BlockSpec((1, 1, bk, d),
                         lambda b_, h_, i, j: (b_, h_ // group, j, 0)),
            pl.BlockSpec((1, 1, bk, d),
                         lambda b_, h_, i, j: (b_, h_ // group, j, 0)),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[
            _vmem((bq,), jnp.float32),
            _vmem((bq,), jnp.float32),
            _vmem((bq, d), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)
    if save_residuals:
        return out[0], out[1][..., 0]
    return out[0]


# ------------------------------ backward: dK/dV -----------------------------


def _attn_bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                         dk_ref, dv_ref, dk_sc, dv_sc, *, causal: bool,
                         bq: int, bk: int, scale: float, nq: int,
                         q_offset: int, kv_len, group: int):
    ji = pl.program_id(2)      # kv block
    qi = pl.program_id(3)      # q block (innermost, sequential)

    @pl.when(qi == 0)
    def _init():
        dk_sc[...] = jnp.zeros_like(dk_sc)
        dv_sc[...] = jnp.zeros_like(dv_sc)

    k = k_ref[0, 0].astype(jnp.float32)            # (bk, d)
    v = v_ref[0, 0].astype(jnp.float32)            # (bk, d)
    # Static loop over the query-head group: dK/dV for this kv head sum
    # contributions from every q head that attends to it (GQA).
    for g in range(group):
        q = q_ref[0, g].astype(jnp.float32)        # (bq, d)
        do = do_ref[0, g].astype(jnp.float32)      # (bq, d)
        lse = lse_ref[0, g]                        # (bq, 1)
        delta = delta_ref[0, g]                    # (bq, 1)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        s = _mask_scores(s, causal=causal, kv_len=kv_len, q_offset=q_offset,
                         qi=qi, ki=ji, bq=bq, bk=bk)
        p = jnp.exp(s - lse)                       # (bq, bk), masked -> 0
        dv_sc[...] += jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)    # p^T @ do  (bk, d)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)    # do @ v^T  (bq, bk)
        ds = p * (dp - delta) * scale
        dk_sc[...] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)    # ds^T @ q  (bk, d)

    @pl.when(qi == nq - 1)
    def _finish():
        dk_ref[0, 0] = dk_sc[...].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_sc[...].astype(dv_ref.dtype)


def flash_attention_bwd_dkv(q, k, v, do, lse, delta, *, causal=True,
                            bq=128, bk=128, q_offset=0, kv_len=None,
                            interpret=False):
    """dK, dV (both (b, kvh, skv, d) fp32) from saved lse + delta.

    ``delta`` = rowsum(o * do), shape (b, h, sq) fp32.
    """
    lse, delta = lse[..., None], delta[..., None]
    b, h, sq, d = q.shape
    kvh, skv = k.shape[1], k.shape[2]
    group = h // kvh
    assert sq % bq == 0 and skv % bk == 0
    nq, nk = sq // bq, skv // bk
    scale = d ** -0.5

    kernel = functools.partial(_attn_bwd_dkv_kernel, causal=causal, bq=bq,
                               bk=bk, scale=scale, nq=nq, q_offset=q_offset,
                               kv_len=kv_len, group=group)
    dk, dv = pl.pallas_call(
        kernel,
        grid=(b, kvh, nk, nq),
        in_specs=[
            pl.BlockSpec((1, group, bq, d),
                         lambda b_, g_, j, i: (b_, g_, i, 0)),
            pl.BlockSpec((1, 1, bk, d), lambda b_, g_, j, i: (b_, g_, j, 0)),
            pl.BlockSpec((1, 1, bk, d), lambda b_, g_, j, i: (b_, g_, j, 0)),
            pl.BlockSpec((1, group, bq, d),
                         lambda b_, g_, j, i: (b_, g_, i, 0)),
            pl.BlockSpec((1, group, bq, 1),
                         lambda b_, g_, j, i: (b_, g_, i, 0)),
            pl.BlockSpec((1, group, bq, 1),
                         lambda b_, g_, j, i: (b_, g_, i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, bk, d), lambda b_, g_, j, i: (b_, g_, j, 0)),
            pl.BlockSpec((1, 1, bk, d), lambda b_, g_, j, i: (b_, g_, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, kvh, skv, d), jnp.float32),
            jax.ShapeDtypeStruct((b, kvh, skv, d), jnp.float32),
        ],
        scratch_shapes=[
            _vmem((bk, d), jnp.float32),
            _vmem((bk, d), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v, do, lse, delta)
    return dk, dv


# ------------------------------- backward: dQ -------------------------------


def _attn_bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                        dq_ref, dq_sc, *, causal: bool, bq: int, bk: int,
                        scale: float, nk: int, q_offset: int, kv_len):
    qi = pl.program_id(2)      # q block
    ki = pl.program_id(3)      # kv block (innermost, sequential)

    @pl.when(ki == 0)
    def _init():
        dq_sc[...] = jnp.zeros_like(dq_sc)

    q = q_ref[0, 0].astype(jnp.float32)            # (bq, d)
    k = k_ref[0, 0].astype(jnp.float32)            # (bk, d)
    v = v_ref[0, 0].astype(jnp.float32)            # (bk, d)
    do = do_ref[0, 0].astype(jnp.float32)          # (bq, d)
    lse = lse_ref[0, 0]                            # (bq, 1)
    delta = delta_ref[0, 0]                        # (bq, 1)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    s = _mask_scores(s, causal=causal, kv_len=kv_len, q_offset=q_offset,
                     qi=qi, ki=ki, bq=bq, bk=bk)
    p = jnp.exp(s - lse)
    dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    ds = p * (dp - delta) * scale
    dq_sc[...] += jax.lax.dot_general(
        ds, k, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)        # ds @ k  (bq, d)

    @pl.when(ki == nk - 1)
    def _finish():
        dq_ref[0, 0] = dq_sc[...].astype(dq_ref.dtype)


def flash_attention_bwd_dq(q, k, v, do, lse, delta, *, causal=True,
                           bq=128, bk=128, q_offset=0, kv_len=None,
                           interpret=False):
    """dQ ((b, h, sq, d) fp32) from saved lse + delta."""
    lse, delta = lse[..., None], delta[..., None]
    b, h, sq, d = q.shape
    kvh, skv = k.shape[1], k.shape[2]
    group = h // kvh
    assert sq % bq == 0 and skv % bk == 0
    nq, nk = sq // bq, skv // bk
    scale = d ** -0.5

    kernel = functools.partial(_attn_bwd_dq_kernel, causal=causal, bq=bq,
                               bk=bk, scale=scale, nk=nk, q_offset=q_offset,
                               kv_len=kv_len)
    return pl.pallas_call(
        kernel,
        grid=(b, h, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, bq, d), lambda b_, h_, i, j: (b_, h_, i, 0)),
            pl.BlockSpec((1, 1, bk, d),
                         lambda b_, h_, i, j: (b_, h_ // group, j, 0)),
            pl.BlockSpec((1, 1, bk, d),
                         lambda b_, h_, i, j: (b_, h_ // group, j, 0)),
            pl.BlockSpec((1, 1, bq, d), lambda b_, h_, i, j: (b_, h_, i, 0)),
            pl.BlockSpec((1, 1, bq, 1), lambda b_, h_, i, j: (b_, h_, i, 0)),
            pl.BlockSpec((1, 1, bq, 1), lambda b_, h_, i, j: (b_, h_, i, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, d),
                               lambda b_, h_, i, j: (b_, h_, i, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, sq, d), jnp.float32),
        scratch_shapes=[_vmem((bq, d), jnp.float32)],
        interpret=interpret,
    )(q, k, v, do, lse, delta)


def _vmem(shape, dtype):
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.VMEM(shape, dtype)
