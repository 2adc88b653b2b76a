"""Paged chunk-attention kernel (Pallas TPU).

One-pass online-softmax attention of a **chunk of T >= 1 query tokens**
per sequence against a block-paged KV pool — the superset of the old
flash-decode kernel (T = 1) that also covers prefill chunks and
speculative verify windows.  The grid walks (seq, kv_block)
with the kv_block axis innermost and sequential, so the (m, l, acc)
running stats live in VMEM scratch across a sequence's blocks.  Each
step moves one physical block with all of its kv heads.

The block-table gather costs nothing extra in HBM traffic: the table
and per-sequence max query positions ride in as scalar-prefetch
operands (``pltpu.PrefetchScalarGridSpec``), so the K/V BlockSpec
index_maps resolve ``block_tables[seq, j]`` *before* the kernel body
runs and the pipeline DMAs exactly the physical block the sequence
owns.  Logical position of entry ``o`` of table slot ``j`` is
``j * block_size + o`` regardless of the physical block id, so
fragmented allocations attend in the right order for free.

Query rows are the chunk x GQA-group product: ops.py flattens
(T, group) to a single row axis R (row ``t * group + g`` is query head
``kv * group + g`` of chunk token ``t``), padded up to the fp32 sublane
count so tiles stay aligned; the whole row block for one kv head shares
each gathered K/V block, so grouped K/V are never broadcast to full
head count in HBM.

Masking is **per-row absolute-position causal** (the PR 5 SeqState
contract): row ``r`` attends key positions ``<= qpos[r]``, and rows
with ``qpos < 0`` (chunk padding) have no valid keys.  Probabilities
are zeroed through the mask *after* the exp (not only the logits), so
an all-masked row accumulates nothing, its normalizer ``l`` stays 0,
and the guarded final divide emits exact **zeros** — never NaN — for
padding rows.  Blocks whose first position already exceeds the
sequence's max query position are skipped entirely (``pl.when`` on the
scalar-prefetched ``maxpos``); table entries past a sequence's live
blocks must still point at a valid (e.g. scratch) physical block.

Quantized KV: the pools may be float8_e4m3 or int8 with one absmax
scale per cached token riding beside them ((n_blocks, bs, 1) fp32);
the kernel dequantizes each gathered block on-chip (`k * k_scale`)
right after the load, so HBM sees only the narrow bytes.  bf16 pools
pass unit scales through the same signature — multiplying by 1.0 is
exact, and one signature means one compiled kernel family.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _chunk_kernel(bt_ref, maxpos_ref, q_ref, qpos_ref, k_ref, v_ref,
                  ks_ref, vs_ref, o_ref, m_sc, l_sc, acc_sc,
                  *, bs: int, scale: float, nb: int, kvh: int):
    si = pl.program_id(0)          # sequence (batch slot)
    ji = pl.program_id(1)          # kv block (innermost, sequential)

    @pl.when(ji == 0)
    def _init():
        m_sc[...] = jnp.full_like(m_sc, NEG_INF)
        l_sc[...] = jnp.zeros_like(l_sc)
        acc_sc[...] = jnp.zeros_like(acc_sc)

    # skip blocks entirely past the chunk's last query position: decode
    # (T=1) touches exactly ceil(len/bs) blocks of the padded table
    @pl.when(ji * bs <= maxpos_ref[si])
    def _compute():
        qpos = qpos_ref[0]                                     # (R, 1)
        R = qpos.shape[0]
        kpos = ji * bs + jax.lax.broadcasted_iota(jnp.int32, (R, bs), 1)
        valid = (kpos <= qpos) & (qpos >= 0)
        ks = ks_ref[0]                                         # (bs, 1)
        vs = vs_ref[0]
        # the whole (bs, kvh, d) block is one DMA; each kv head reads its
        # (bs, d) slice of it on-chip
        for h in range(kvh):
            q = q_ref[0, h].astype(jnp.float32)                # (R, d)
            k = k_ref[0, :, h, :].astype(jnp.float32) * ks     # (bs, d)
            v = v_ref[0, :, h, :].astype(jnp.float32) * vs

            s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            s = jnp.where(valid, s * scale, NEG_INF)

            m_prev = m_sc[h]                                   # (R, 1)
            l_prev = l_sc[h]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            # mask the probabilities, not just the logits: an all-masked
            # row has m_new == NEG_INF and exp(NEG_INF - NEG_INF) == 1,
            # which would silently accumulate mass; zeroing through
            # `valid` keeps l == 0 so _finish emits exact zeros for
            # padding rows
            p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
            l_sc[h] = l_prev * alpha + jnp.sum(p, axis=1, keepdims=True)
            acc_sc[h] = acc_sc[h] * alpha + jax.lax.dot_general(
                p, v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            m_sc[h] = m_new

    @pl.when(ji == nb - 1)
    def _finish():
        l_safe = jnp.maximum(l_sc[...], 1e-30)
        o_ref[0] = (acc_sc[...] / l_safe).astype(o_ref.dtype)


def paged_chunk_attention_kernel(q, qpos, k_pool, v_pool, k_scale, v_scale,
                                 block_tables, maxpos, *, interpret=False):
    """q (b, kvh, R, d); qpos (b, R, 1) int32; k/v_pool
    (n_blocks, bs, kvh, d); k/v_scale (n_blocks, bs, 1) float32;
    block_tables (b, nbmax) int32; maxpos (b,) int32 -> (b, kvh, R, d).

    ``R`` is the flattened (chunk, padded-GQA-group) row axis — see
    ops.py for the packing.  ``maxpos[s]`` is the max of sequence s's
    query positions (negative when the whole chunk is padding: every
    block is skipped and the output rows are zeros).

    Each grid step takes one physical block with all ``kvh`` heads: a
    (bs, kvh, d) block keeps the pool's full trailing dims, which is the
    tiling the TPU compiler accepts for any kvh (a one-head block would
    put a size-1 dim second-minor).
    """
    b, kvh, R, d = q.shape
    bs = k_pool.shape[1]
    nbmax = block_tables.shape[1]
    scale = d ** -0.5

    kernel = functools.partial(_chunk_kernel, bs=bs, scale=scale, nb=nbmax,
                               kvh=kvh)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, nbmax),
        in_specs=[
            pl.BlockSpec((1, kvh, R, d),
                         lambda s_, j, bt, mp: (s_, 0, 0, 0)),
            pl.BlockSpec((1, R, 1),
                         lambda s_, j, bt, mp: (s_, 0, 0)),
            pl.BlockSpec((1, bs, kvh, d),
                         lambda s_, j, bt, mp: (bt[s_, j], 0, 0, 0)),
            pl.BlockSpec((1, bs, kvh, d),
                         lambda s_, j, bt, mp: (bt[s_, j], 0, 0, 0)),
            pl.BlockSpec((1, bs, 1),
                         lambda s_, j, bt, mp: (bt[s_, j], 0, 0)),
            pl.BlockSpec((1, bs, 1),
                         lambda s_, j, bt, mp: (bt[s_, j], 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, kvh, R, d),
                               lambda s_, j, bt, mp: (s_, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((kvh, R, 1), jnp.float32),
            pltpu.VMEM((kvh, R, 1), jnp.float32),
            pltpu.VMEM((kvh, R, d), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, kvh, R, d), q.dtype),
        interpret=interpret,
    )(block_tables, maxpos, q, qpos, k_pool, v_pool, k_scale, v_scale)
