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
  instead of via a post-hoc jnp sum over broadcast heads.  It works on
  transposed score blocks (keys on rows), so every product is a plain
  or a ``b``-transposed matmul and the per-query stats broadcast down
  the rows.
* ``flash_attention_bwd_dq`` — grid (batch, q_head, q_block, kv_block),
  kv innermost, accumulating dQ for one q block in VMEM scratch.

Both recompute p = exp(s - lse) from the saved logsumexp, then
ds = p * (dp - delta) * scale with delta = rowsum(o * do) precomputed by
the caller (ops.py), shared between the two kernels.

Block sizes are the caller's (ops.py picks them from the shape).  A
block is a multiple of 128 rows or a whole (16-aligned) axis, so a
(1, bq) row of per-query stats is a legal, lane-dense tile.

Causal skip: a (q block, kv block) pair that holds no unmasked pair is
dead.  Its step runs no code, and the index maps clamp the dead steps'
blocks to a live neighbour (the last live kv block for a q block; the
first live q block for a kv block), so Pallas issues no DMA for them.
Only blocks that straddle the diagonal or the ``kv_len`` edge build and
apply the mask.

Stats layout: the forward's running max and sum are (bq, 128) fp32
scratch with the value repeated across the lanes, so they meet a
(bq, bk) score block without a relayout.  ``lse`` and ``delta`` travel
between the kernels as (b, h, sq) fp32 and enter each kernel as a
(1, bq) row: the dK/dV kernel uses the row as is, the dQ kernel turns it
into lane-repeated columns once per q block, and the forward turns its
columns into the row once per q block (both by selecting the diagonal of
a (bq, bq) block, which is exact).

Precision: q·kᵀ and do·vᵀ take their operands as the caller gave them
(bf16 in training) with fp32 accumulation; products with p or ds keep
that operand in fp32 and upcast the other.

``kv_len`` masks key positions >= kv_len so callers can pad skv up to a
block multiple (ops.py does this for non-multiple-of-block lengths).
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
LANES = 128
_NT = (((1,), (1,)), ((), ()))     # a @ b.T
_NN = (((1,), (0,)), ((), ()))     # a @ b
_MiB = 1 << 20
# Mosaic's scoped VMEM limit when a kernel asks for none (v5e).
_SCOPED_VMEM_DEFAULT = 16 * _MiB


# ----------------------------- block geometry ------------------------------


@dataclasses.dataclass(frozen=True)
class Blocks:
    """Which (q block, kv block) pairs hold unmasked pairs.  The methods
    take Python ints or traced grid indices alike."""
    bq: int
    bk: int
    nq: int
    nk: int
    causal: bool
    q_offset: int
    kv_len: int | None = None

    def live(self, qi, ki):
        """Some query of block qi may attend to some key of block ki."""
        live = True
        if self.causal:
            live = ki * self.bk <= self.q_offset + (qi + 1) * self.bq - 1
        if self.kv_len is not None:
            live = live & (ki * self.bk < self.kv_len)
        return live

    def masked(self, qi, ki):
        """Some (query, key) pair of the two blocks is masked."""
        masked = False
        if self.causal:
            masked = (ki + 1) * self.bk - 1 > self.q_offset + qi * self.bq
        if self.kv_len is not None:
            masked = masked | ((ki + 1) * self.bk > self.kv_len)
        return masked

    def kv_block(self, qi, ki):
        """kv block to fetch at step (qi, ki): past the last live one, the
        last live one again (no new DMA)."""
        if not self.causal:
            return ki
        last = (self.q_offset + (qi + 1) * self.bq - 1) // self.bk
        return jnp.minimum(ki, jnp.clip(last, 0, self.nk - 1))

    def q_block(self, ki, qi):
        """q block to fetch at step (ki, qi) of the dK/dV grid: before the
        first live one, the first live one."""
        if not self.causal:
            return qi
        first = (ki * self.bk - self.q_offset) // self.bq
        return jnp.maximum(qi, jnp.clip(first, 0, self.nq - 1))


def _when_live(body, blocks: Blocks, qi, ki):
    """Run ``body(masked)`` on a live block pair: with the mask where the
    pair holds a masked position, without it elsewhere."""
    live, masked = blocks.live(qi, ki), blocks.masked(qi, ki)
    if masked is False:        # neither causal nor padded: all pairs live
        body(False)
        return
    pl.when(live & masked)(lambda: body(True))
    pl.when(live & jnp.logical_not(masked))(lambda: body(False))


def _mask_scores(s, blocks: Blocks, qi, ki, *, kv_rows=False):
    """Causal + key-padding masks on a (bq, bk) score block, or on a
    (bk, bq) one with ``kv_rows``."""
    q_axis, k_axis = (1, 0) if kv_rows else (0, 1)
    kpos = ki * blocks.bk + jax.lax.broadcasted_iota(jnp.int32, s.shape,
                                                     k_axis)
    keep = None
    if blocks.causal:
        qpos = blocks.q_offset + qi * blocks.bq + \
            jax.lax.broadcasted_iota(jnp.int32, s.shape, q_axis)
        keep = qpos >= kpos
    if blocks.kv_len is not None:
        in_len = kpos < blocks.kv_len
        keep = in_len if keep is None else keep & in_len
    return jnp.where(keep, s, NEG_INF)


def vmem_bytes(kind: str, bq: int, bk: int, head_dim: int, group: int,
               itemsize: int) -> int:
    """Upper estimate of one kernel's VMEM: its double-buffered tiles
    (lane- and sublane-padded), its scratch, and fp32 temporaries (four
    (bq, bk) score-sized blocks, two (bq, bq) blocks for the stats'
    row/column turn, fp32 copies of the upcast tiles)."""
    d = -(-head_dim // LANES) * LANES
    sub = 32 // itemsize                       # 8 rows fp32, 16 bf16
    rows = lambda n: -(-n // sub) * sub        # noqa: E731
    q_tile, kv_tile = rows(bq) * d * itemsize, rows(bk) * d * itemsize
    stat_row = 8 * max(bq, LANES) * 4
    f32 = 4
    if kind == "fwd":
        tiles = 2 * q_tile + 2 * kv_tile + stat_row
        scratch = (2 * LANES + d) * bq * f32
    elif kind == "dkv":
        tiles = group * (2 * q_tile + 2 * stat_row) + 2 * kv_tile \
            + 2 * bk * d * f32
        scratch = 2 * bk * d * f32
    else:
        tiles = 2 * q_tile + 2 * kv_tile + 2 * stat_row + bq * d * f32
        scratch = (d + 2 * LANES) * bq * f32
    temps = 4 * bq * bk * f32 + 2 * bq * bq * f32 + 2 * max(bq, bk) * d * f32
    return 2 * tiles + scratch + temps


def _compiler_params(kind, bq, bk, head_dim, group, itemsize):
    """A VMEM limit past Mosaic's default only where the blocks need it."""
    need = vmem_bytes(kind, bq, bk, head_dim, group, itemsize)
    if need <= _SCOPED_VMEM_DEFAULT:
        return None
    return pltpu.CompilerParams(
        vmem_limit_bytes=-(-need * 5 // 4 // _MiB) * _MiB)


# ------------------------------ in-kernel helpers ---------------------------


def _dot(a, b, dims):
    """MXU product with fp32 accumulation; operands of one dtype go in
    as they are, mixed ones both as fp32."""
    if a.dtype != b.dtype:
        a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    return jax.lax.dot_general(a, b, dims,
                               preferred_element_type=jnp.float32)


def _lanes(x, n: int):
    """(r, 128) lane-repeated columns -> (r, n)."""
    if n <= LANES:
        return x[:, :n]
    if n % LANES == 0:
        return pltpu.repeat(x, n // LANES, axis=1)
    return jnp.broadcast_to(x[:, :1], (x.shape[0], n))


def _eye(n: int):
    return (jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
            == jax.lax.broadcasted_iota(jnp.int32, (n, n), 1))


def _cols_to_row(x):
    """(n, 128) lane-repeated columns -> (1, n) row (exact)."""
    n = x.shape[0]
    return jnp.sum(jnp.where(_eye(n), _lanes(x, n), 0.0), axis=0,
                   keepdims=True)


def _row_to_cols(r):
    """(1, n) row -> (n, 128) lane-repeated columns (exact)."""
    n = r.shape[1]
    col = jnp.sum(jnp.where(_eye(n), r, 0.0), axis=1, keepdims=True)
    return jnp.broadcast_to(col, (n, LANES))


# ------------------------------- forward -----------------------------------


def _attn_fwd_kernel(q_ref, k_ref, v_ref, o_ref, *rest, blocks: Blocks,
                     scale: float, save_lse: bool):
    if save_lse:
        lse_ref, m_sc, l_sc, acc_sc = rest
    else:
        m_sc, l_sc, acc_sc = rest
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    d = acc_sc.shape[1]

    @pl.when(ki == 0)
    def _init():
        m_sc[...] = jnp.full_like(m_sc, NEG_INF)
        l_sc[...] = jnp.zeros_like(l_sc)
        acc_sc[...] = jnp.zeros_like(acc_sc)

    def step(masked):
        s = _dot(q_ref[0, 0], k_ref[0, 0], _NT) * scale      # (bq, bk)
        if masked:
            s = _mask_scores(s, blocks, qi, ki)
        m_prev = m_sc[...]                                    # (bq, 128)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - _lanes(m_new, blocks.bk))
        l_sc[...] = l_sc[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        m_sc[...] = m_new
        acc_sc[...] = acc_sc[...] * _lanes(alpha, d) + _dot(
            p, v_ref[0, 0].astype(jnp.float32), _NN)

    _when_live(step, blocks, qi, ki)

    @pl.when(ki == blocks.nk - 1)
    def _finish():
        l_safe = jnp.maximum(l_sc[...], 1e-30)
        o_ref[0, 0] = (acc_sc[...] / _lanes(l_safe, d)).astype(o_ref.dtype)
        if save_lse:
            lse_ref[0, 0] = _cols_to_row(m_sc[...] + jnp.log(l_safe))


def flash_attention_fwd(q, k, v, *, bq, bk, causal=True, interpret=False,
                        q_offset=None, kv_len=None, save_residuals=False):
    """q (b, h, sq, d); k/v (b, kvh, skv, d) with h % kvh == 0.

    ``q_offset``: absolute position of q[0] among the keys; defaults to
    skv - sq (end-aligned, the decode/prefill-continuation convention).
    ``kv_len``: number of valid keys (< skv masks padded key positions).
    ``save_residuals``: also return the per-row logsumexp (b, h, sq) fp32.
    """
    b, h, sq, d = q.shape
    kvh, skv = k.shape[1], k.shape[2]
    assert h % kvh == 0, (h, kvh)
    group = h // kvh
    assert sq % bq == 0 and skv % bk == 0, (sq, bq, skv, bk)
    if q_offset is None:
        q_offset = skv - sq
    blocks = Blocks(bq, bk, sq // bq, skv // bk, causal, q_offset, kv_len)

    kernel = functools.partial(_attn_fwd_kernel, blocks=blocks,
                               scale=d ** -0.5, save_lse=save_residuals)
    q_map = lambda b_, h_, i, j: (b_, h_, i, 0)           # noqa: E731
    out_shape = [jax.ShapeDtypeStruct((b, h, sq, d), q.dtype)]
    out_specs = [pl.BlockSpec((1, 1, bq, d), q_map)]
    if save_residuals:
        out_shape.append(jax.ShapeDtypeStruct((b, h, 1, sq), jnp.float32))
        out_specs.append(pl.BlockSpec((1, 1, 1, bq),
                                      lambda b_, h_, i, j: (b_, h_, 0, i)))
    kv_spec = pl.BlockSpec(
        (1, 1, bk, d),
        lambda b_, h_, i, j: (b_, h_ // group, blocks.kv_block(i, j), 0))
    out = pl.pallas_call(
        kernel,
        grid=(b, h, blocks.nq, blocks.nk),
        in_specs=[pl.BlockSpec((1, 1, bq, d), q_map), kv_spec, kv_spec],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((bq, LANES), jnp.float32),
            pltpu.VMEM((bq, LANES), jnp.float32),
            pltpu.VMEM((bq, d), jnp.float32),
        ],
        compiler_params=_compiler_params("fwd", bq, bk, d, group,
                                         q.dtype.itemsize),
        interpret=interpret,
    )(q, k, v)
    if save_residuals:
        return out[0], out[1].reshape(b, h, sq)
    return out[0]


# ------------------------------ backward: dK/dV -----------------------------


def _attn_bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                         dk_ref, dv_ref, dk_sc, dv_sc, *, blocks: Blocks,
                         scale: float, group: int):
    ji = pl.program_id(2)      # kv block
    qi = pl.program_id(3)      # q block (innermost, sequential)

    @pl.when(qi == 0)
    def _init():
        dk_sc[...] = jnp.zeros_like(dk_sc)
        dv_sc[...] = jnp.zeros_like(dv_sc)

    def step(masked):
        k = k_ref[0, 0]                            # (bk, d)
        v = v_ref[0, 0]
        # Static loop over the query-head group: dK/dV for this kv head
        # sum contributions from every q head that attends to it (GQA).
        # Score blocks are transposed, (bk, bq): keys on rows.
        for g in range(group):
            q = q_ref[0, g]                        # (bq, d)
            do = do_ref[0, g]
            st = _dot(k, q, _NT) * scale
            if masked:
                st = _mask_scores(st, blocks, qi, ji, kv_rows=True)
            pt = jnp.exp(st - lse_ref[0, g])       # lse row (1, bq)
            dv_sc[...] += _dot(pt, do.astype(jnp.float32), _NN)
            dpt = _dot(v, do, _NT)                 # (do @ v^T)^T
            dst = pt * (dpt - delta_ref[0, g]) * scale
            dk_sc[...] += _dot(dst, q.astype(jnp.float32), _NN)

    _when_live(step, blocks, qi, ji)

    @pl.when(qi == blocks.nq - 1)
    def _finish():
        dk_ref[0, 0] = dk_sc[...].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_sc[...].astype(dv_ref.dtype)


def flash_attention_bwd_dkv(q, k, v, do, lse, delta, *, bq, bk, causal=True,
                            q_offset=0, kv_len=None, interpret=False):
    """dK, dV (both (b, kvh, skv, d) fp32) from saved lse + delta.

    ``lse`` and ``delta`` = rowsum(o * do) are (b, h, sq) fp32.
    """
    b, h, sq, d = q.shape
    kvh, skv = k.shape[1], k.shape[2]
    group = h // kvh
    assert sq % bq == 0 and skv % bk == 0, (sq, bq, skv, bk)
    blocks = Blocks(bq, bk, sq // bq, skv // bk, causal, q_offset, kv_len)
    lse, delta = lse.reshape(b, h, 1, sq), delta.reshape(b, h, 1, sq)

    kernel = functools.partial(_attn_bwd_dkv_kernel, blocks=blocks,
                               scale=d ** -0.5, group=group)
    q_spec = pl.BlockSpec(
        (1, group, bq, d),
        lambda b_, g_, j, i: (b_, g_, blocks.q_block(j, i), 0))
    row_spec = pl.BlockSpec(
        (1, group, 1, bq),
        lambda b_, g_, j, i: (b_, g_, 0, blocks.q_block(j, i)))
    kv_spec = pl.BlockSpec((1, 1, bk, d), lambda b_, g_, j, i: (b_, g_, j, 0))
    dk, dv = pl.pallas_call(
        kernel,
        grid=(b, kvh, blocks.nk, blocks.nq),
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, row_spec, row_spec],
        out_specs=[kv_spec, kv_spec],
        out_shape=[
            jax.ShapeDtypeStruct((b, kvh, skv, d), jnp.float32),
            jax.ShapeDtypeStruct((b, kvh, skv, d), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bk, d), jnp.float32),
            pltpu.VMEM((bk, d), jnp.float32),
        ],
        compiler_params=_compiler_params("dkv", bq, bk, d, group,
                                         q.dtype.itemsize),
        interpret=interpret,
    )(q, k, v, do, lse, delta)
    return dk, dv


# ------------------------------- backward: dQ -------------------------------


def _attn_bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                        dq_ref, dq_sc, lse_sc, delta_sc, *, blocks: Blocks,
                        scale: float):
    qi = pl.program_id(2)      # q block
    ki = pl.program_id(3)      # kv block (innermost, sequential)

    @pl.when(ki == 0)
    def _init():
        dq_sc[...] = jnp.zeros_like(dq_sc)
        lse_sc[...] = _row_to_cols(lse_ref[0, 0])
        delta_sc[...] = _row_to_cols(delta_ref[0, 0])

    def step(masked):
        k = k_ref[0, 0]
        s = _dot(q_ref[0, 0], k, _NT) * scale                # (bq, bk)
        if masked:
            s = _mask_scores(s, blocks, qi, ki)
        p = jnp.exp(s - _lanes(lse_sc[...], blocks.bk))
        dp = _dot(do_ref[0, 0], v_ref[0, 0], _NT)
        ds = p * (dp - _lanes(delta_sc[...], blocks.bk)) * scale
        dq_sc[...] += _dot(ds, k.astype(jnp.float32), _NN)  # (bq, d)

    _when_live(step, blocks, qi, ki)

    @pl.when(ki == blocks.nk - 1)
    def _finish():
        dq_ref[0, 0] = dq_sc[...].astype(dq_ref.dtype)


def flash_attention_bwd_dq(q, k, v, do, lse, delta, *, bq, bk, causal=True,
                           q_offset=0, kv_len=None, interpret=False):
    """dQ ((b, h, sq, d) fp32) from saved lse + delta ((b, h, sq) fp32)."""
    b, h, sq, d = q.shape
    kvh, skv = k.shape[1], k.shape[2]
    group = h // kvh
    assert sq % bq == 0 and skv % bk == 0, (sq, bq, skv, bk)
    blocks = Blocks(bq, bk, sq // bq, skv // bk, causal, q_offset, kv_len)
    lse, delta = lse.reshape(b, h, 1, sq), delta.reshape(b, h, 1, sq)

    kernel = functools.partial(_attn_bwd_dq_kernel, blocks=blocks,
                               scale=d ** -0.5)
    q_spec = pl.BlockSpec((1, 1, bq, d), lambda b_, h_, i, j: (b_, h_, i, 0))
    row_spec = pl.BlockSpec((1, 1, 1, bq),
                            lambda b_, h_, i, j: (b_, h_, 0, i))
    kv_spec = pl.BlockSpec(
        (1, 1, bk, d),
        lambda b_, h_, i, j: (b_, h_ // group, blocks.kv_block(i, j), 0))
    return pl.pallas_call(
        kernel,
        grid=(b, h, blocks.nq, blocks.nk),
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, row_spec, row_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, sq, d), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((bq, d), jnp.float32),
            pltpu.VMEM((bq, LANES), jnp.float32),
            pltpu.VMEM((bq, LANES), jnp.float32),
        ],
        compiler_params=_compiler_params("dq", bq, bk, d, group,
                                         q.dtype.itemsize),
        interpret=interpret,
    )(q, k, v, do, lse, delta)
