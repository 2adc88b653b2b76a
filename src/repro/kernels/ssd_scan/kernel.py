"""Mamba-2 SSD chunk-scan kernels (Pallas TPU).

The hardware-adaptation showcase (DESIGN.md §6): the selective-state
recurrence is reformulated as chunked matmuls (MXU work) with the carried
state held in VMEM scratch across the sequential chunk axis of the grid —
HBM sees each chunk exactly once.

Both grids are (batch, n_chunks, head) and run sequentially over chunks
and heads.  The wrappers lay x/dy/y/dx out head-major ((b, h, l, p)) and
the within-chunk cumulative log-decays as per-head columns and rows, so
every in-kernel operand is a 2-D tile whose trailing dims the TPU
compiler accepts, and every contraction is a plain 2-D matmul.  The
cumulative sums themselves (forward, and the reverse one that turns
d(cum) into d(a)) run in jnp around the kernels: Pallas TPU does not
lower ``jnp.cumsum``.  Each head's carried state (p, n)
lives in a (h, p, n) VMEM scratch indexed by the head grid index; the
(c, c) ``C @ B^T`` scores are computed once per chunk (at head 0) and
reused by the chunk's other heads.  Per-step working set at (c=256,
p=64, n<=128): a handful of (c, c) fp32 tiles, ~2 MB.

The vjp-fwd variant additionally saves each chunk's *incoming* carried
state (b, nc, h, p, n) — O(l/chunk) memory instead of the O(l*chunk)
decay matrices jnp autodiff of the chunked ref would stash.  The backward
(``ssd_scan_bwd``) walks the chunk axis in reverse (index maps flip the
grid), carries dh_state in VMEM, and rebuilds each chunk's decay matrix
on-chip, so dx/da/dB/dC cost one more pass over the same HBM traffic as
the forward.  dB/dC sum over heads: they accumulate in VMEM across the
innermost head axis and are written once per chunk.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _mm(a, b, contract=((1,), (0,)), precision=None):
    return jax.lax.dot_general(a, b, (contract, ((), ())),
                               precision=precision,
                               preferred_element_type=jnp.float32)


def _chunk_decays(ccol_ref, crow_ref, n: int):
    """The chunk's cumulative log-decays as a column (c, 1), the (c, c)
    causal decay matrix L[l, s] = exp(cum[l] - cum[s]) for l >= s else 0,
    and the chunk total cum[-1] repeated along a (1, n) row.  The row is
    an exact one-hot matmul: the compiler cannot splat a (1, 1) value
    over a (p, n) tile."""
    cum = ccol_ref[0, 0]                            # (c, 1) fp32
    cum_row = crow_ref[0, 0]                        # (1, c)
    c_len = cum.shape[0]
    ii = jax.lax.broadcasted_iota(jnp.int32, (c_len, c_len), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (c_len, c_len), 1)
    L = jnp.where(ii >= jj, jnp.exp(cum - cum_row), 0.0)
    last = jax.lax.broadcasted_iota(jnp.int32, (c_len, n), 0) == c_len - 1
    total = _mm(cum_row, last.astype(jnp.float32),   # (1, n)
                precision=jax.lax.Precision.HIGHEST)
    return cum, L, total


def _ssd_kernel(x_ref, ccol_ref, crow_ref, b_ref, c_ref, y_ref, hfin_ref,
                *rest, nc: int, save_residuals: bool):
    if save_residuals:
        hprev_ref, h_sc, sc_sc = rest
    else:
        h_sc, sc_sc = rest
    ci = pl.program_id(1)
    hi = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        h_sc[hi] = jnp.zeros(h_sc.shape[1:], jnp.float32)

    B = b_ref[0].astype(jnp.float32)                # (c, n)
    C = c_ref[0].astype(jnp.float32)                # (c, n)

    @pl.when(hi == 0)
    def _scores():
        sc_sc[...] = _mm(C, B, ((1,), (1,)))        # (l, s)

    x = x_ref[0, 0].astype(jnp.float32)             # (c, p)
    cum, L, total = _chunk_decays(ccol_ref, crow_ref, B.shape[1])
    cum_last = cum[-1:, :]                          # (1, 1)
    hprev = h_sc[hi]                                # (p, n)
    if save_residuals:
        hprev_ref[0, 0, 0] = hprev

    y_diag = _mm(sc_sc[...] * L, x)                 # (c, p)
    y_off = _mm(C, hprev, ((1,), (1,))) * jnp.exp(cum)
    decay_end = jnp.exp(cum_last - cum)             # (c, 1)
    h_new = _mm(x * decay_end, B, ((0,), (0,)))     # (p, n)
    h_sc[hi] = h_new + hprev * jnp.exp(total)

    y_ref[0, 0] = (y_diag + y_off).astype(y_ref.dtype)

    @pl.when(ci == nc - 1)
    def _fin():
        hfin_ref[0, 0] = h_sc[hi]


def _head_major(x, a, c: int):
    """(b, l, h, p) -> (b, h, l, p); log-decays (b, l, h) -> their
    inclusive cumsum within each chunk of ``c`` steps, as per-head columns
    (b, h, l, 1) and rows (b, h, 1, l)."""
    b, l, h = a.shape
    cum = jnp.cumsum(a.astype(jnp.float32).reshape(b, l // c, c, h), axis=2)
    cum = jnp.transpose(cum.reshape(b, l, h), (0, 2, 1))
    return (jnp.transpose(x, (0, 2, 1, 3)), cum[..., None],
            cum[:, :, None, :])


def ssd_scan_fwd(x, a, B, C, *, chunk=256, interpret=False,
                 save_residuals=False):
    """x (b,l,h,p); a (b,l,h) log-decay; B/C (b,l,n).

    Returns (y (b,l,h,p), h_final (b,h,p,n))
    [, h_prev (b,nc,h,p,n) fp32 incoming state per chunk]."""
    b, l, h, p = x.shape
    n = B.shape[-1]
    c = min(chunk, l)
    assert l % c == 0
    nc = l // c
    xt, ccol, crow = _head_major(x, a, c)
    in_specs = [
        pl.BlockSpec((1, 1, c, p), lambda bi, ci, hi: (bi, hi, ci, 0)),
        pl.BlockSpec((1, 1, c, 1), lambda bi, ci, hi: (bi, hi, ci, 0)),
        pl.BlockSpec((1, 1, 1, c), lambda bi, ci, hi: (bi, hi, 0, ci)),
        pl.BlockSpec((1, c, n), lambda bi, ci, hi: (bi, ci, 0)),
        pl.BlockSpec((1, c, n), lambda bi, ci, hi: (bi, ci, 0)),
    ]
    out_specs = [
        pl.BlockSpec((1, 1, c, p), lambda bi, ci, hi: (bi, hi, ci, 0)),
        pl.BlockSpec((1, 1, p, n), lambda bi, ci, hi: (bi, hi, 0, 0)),
    ]
    out_shape = [
        jax.ShapeDtypeStruct((b, h, l, p), x.dtype),
        jax.ShapeDtypeStruct((b, h, p, n), jnp.float32),
    ]
    if save_residuals:
        out_specs.append(pl.BlockSpec(
            (1, 1, 1, p, n), lambda bi, ci, hi: (bi, ci, hi, 0, 0)))
        out_shape.append(
            jax.ShapeDtypeStruct((b, nc, h, p, n), jnp.float32))
    out = pl.pallas_call(
        functools.partial(_ssd_kernel, nc=nc,
                          save_residuals=save_residuals),
        grid=(b, nc, h),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((h, p, n), jnp.float32),
                        pltpu.VMEM((c, c), jnp.float32)],
        interpret=interpret,
    )(xt, ccol, crow, B, C)
    return (jnp.transpose(out[0], (0, 2, 1, 3)),) + tuple(out[1:])


def _ssd_bwd_kernel(x_ref, ccol_ref, crow_ref, b_ref, c_ref, hprev_ref,
                    dy_ref, dhfin_ref, dx_ref, dcum_ref, db_ref, dc_ref,
                    dh_sc, sc_sc, db_sc, dc_sc, *, n_heads: int):
    """One reverse-recurrence step: grads for chunk ``nc - 1 - ci``, head
    ``hi``.

    ``dh_sc[hi]`` carries dL/d(state entering the *next* chunk); at
    ci == 0 (the last chunk) that is the caller's dL/d(h_final) cotangent.
    """
    ci = pl.program_id(1)
    hi = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        dh_sc[hi] = dhfin_ref[0, 0]

    B = b_ref[0].astype(jnp.float32)                # (c, n)
    C = c_ref[0].astype(jnp.float32)                # (c, n)

    @pl.when(hi == 0)
    def _scores():
        sc_sc[...] = _mm(C, B, ((1,), (1,)))        # (l, s)
        db_sc[...] = jnp.zeros_like(db_sc)
        dc_sc[...] = jnp.zeros_like(dc_sc)

    x = x_ref[0, 0].astype(jnp.float32)             # (c, p)
    hin = hprev_ref[0, 0, 0]                        # (p, n) fp32
    dy = dy_ref[0, 0].astype(jnp.float32)           # (c, p)
    dhout = dh_sc[hi]                               # (p, n)
    cum, L, total = _chunk_decays(ccol_ref, crow_ref, B.shape[1])
    c_len = x.shape[0]
    ecum = jnp.exp(cum)                             # (c, 1)
    ecum_last = ecum[-1:, :]                        # (1, 1)
    scores = sc_sc[...]
    ones = jnp.ones((c_len, 1), jnp.float32)

    # ---- intra-chunk (diag) term:  y_diag = (scores * L) @ x ----
    G = _mm(dy, x, ((1,), (1,)))                    # (l, s)
    LG = L * G
    dx = _mm(scores * L, dy, ((0,), (0,)))          # (s, p)
    dC = _mm(LG, B)                                 # (l, n)
    dB = _mm(LG, C, ((0,), (0,)))                   # (s, n)
    SLG = scores * LG
    dseg_sum_s = jnp.sum(SLG, axis=1, keepdims=True)        # (l, 1)
    dseg_sum_l = _mm(SLG, ones, ((0,), (0,)))               # (s, 1)

    # ---- inter-chunk term:  y_off = (C @ h_in^T) * exp(cum) ----
    hC = _mm(dy, hin)                               # (l, n)
    dC = dC + hC * ecum
    dhin = _mm(dy * ecum, C, ((0,), (0,)))          # (p, n)
    dcum = (dseg_sum_s - dseg_sum_l
            + ecum * jnp.sum(hC * C, axis=1, keepdims=True))

    # ---- state carry:  h_out = (x * de)^T @ B + h_in * exp(cum_c) ----
    de = jnp.exp(cum[-1:, :] - cum)                 # (s, 1)
    Bdh = _mm(B, dhout, ((1,), (1,)))               # (s, p)
    dx = dx + de * Bdh
    dB = dB + de * _mm(x, dhout)                    # (s, n)
    dde = jnp.sum(x * Bdh, axis=1, keepdims=True)   # (s, 1)
    dhin = dhin + dhout * jnp.exp(total)
    dcum = dcum - de * dde
    dcum_last = (jnp.sum(de * dde, axis=0, keepdims=True)
                 + ecum_last * jnp.sum(hin * dhout, keepdims=True))
    row = jax.lax.broadcasted_iota(jnp.int32, (c_len, 1), 0)
    dcum = dcum + jnp.where(row == c_len - 1, dcum_last, 0.0)

    dx_ref[0, 0] = dx.astype(dx_ref.dtype)
    dcum_ref[0, 0] = dcum
    db_sc[...] += dB
    dc_sc[...] += dC
    dh_sc[hi] = dhin

    @pl.when(hi == n_heads - 1)
    def _write_bc():
        db_ref[0] = db_sc[...].astype(db_ref.dtype)
        dc_ref[0] = dc_sc[...].astype(dc_ref.dtype)


def ssd_scan_bwd(x, a, B, C, hprev, dy, dhfin, *, chunk=256,
                 interpret=False):
    """Fused backward: reverse chunked recurrence.

    hprev (b,nc,h,p,n): per-chunk incoming states saved by the forward.
    dy (b,l,h,p); dhfin (b,h,p,n) cotangent of h_final.
    Returns (dx, da, dB, dC) matching the primal dtypes."""
    b, l, h, p = x.shape
    n = B.shape[-1]
    c = min(chunk, l)
    assert l % c == 0
    nc = l // c
    assert hprev.shape == (b, nc, h, p, n), (hprev.shape, (b, nc, h, p, n))
    xt, ccol, crow = _head_major(x, a, c)
    dyt = jnp.transpose(dy, (0, 2, 1, 3))

    def rev(ci):
        return nc - 1 - ci

    dx, dcum, dB, dC = pl.pallas_call(
        functools.partial(_ssd_bwd_kernel, n_heads=h),
        grid=(b, nc, h),
        in_specs=[
            pl.BlockSpec((1, 1, c, p),
                         lambda bi, ci, hi: (bi, hi, rev(ci), 0)),
            pl.BlockSpec((1, 1, c, 1),
                         lambda bi, ci, hi: (bi, hi, rev(ci), 0)),
            pl.BlockSpec((1, 1, 1, c),
                         lambda bi, ci, hi: (bi, hi, 0, rev(ci))),
            pl.BlockSpec((1, c, n), lambda bi, ci, hi: (bi, rev(ci), 0)),
            pl.BlockSpec((1, c, n), lambda bi, ci, hi: (bi, rev(ci), 0)),
            pl.BlockSpec((1, 1, 1, p, n),
                         lambda bi, ci, hi: (bi, rev(ci), hi, 0, 0)),
            pl.BlockSpec((1, 1, c, p),
                         lambda bi, ci, hi: (bi, hi, rev(ci), 0)),
            pl.BlockSpec((1, 1, p, n), lambda bi, ci, hi: (bi, hi, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, c, p),
                         lambda bi, ci, hi: (bi, hi, rev(ci), 0)),
            pl.BlockSpec((1, 1, c, 1),
                         lambda bi, ci, hi: (bi, hi, rev(ci), 0)),
            pl.BlockSpec((1, c, n), lambda bi, ci, hi: (bi, rev(ci), 0)),
            pl.BlockSpec((1, c, n), lambda bi, ci, hi: (bi, rev(ci), 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, l, p), x.dtype),
            jax.ShapeDtypeStruct((b, h, l, 1), jnp.float32),
            jax.ShapeDtypeStruct((b, l, n), B.dtype),
            jax.ShapeDtypeStruct((b, l, n), C.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((h, p, n), jnp.float32),
                        pltpu.VMEM((c, c), jnp.float32),
                        pltpu.VMEM((c, n), jnp.float32),
                        pltpu.VMEM((c, n), jnp.float32)],
        interpret=interpret,
    )(xt, ccol, crow, B, C, hprev, dyt, dhfin)
    # da[t] = sum_{u>=t} dcum[u] within each chunk (reverse cumsum,
    # flip-free)
    dcum = dcum.reshape(b, h, nc, c)
    s_ = jnp.cumsum(dcum, axis=-1)
    da = (s_[..., -1:] - s_ + dcum).reshape(b, h, l)
    return (jnp.transpose(dx, (0, 2, 1, 3)),
            jnp.transpose(da, (0, 2, 1)).astype(a.dtype), dB, dC)
