"""Fused RMSNorm kernels (Pallas TPU): row-tiled, fp32 accumulation in VMEM.

Forward optionally saves the per-row inverse RMS (``rstd``) so the
backward never recomputes the row reduction from HBM.  The backward is
two kernels: ``rmsnorm_bwd_dx`` (row-tiled, one fused pass producing dx
from x/w/dy/rstd) and ``rmsnorm_bwd_dw`` (the same tiling emitting one
partial dw per row block; the final (n_blocks, d) -> (d,) reduction is a
single jnp sum — the "two-pass" dw reduction).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _rmsnorm_kernel(x_ref, w_ref, o_ref, *, eps: float):
    x = x_ref[...].astype(jnp.float32)              # (bn, d)
    w = w_ref[...].astype(jnp.float32)              # (d,)
    ms = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    o_ref[...] = (x * jax.lax.rsqrt(ms + eps) * w).astype(o_ref.dtype)


def _rmsnorm_res_kernel(x_ref, w_ref, o_ref, r_ref, *, eps: float):
    x = x_ref[...].astype(jnp.float32)              # (bn, d)
    w = w_ref[...].astype(jnp.float32)              # (d,)
    ms = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    r = jax.lax.rsqrt(ms + eps)                     # (bn, 1)
    o_ref[...] = (x * r * w).astype(o_ref.dtype)
    r_ref[...] = r


def rmsnorm_fwd(x, w, *, eps=1e-6, block_rows=256, interpret=False,
                save_residuals=False):
    """x (n, d); w (d,). Returns rmsnorm(x) * w [, rstd (n, 1) fp32]."""
    n, d = x.shape
    bn = min(block_rows, n)
    assert n % bn == 0, (n, bn)
    out_specs = pl.BlockSpec((bn, d), lambda i: (i, 0))
    out_shape = jax.ShapeDtypeStruct((n, d), x.dtype)
    if save_residuals:
        kernel = functools.partial(_rmsnorm_res_kernel, eps=eps)
        out_specs = [out_specs, pl.BlockSpec((bn, 1), lambda i: (i, 0))]
        out_shape = [out_shape,
                     jax.ShapeDtypeStruct((n, 1), jnp.float32)]
    else:
        kernel = functools.partial(_rmsnorm_kernel, eps=eps)
    return pl.pallas_call(
        kernel,
        grid=(n // bn,),
        in_specs=[
            pl.BlockSpec((bn, d), lambda i: (i, 0)),
            pl.BlockSpec((d,), lambda i: (0,)),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret,
    )(x, w)


def _rmsnorm_bwd_dx_kernel(x_ref, w_ref, dy_ref, r_ref, dx_ref):
    x = x_ref[...].astype(jnp.float32)              # (bn, d)
    w = w_ref[...].astype(jnp.float32)              # (d,)
    dy = dy_ref[...].astype(jnp.float32)            # (bn, d)
    r = r_ref[...]                                  # (bn, 1) fp32
    d = x.shape[-1]
    g = dy * w
    dot = jnp.sum(g * x, axis=-1, keepdims=True)    # (bn, 1)
    dx = r * g - x * (r * r * r) * (dot / d)
    dx_ref[...] = dx.astype(dx_ref.dtype)


def rmsnorm_bwd_dx(x, w, dy, rstd, *, block_rows=256, interpret=False):
    """dL/dx for y = x * rstd * w. Shapes: x/dy (n, d); rstd (n, 1)."""
    n, d = x.shape
    bn = min(block_rows, n)
    assert n % bn == 0, (n, bn)
    return pl.pallas_call(
        _rmsnorm_bwd_dx_kernel,
        grid=(n // bn,),
        in_specs=[
            pl.BlockSpec((bn, d), lambda i: (i, 0)),
            pl.BlockSpec((d,), lambda i: (0,)),
            pl.BlockSpec((bn, d), lambda i: (i, 0)),
            pl.BlockSpec((bn, 1), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((bn, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, d), x.dtype),
        interpret=interpret,
    )(x, w, dy, rstd)


def _rmsnorm_bwd_dw_kernel(x_ref, dy_ref, r_ref, dwp_ref):
    x = x_ref[...].astype(jnp.float32)              # (bn, d)
    dy = dy_ref[...].astype(jnp.float32)            # (bn, d)
    r = r_ref[...]                                  # (bn, 1)
    dwp_ref[0] = jnp.sum(dy * x * r, axis=0, keepdims=True)


def rmsnorm_bwd_dw(x, dy, rstd, *, block_rows=256, interpret=False):
    """Pass 1: per-row-block partial dw (n_blocks, 1, d) fp32; pass 2
    (jnp): sum over blocks.  Each partial is a (1, d) block whose trailing
    dims are the array's full trailing dims, as the TPU compiler wants."""
    n, d = x.shape
    bn = min(block_rows, n)
    assert n % bn == 0, (n, bn)
    partial = pl.pallas_call(
        _rmsnorm_bwd_dw_kernel,
        grid=(n // bn,),
        in_specs=[
            pl.BlockSpec((bn, d), lambda i: (i, 0)),
            pl.BlockSpec((bn, d), lambda i: (i, 0)),
            pl.BlockSpec((bn, 1), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, d), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((n // bn, 1, d), jnp.float32),
        interpret=interpret,
    )(x, dy, rstd)
    return jnp.sum(partial, axis=(0, 1))
