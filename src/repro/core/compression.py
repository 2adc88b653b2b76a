"""Gradient compression for the weak link (HFReduce phase 2 payload).

The paper's HFReduce reduces on CPU in FP32/FP16/BF16/FP8 (§IV-D1) — the
dtype of the wire format is a first-class knob.  Here:

  * ``bf16_psum``: cast -> psum -> cast (2x fewer cross-pod bytes vs fp32).
  * ``fp8_psum``: float8_e4m3 wire format (4x fewer bytes); payloads travel
    as e4m3 bitcast to uint8, ranks dequantize + sum in fp32 locally, so no
    collective ever adds in fp8.  e4m3 saturates at +-448 — callers must
    pre-scale means into the sum (``hfreduce(prescale=...)``) rather than
    dividing after decompression.
  * ``int8_psum``: blockwise-absmax int8 quantization; the allreduce is a
    quantize -> all_to_all -> local dequant-sum -> quantize -> all_gather
    schedule so payloads stay int8 on the wire (4x fewer bytes).
  * error feedback (``ef_compress``): the residual of the quantizer is
    carried by the caller (optimizer state) and re-added next step, keeping
    SGD convergence (1-bit Adam / EF-SGD lineage).

``quantize_blockwise``/``dequantize_blockwise`` are the jnp oracles for the
Pallas ``kernels/quant_comm`` kernel.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax


BLOCK = 256


def quantize_blockwise(x, block=BLOCK):
    """x (n,) fp -> (q int8 (n,), scales fp32 (n/block,)). n % block == 0."""
    n = x.shape[0]
    assert n % block == 0, (n, block)
    xb = x.reshape(n // block, block).astype(jnp.float32)
    absmax = jnp.max(jnp.abs(xb), axis=1, keepdims=True)
    scale = absmax / 127.0
    q = jnp.clip(jnp.round(xb / jnp.maximum(scale, 1e-12)), -127, 127)
    return q.astype(jnp.int8).reshape(n), scale[:, 0]


def dequantize_blockwise(q, scales, block=BLOCK):
    n = q.shape[0]
    xb = q.reshape(n // block, block).astype(jnp.float32)
    return (xb * scales[:, None]).reshape(n)


def bf16_psum(x, axis_name):
    """Cross-pod allreduce with a bf16 wire format."""
    return lax.psum(x.astype(jnp.bfloat16), axis_name).astype(x.dtype)


def fp8_psum(x, axis_name):
    """Cross-pod allreduce with a float8_e4m3 wire format.

    Schedule (P = axis size): split x into P chunks; cast to e4m3;
    all_to_all the raw bytes (bitcast to uint8 — f8 collectives are not
    supported on every backend); dequantize + sum in fp32 locally;
    requantize; all_gather; dequantize.  Wire bytes per rank: 2 * |x| / 4.
    """
    P = lax.axis_size(axis_name)
    if P == 1:
        return x
    shape, dtype = x.shape, x.dtype
    flat = x.reshape(-1).astype(jnp.float32)
    pad = (-flat.shape[0]) % P
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros((pad,), jnp.float32)])
    n = flat.shape[0]
    q = lax.bitcast_convert_type(flat.astype(jnp.float8_e4m3fn), jnp.uint8)
    qc = q.reshape(P, n // P)
    qr = lax.all_to_all(qc, axis_name, split_axis=0, concat_axis=0,
                        tiled=False)
    deq = lax.bitcast_convert_type(qr, jnp.float8_e4m3fn).astype(jnp.float32)
    red = jnp.sum(deq, axis=0)
    q2 = lax.bitcast_convert_type(red.astype(jnp.float8_e4m3fn), jnp.uint8)
    qg = lax.all_gather(q2, axis_name, axis=0, tiled=True)
    out = lax.bitcast_convert_type(qg, jnp.float8_e4m3fn).astype(jnp.float32)
    if pad:
        out = out[:-pad]
    return out.reshape(shape).astype(dtype)


def int8_psum(x, axis_name, block=BLOCK):
    """Cross-pod allreduce with an int8 wire format.

    Schedule (P = axis size): split x into P chunks; quantize; all_to_all so
    rank i holds every rank's chunk i; dequant + sum locally; requantize;
    all_gather the reduced chunks.  Wire bytes per rank: 2 * |x| / 4 (int8)
    + scales — vs 2 * |x| fp32 for a flat psum.
    """
    P = lax.axis_size(axis_name)
    if P == 1:
        return x
    shape, dtype = x.shape, x.dtype
    flat = x.reshape(-1).astype(jnp.float32)
    pad = (-flat.shape[0]) % (P * block)
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros((pad,), jnp.float32)])
    n = flat.shape[0]
    q, s = quantize_blockwise(flat, block)
    qc = q.reshape(P, n // P)
    sc = s.reshape(P, n // P // block)
    # all_to_all: rank i receives chunk i from every rank
    qr = lax.all_to_all(qc, axis_name, split_axis=0, concat_axis=0,
                        tiled=False)
    sr = lax.all_to_all(sc, axis_name, split_axis=0, concat_axis=0,
                        tiled=False)
    # local dequant + reduce over ranks
    deq = jax.vmap(lambda qq, ss: dequantize_blockwise(qq, ss, block))(qr, sr)
    red = jnp.sum(deq, axis=0)
    q2, s2 = quantize_blockwise(red, block)
    qg = lax.all_gather(q2, axis_name, axis=0, tiled=True)
    sg = lax.all_gather(s2, axis_name, axis=0, tiled=True)
    out = dequantize_blockwise(qg, sg, block)
    if pad:
        out = out[:-pad]
    return out.reshape(shape).astype(dtype)


def make_weak_psum(kind: str):
    if kind in ("", "fp32", None):
        return None
    if kind == "bf16":
        return bf16_psum
    if kind == "fp8":
        return fp8_psum
    if kind == "int8":
        return int8_psum
    raise ValueError(kind)


# --------------------------- error feedback --------------------------------


def ef_compress(x, residual, compress_fn):
    """Error feedback: y = compress(x + residual); residual' = x+r - y."""
    target = x + residual
    y = compress_fn(target)
    return y, target - y


def int8_roundtrip(x, block=BLOCK):
    """Quantize+dequantize (the lossy part of int8_psum) for EF residuals."""
    shape = x.shape
    flat = x.reshape(-1).astype(jnp.float32)
    pad = (-flat.shape[0]) % block
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros((pad,), jnp.float32)])
    q, s = quantize_blockwise(flat, block)
    out = dequantize_blockwise(q, s, block)
    if pad:
        out = out[:-pad]
    return out.reshape(shape).astype(x.dtype)
