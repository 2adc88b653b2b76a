"""Shared model building blocks: norms, activations, RoPE, losses."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.params import p


# ----------------------------- norms ------------------------------------


def norm_defs(cfg, name="norm"):
    d = {f"{name}_scale": p((cfg.d_model,), ("embed",), init="ones")}
    if cfg.norm == "layernorm":
        d[f"{name}_bias"] = p((cfg.d_model,), ("embed",), init="zeros")
    return d


def norm_kernel_impl(cfg, x):
    """Resolve ``cfg.norm_impl`` for an rmsnorm call site.

    Returns "kernel"/"interpret" to route through the fused Pallas
    custom_vjp op (``kernels.rmsnorm``), or None for the inline jnp path.
    "auto" only picks the kernel for multi-token streams: one-token decode
    would pay a pallas_call per layer per token for a trivial reduction.
    """
    impl = getattr(cfg, "norm_impl", "auto")
    if impl in ("kernel", "interpret"):
        return impl
    if impl == "auto" and jax.default_backend() == "tpu" \
            and x.ndim >= 2 and x.shape[-2] > 1:
        return "kernel"
    return None


@jax.named_scope("norm")
def apply_norm(cfg, params, x, name="norm"):
    """Stats in fp32, scaling applied in the stream dtype.

    Upcasting the whole stream (x.astype(f32) ... .astype(bf16)) makes AD
    carry the residual GRADIENT in fp32 through every layer: 2x bytes on
    every boundary psum and on the scan's stacked backward saves (measured
    on llama3-405b — EXPERIMENTS.md §Perf iteration L1).  The fused
    rmsnorm path keeps the same property: its custom_vjp backward emits dx
    in the stream dtype from the saved inverse-RMS residual instead of
    letting AD differentiate the row reduction."""
    dtype = x.dtype
    if cfg.norm == "rmsnorm":
        impl = norm_kernel_impl(cfg, x)
        if impl is not None:
            from repro.kernels.rmsnorm import rmsnorm
            return rmsnorm(x, params[f"{name}_scale"], impl=impl)
    xf = x.astype(jnp.float32)
    if cfg.norm == "layernorm":
        mean = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.var(xf, axis=-1, keepdims=True)
        inv = jax.lax.rsqrt(var + 1e-5).astype(dtype)
        y = (x - mean.astype(dtype)) * inv
        y = y * params[f"{name}_scale"].astype(dtype) \
            + params[f"{name}_bias"].astype(dtype)
    else:  # rmsnorm
        ms = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
        inv = jax.lax.rsqrt(ms + 1e-6).astype(dtype)
        y = x * inv * params[f"{name}_scale"].astype(dtype)
    return y


# --------------------------- activations ---------------------------------


def activate(name: str, gate, up=None):
    """Gated activations take (gate, up); ungated take a single arg."""
    if name == "swiglu":
        return jax.nn.silu(gate) * up
    if name == "geglu":
        return jax.nn.gelu(gate) * up
    if name == "squared_relu":
        r = jax.nn.relu(gate)
        return r * r
    if name == "gelu":
        return jax.nn.gelu(gate)
    raise ValueError(name)


def is_gated(name: str) -> bool:
    return name in ("swiglu", "geglu")


# ------------------------------ RoPE --------------------------------------


def rope_freqs(head_dim: int, theta: float, positions: jax.Array):
    """positions: (...,) int32 -> cos/sin of shape (..., head_dim//2)."""
    half = head_dim // 2
    inv = 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))
    ang = positions[..., None].astype(jnp.float32) * inv
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array):
    """x: (b, s, h, dh); cos/sin: (b, s, dh//2) or (s, dh//2).

    Rotation applied in the stream dtype (angles computed fp32) — same
    fp32-gradient-chain rationale as apply_norm."""
    half = x.shape[-1] // 2
    if cos.ndim == 2:  # (s, half) -> broadcast over batch & heads
        cos = cos[None, :, None, :]
        sin = sin[None, :, None, :]
    else:              # (b, s, half)
        cos = cos[:, :, None, :]
        sin = sin[:, :, None, :]
    cos = cos.astype(x.dtype)
    sin = sin.astype(x.dtype)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin,
                            x1 * sin + x2 * cos], axis=-1)


def sinusoidal_pe(positions: jax.Array, d_model: int):
    """Whisper-style sinusoidal embeddings at arbitrary integer
    positions: (...,) -> (..., d_model)."""
    dim = jnp.arange(d_model // 2, dtype=jnp.float32)
    inv = jnp.exp(-jnp.log(10_000.0) * dim / (d_model // 2))
    ang = positions[..., None].astype(jnp.float32) * inv
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


def sinusoidal_positions(max_len: int, d_model: int):
    """Fixed sinusoidal embedding table (s, d)."""
    return sinusoidal_pe(jnp.arange(max_len), d_model)


# ------------------------------ loss ---------------------------------------


def cross_entropy(logits: jax.Array, labels: jax.Array,
                  mask: jax.Array | None = None):
    """logits (..., V) fp32; labels (...); mask (...) optional. Mean NLL."""
    logits = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = lse - ll
    if mask is None:
        return jnp.mean(nll)
    mask = mask.astype(jnp.float32)
    return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
