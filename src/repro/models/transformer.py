"""Dense decoder-only transformer (+ encoder-decoder variant for Whisper).

Layers are stacked and executed with ``lax.scan`` + remat so HLO stays small
at 126 layers; weights are cast to the compute dtype at use.  Self-attention
in train/prefill goes through ``attention_core``, which on TPU (or with
``cfg.attn_impl``) runs the fused Pallas flash-attention op with its
custom_vjp backward, so the per-layer remat recomputes an O(S) forward
instead of differentiating through a materialized score matrix.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models import attention as attn
from repro.models.common import (activate, apply_norm, cross_entropy,
                                 is_gated, norm_defs, sinusoidal_positions)
from repro.models.params import p
from repro.parallel.axes import shard_act


# ------------------------------- MLP ---------------------------------------


def mlp_defs(cfg, d_ff=None, prefix=""):
    d_ff = d_ff or cfg.d_ff
    d = cfg.d_model
    defs = {}
    if is_gated(cfg.activation):
        defs[prefix + "w_gate"] = p((d, d_ff), ("embed", "mlp"))
        defs[prefix + "w_up"] = p((d, d_ff), ("embed", "mlp"))
    else:
        defs[prefix + "w_up"] = p((d, d_ff), ("embed", "mlp"))
    defs[prefix + "w_down"] = p((d_ff, d), ("mlp", "embed"))
    return defs


@jax.named_scope("mlp")
def apply_mlp(cfg, params, x, prefix=""):
    cd = x.dtype
    if is_gated(cfg.activation):
        g = x @ params[prefix + "w_gate"].astype(cd)
        u = x @ params[prefix + "w_up"].astype(cd)
        h = activate(cfg.activation, g, u)
    else:
        h = activate(cfg.activation, x @ params[prefix + "w_up"].astype(cd))
    h = shard_act(h, "batch", "seq", "mlp")
    y = h @ params[prefix + "w_down"].astype(cd)
    return shard_act(y, "batch", "seq", "embed")


# ----------------------------- one layer -----------------------------------


def layer_defs(cfg, cross_attention=False):
    defs = {}
    defs.update({f"ln1_{k}": v for k, v in norm_defs(cfg).items()})
    defs.update({f"attn_{k}": v for k, v in attn.attn_defs(cfg).items()})
    if cross_attention:
        defs.update({f"lnx_{k}": v for k, v in norm_defs(cfg).items()})
        defs.update({f"xattn_{k}": v for k, v in attn.attn_defs(cfg).items()})
    defs.update({f"ln2_{k}": v for k, v in norm_defs(cfg).items()})
    defs.update(mlp_defs(cfg, prefix="mlp_"))
    return defs


def _sub(params, prefix):
    n = len(prefix)
    return {k[n:]: v for k, v in params.items() if k.startswith(prefix)}


def dense_layer(cfg, lp, x, *, causal=True, positions=None,
                cross_kv=None):
    """Pre-norm transformer layer. x (b, s, d)."""
    h = apply_norm(cfg, _sub(lp, "ln1_"), x, name="norm")
    q, k, v = attn.project_qkv(cfg, _sub(lp, "attn_"), h, positions=positions)
    o = attn.attention_core(cfg, q, k, v, causal=causal)
    x = x + attn.out_proj(cfg, _sub(lp, "attn_"), o)
    if cross_kv is not None:
        xk, xv = cross_kv
        h = apply_norm(cfg, _sub(lp, "lnx_"), x, name="norm")
        q = jnp.einsum("bsd,dhk->bshk", h, lp["xattn_wq"].astype(h.dtype))
        o = attn.attention_core(cfg, q, xk, xv, causal=False)
        x = x + attn.out_proj(cfg, _sub(lp, "xattn_"), o)
    h = apply_norm(cfg, _sub(lp, "ln2_"), x, name="norm")
    x = x + apply_mlp(cfg, lp, h, prefix="mlp_")
    return shard_act(x, "batch", "seq", "embed")


def paged_chunk_layer(cfg, lp, x, k_pool, v_pool, block_tables, positions,
                      slots, *, k_scale=None, v_scale=None):
    """One layer of a chunk (T >= 1 tokens) against a block-paged pool.

    x (b, T, d); k_pool/v_pool (n_blocks, bs, kv, hd); ``positions``
    (b, T) is each token's absolute position (its RoPE position *and*
    the key positions its query attends ``<=``; negative = padding),
    landing at flat pool index ``slots`` (b, T) (computed once by the
    caller, shared across layers).  T = 1 is a decode tick, larger T a
    prefill chunk or speculative verify window — all one fused op.

    Quantized pools thread their per-token ``k_scale``/``v_scale``
    pools through the write and the attention; pass None for bf16.
    """
    h = apply_norm(cfg, _sub(lp, "ln1_"), x, name="norm")
    q, k, v = attn.project_qkv(cfg, _sub(lp, "attn_"), h,
                               positions=positions)
    if k_scale is not None:
        k_pool, v_pool, k_scale, v_scale = attn.paged_cache_update(
            k_pool, v_pool, k, v, slots, k_scale, v_scale)
    else:
        k_pool, v_pool = attn.paged_cache_update(k_pool, v_pool, k, v,
                                                 slots)
    o = attn.paged_chunk_attn(cfg, q, k_pool, v_pool, block_tables,
                              positions, k_scale=k_scale, v_scale=v_scale)
    x = x + attn.out_proj(cfg, _sub(lp, "attn_"), o)
    h = apply_norm(cfg, _sub(lp, "ln2_"), x, name="norm")
    x = x + apply_mlp(cfg, lp, h, prefix="mlp_")
    return x, k_pool, v_pool, k_scale, v_scale


def chunk_layer(cfg, lp, x, ck, cv, positions, *, fresh=False,
                cross_kv=None):
    """One layer of the chunk-oriented forward: prefill = decode = a chunk.

    x (b, T, d) for any T >= 1; ck/cv (b, S, kv, hd) dense cache;
    positions (b, T) absolute per-slot positions (negative = padding).
    The chunk's K/V are scattered into the cache first, then every query
    attends cache positions ``<=`` its own position — T = prompt length
    is a monolithic prefill, T = 1 is a decode step, anything between is
    a prefill chunk.

    ``fresh=True`` is the caller's *static* promise that the cache is
    factory-fresh and valid positions are lockstep ``arange`` rows; the
    layer then runs the fused causal core (flash-attention kernel on
    TPU) over the chunk itself instead of the masked cache gather.
    """
    h = apply_norm(cfg, _sub(lp, "ln1_"), x, name="norm")
    q, k, v = attn.project_qkv(cfg, _sub(lp, "attn_"), h, positions=positions)
    ck, cv = attn.chunk_cache_update(ck, cv, k, v, positions)
    if fresh:
        o = attn.attention_core(cfg, q, k, v, causal=True)
    else:
        o = attn.chunk_attention(cfg, q, ck, cv, positions)
    x = x + attn.out_proj(cfg, _sub(lp, "attn_"), o)
    if cross_kv is not None:
        xk, xv = cross_kv
        h = apply_norm(cfg, _sub(lp, "lnx_"), x, name="norm")
        qx = jnp.einsum("bsd,dhk->bshk", h, lp["xattn_wq"].astype(h.dtype))
        o = attn.attention_core(cfg, qx, xk, xv, causal=False)
        x = x + attn.out_proj(cfg, _sub(lp, "xattn_"), o)
    h = apply_norm(cfg, _sub(lp, "ln2_"), x, name="norm")
    x = x + apply_mlp(cfg, lp, h, prefix="mlp_")
    return x, ck, cv


# -------------------------- stacked-layer helpers ---------------------------


def stack_defs(defs: dict, n: int) -> dict:
    return jax.tree_util.tree_map(
        lambda d: p((n, *d.shape), ("layers", *d.axes), d.init, d.scale,
                    d.dtype),
        defs, is_leaf=lambda x: hasattr(x, "axes") and hasattr(x, "init"))


def scan_layers(fn, x, stacked, *, remat=True, extra_xs=None, extra_ys=False):
    """Run ``fn(x, layer_params[, extra]) -> x[, ys]`` over stacked layers."""
    body = jax.checkpoint(fn) if remat else fn

    if extra_xs is None and not extra_ys:
        def step(carry, lp):
            return body(carry, lp), None
        with jax.named_scope("layers"):
            x, _ = jax.lax.scan(step, x, stacked)
        return x

    def step(carry, inp):
        return body(carry, *inp)

    xs = (stacked,) if extra_xs is None else (stacked, *extra_xs)
    with jax.named_scope("layers"):
        return jax.lax.scan(step, x, xs)
