"""GQA attention: projections, fused/chunked softmax cores, KV cache.

On TPU, train/prefill attention runs the fused Pallas ``flash_attention``
op (forward + custom_vjp backward, O(S) memory on both passes — see
``attention_core``).  The chunked jnp core is the memory-frugal XLA
fallback off-TPU and doubles as the oracle for the Pallas kernel.  Decode
attends against a KV cache whose *sequence* dimension may be sharded over
the "model" mesh axis (flash-decoding style — GSPMD inserts the
partial-softmax combine).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.params import p
from repro.models.common import apply_rope, rope_freqs
from repro.parallel.axes import shard_act
from repro.telemetry import get_registry

NEG_INF = -1e30

# Quantized KV pool dtypes (DESIGN.md §9).  Mirrors core/compression.py's
# wire formats: e4m3 saturates at +-448 and overflowing casts go to NaN,
# so values are clipped *before* the cast; int8 is blockwise-absmax with
# round + clip (quantize_blockwise's scheme, absmax taken per cached
# token instead of per flat 256-block).
KV_DTYPES = {
    "bfloat16": jnp.bfloat16,
    "float8_e4m3": jnp.float8_e4m3fn,
    "int8": jnp.int8,
}
_KV_QMAX = {jnp.dtype(jnp.float8_e4m3fn): 448.0, jnp.dtype(jnp.int8): 127.0}

# Trace counter for the retired hot path: incremented every time the
# dense masked (T, S) score fallback of ``chunk_attention`` is *traced*.
# Engine tests assert it stays flat when the kernel path is routed
# (attn_impl="kernel"/"interpret"), i.e. no dense score tensor is ever
# staged on the paged serving path.  Lives in the default telemetry
# registry; ``CHUNK_SCORE_TRACES`` remains readable as a module
# attribute (PEP 562) for back-compat with existing assertions.
_chunk_score_traces = get_registry().counter("attention.chunk_score_traces")


def __getattr__(name):
    if name == "CHUNK_SCORE_TRACES":
        return _chunk_score_traces.value
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


def quantize_kv(x, dtype):
    """Quantize K or V entries (..., kv, hd) -> (q, scale (...,) fp32).

    One absmax scale per cached token (over its kv x hd values): decode
    appends one token at a time, so per-token scales quantize once on
    write and never re-touch neighbours — a per-physical-block scale
    would force a read-modify-requantize of the whole block per append
    and let stale garbage in recycled blocks inflate the absmax.
    """
    dt = jnp.dtype(dtype)
    if dt not in _KV_QMAX:
        return x.astype(dtype), jnp.ones(x.shape[:-2], jnp.float32)
    qmax = _KV_QMAX[dt]
    xf = x.astype(jnp.float32)
    absmax = jnp.max(jnp.abs(xf), axis=(-2, -1))
    scale = jnp.maximum(absmax / qmax, 1e-12)
    y = xf / scale[..., None, None]
    if dt == jnp.dtype(jnp.int8):
        y = jnp.round(y)
    y = jnp.clip(y, -qmax, qmax)     # pre-cast clip: e4m3 overflow -> NaN
    return y.astype(dtype), scale


# ----------------------------- params -------------------------------------


def attn_defs(cfg):
    d, h, kv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    defs = {
        "wq": p((d, h, hd), ("embed", "heads", "head_dim")),
        "wk": p((d, kv, hd), ("embed", "kv_heads", "head_dim")),
        "wv": p((d, kv, hd), ("embed", "kv_heads", "head_dim")),
        "wo": p((h, hd, d), ("heads", "head_dim", "embed")),
    }
    if cfg.qkv_bias:
        defs["bq"] = p((h, hd), ("heads", "head_dim"), init="zeros")
        defs["bk"] = p((kv, hd), ("kv_heads", "head_dim"), init="zeros")
        defs["bv"] = p((kv, hd), ("kv_heads", "head_dim"), init="zeros")
    return defs


@jax.named_scope("qkv")
def project_qkv(cfg, params, x, positions=None, rope: bool = True):
    """x: (b, s, d) -> q (b,s,h,hd), k/v (b,s,kv,hd); RoPE applied."""
    cd = x.dtype
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"].astype(cd))
    k = jnp.einsum("bsd,dhk->bshk", x, params["wk"].astype(cd))
    v = jnp.einsum("bsd,dhk->bshk", x, params["wv"].astype(cd))
    if cfg.qkv_bias:
        q = q + params["bq"].astype(cd)
        k = k + params["bk"].astype(cd)
        v = v + params["bv"].astype(cd)
    if rope and cfg.rope_theta:
        if positions is None:
            positions = jnp.arange(x.shape[1])
        cos, sin = rope_freqs(cfg.head_dim, cfg.rope_theta, positions)
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)
    q = shard_act(q, "batch", "seq", "heads", "head_dim")
    return q, k, v


@jax.named_scope("out_proj")
def out_proj(cfg, params, attn_out):
    """attn_out (b, s, h, hd) -> (b, s, d)."""
    y = jnp.einsum("bshk,hkd->bsd", attn_out,
                   params["wo"].astype(attn_out.dtype))
    return shard_act(y, "batch", "seq", "embed")


# ------------------------- softmax attention cores -------------------------


def _broadcast_kv(k, n_heads):
    """(b, s, kv, hd) -> (b, s, h, hd) by group broadcast (GQA)."""
    b, s, kv, hd = k.shape
    if kv == n_heads:
        return k
    rep = n_heads // kv
    k = jnp.broadcast_to(k[:, :, :, None, :], (b, s, kv, rep, hd))
    return k.reshape(b, s, n_heads, hd)


def direct_attention(q, k, v, *, causal: bool, q_offset=0,
                     mask: jax.Array | None = None):
    """Full-materialization softmax attention. q (b,sq,h,hd), k/v (b,skv,h,hd).

    ``q_offset``: absolute position of q[0] relative to k[0] (decode)."""
    b, sq, h, hd = q.shape
    skv = k.shape[1]
    scale = hd ** -0.5
    logits = jnp.einsum("bqhk,bshk->bhqs", q, k).astype(jnp.float32) * scale
    if causal:
        qpos = q_offset + jnp.arange(sq)
        kpos = jnp.arange(skv)
        cm = qpos[:, None] >= kpos[None, :]
        logits = jnp.where(cm[None, None], logits, NEG_INF)
    if mask is not None:
        logits = jnp.where(mask, logits, NEG_INF)
    w = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqs,bshk->bqhk", w, v)


def chunked_attention(q, k, v, *, causal: bool, q_chunk=1024, kv_chunk=1024,
                      q_offset=0):
    """Flash-style online-softmax attention in pure jnp (O(sq*chunk) memory).

    q (b,sq,h,hd), k/v (b,skv,h,hd) — kv already GQA-broadcast.
    """
    b, sq, h, hd = q.shape
    skv = k.shape[1]
    scale = hd ** -0.5
    nq = max(sq // q_chunk, 1)
    nk = max(skv // kv_chunk, 1)
    q_chunk = sq // nq
    kv_chunk = skv // nk

    qr = q.reshape(b, nq, q_chunk, h, hd)
    kr = k.reshape(b, nk, kv_chunk, h, hd)
    vr = v.reshape(b, nk, kv_chunk, h, hd)

    def one_q_block(qi, qblk):
        # qblk: (b, qc, h, hd)
        # checkpoint the kv step: without it, scan stacks the exp'd score
        # blocks ((nk, b, h, qc, kc) fp32) as backward saves — O(s^2/chunk)
        # live memory; with it, backward recomputes them from (carry, kv
        # chunk) — flash-attention-style (EXPERIMENTS.md §Perf).
        @jax.checkpoint
        def kv_step(carry, inp):
            m, l, acc = carry
            ki, kblk, vblk = inp
            s = jnp.einsum("bqhk,bshk->bhqs", qblk, kblk)
            s = s.astype(jnp.float32) * scale
            if causal:
                qpos = q_offset + qi * q_chunk + jnp.arange(q_chunk)
                kpos = ki * kv_chunk + jnp.arange(kv_chunk)
                cm = qpos[:, None] >= kpos[None, :]
                s = jnp.where(cm[None, None], s, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            alpha = jnp.exp(m - m_new)
            pe = jnp.exp(s - m_new[..., None])
            l_new = l * alpha + jnp.sum(pe, axis=-1)
            acc_new = acc * alpha[..., None] + jnp.einsum(
                "bhqs,bshk->bhqk", pe, vblk.astype(jnp.float32))
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((b, h, q_chunk), NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, h, q_chunk), jnp.float32)
        a0 = jnp.zeros((b, h, q_chunk, hd), jnp.float32)
        ks = jnp.arange(nk)
        (m, l, acc), _ = jax.lax.scan(
            kv_step, (m0, l0, a0),
            (ks, jnp.moveaxis(kr, 1, 0), jnp.moveaxis(vr, 1, 0)))
        out = acc / jnp.maximum(l[..., None], 1e-30)
        return jnp.moveaxis(out, 1, 2).astype(q.dtype)  # (b, qc, h, hd)

    outs = [one_q_block(i, qr[:, i]) for i in range(nq)]
    return jnp.concatenate(outs, axis=1) if nq > 1 else outs[0]


@jax.named_scope("attention")
def attention_core(cfg, q, k, v, *, causal=True, q_offset=0,
                   chunked_threshold=2048, impl=None):
    """Dispatch the training/prefill softmax core.

    ``impl`` (default ``cfg.attn_impl``): "kernel"/"interpret" force the
    fused Pallas ``flash_attention`` (custom_vjp backward, O(S) memory on
    both passes, GQA folded into the kernel so K/V are never broadcast in
    HBM); "auto" uses the kernel only on TPU and otherwise falls back to
    the jnp direct/chunked cores; "ref" forces the jnp path.
    """
    if impl is None:
        impl = getattr(cfg, "attn_impl", "auto")
    # "auto" only picks the kernel for multi-token queries: one-token
    # decode (e.g. Whisper cross-attention in the decode loop) would pay
    # sublane padding + a pallas_call per token for a single matmul row.
    if impl in ("kernel", "interpret") or (
            impl == "auto" and q.shape[1] > 1 and
            jax.default_backend() == "tpu"):
        from repro.kernels.flash_attention import flash_attention
        o = flash_attention(
            jnp.swapaxes(q, 1, 2), jnp.swapaxes(k, 1, 2),
            jnp.swapaxes(v, 1, 2), causal=causal,
            impl="kernel" if impl == "auto" else impl, q_offset=q_offset)
        return jnp.swapaxes(o, 1, 2)
    k = _broadcast_kv(k, cfg.n_heads)
    v = _broadcast_kv(v, cfg.n_heads)
    skv = k.shape[1]
    if skv <= chunked_threshold:
        return direct_attention(q, k, v, causal=causal, q_offset=q_offset)
    return chunked_attention(q, k, v, causal=causal, q_offset=q_offset,
                             q_chunk=min(q.shape[1], 1024),
                             kv_chunk=min(skv, 1024))


# ------------------------------- KV cache ----------------------------------


@jax.named_scope("kv_write")
def chunk_cache_update(cache_k, cache_v, k_new, v_new, positions):
    """Scatter a chunk of K/V into a dense cache at per-slot positions.

    cache_k/v: (b, S, kv, hd); k_new/v_new: (b, T, kv, hd); positions
    (b, T) int32 — the absolute position of every token, **per slot**
    (no shared scalar index: slot i may be 3 tokens into its prompt
    while slot j is 500 deep).  Negative positions mark padding tokens:
    their writes are dropped (sanitized to an out-of-bounds index).
    """
    S = cache_k.shape[1]
    pw = jnp.where(positions >= 0, positions, S)        # OOB -> dropped
    bidx = jnp.arange(cache_k.shape[0])[:, None]
    ck = cache_k.at[bidx, pw].set(k_new.astype(cache_k.dtype), mode="drop")
    cv = cache_v.at[bidx, pw].set(v_new.astype(cache_v.dtype), mode="drop")
    return ck, cv


@jax.named_scope("attention")
def chunk_attention(cfg, q, cache_k, cache_v, positions, *, impl=None):
    """Chunk-of-T-tokens attention against a dense cache (T >= 1).

    q: (b, T, h, hd); cache_k/v: (b, S, kv, hd) **already containing
    this chunk's K/V** (write-then-attend); positions (b, T) absolute
    per-slot query positions, negative = padding.  Each query attends
    every cache position ``<= `` its own absolute position, which is
    simultaneously today's decode (T=1, one valid key prefix), a
    mid-prompt prefill chunk, and — with a fresh cache — a whole
    prompt.

    ``impl`` (default ``cfg.attn_impl``) dispatches like
    ``attention_core``: "kernel"/"interpret" (and "auto" on TPU) lower
    to the fused ``paged_chunk_attention`` op by viewing the dense
    cache as a one-block-per-sequence pool (n_blocks = b, block_size =
    S, table = arange(b)) — zero-copy, and padding rows come back as
    exact zeros.  "ref" (and "auto" off-TPU) keeps the masked (T, S)
    jnp score path, whose padding rows produce garbage masked out by
    the caller's last-token gather; tracing it bumps the module-level
    ``CHUNK_SCORE_TRACES`` counter so tests can assert the dense score
    tensor never appears on the kernel-routed serving path.
    """
    if impl is None:
        impl = getattr(cfg, "attn_impl", "auto")
    if impl == "auto":
        impl = "kernel" if jax.default_backend() == "tpu" else "ref"
    if impl != "ref":
        from repro.kernels.paged_chunk_attention import paged_chunk_attention
        b = q.shape[0]
        tables = jnp.arange(b, dtype=jnp.int32)[:, None]
        return paged_chunk_attention(q, cache_k, cache_v, tables, positions,
                                     impl=impl)
    _chunk_score_traces.inc()
    k = _broadcast_kv(cache_k, cfg.n_heads)
    v = _broadcast_kv(cache_v, cfg.n_heads)
    k = shard_act(k, "batch", "kv_seq", "heads", "head_dim")
    v = shard_act(v, "batch", "kv_seq", "heads", "head_dim")
    scale = cfg.head_dim ** -0.5
    s = jnp.einsum("bqhk,bshk->bhqs", q, k).astype(jnp.float32) * scale
    kpos = jnp.arange(k.shape[1])
    mask = kpos[None, None, :] <= positions[:, :, None]     # (b, T, S)
    s = jnp.where(mask[:, None], s, NEG_INF)
    w = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqs,bshk->bqhk", w, v)


# ---------------------------- paged KV cache --------------------------------


def paged_slot_index(block_tables, positions, block_size):
    """Flat pool index (``block_id * bs + offset``) where each slot's
    token at ``positions`` lands — the one place the block-table
    address arithmetic lives.  positions (b,) or (b, T) int32; negative
    positions (chunk padding) map to slot -1, which
    ``paged_cache_update`` drops."""
    pos = positions if positions.ndim == 2 else positions[:, None]
    pw = jnp.where(pos >= 0, pos, 0)
    blk = jnp.take_along_axis(block_tables, pw // block_size, axis=1)
    slots = jnp.where(pos >= 0, blk * block_size + pw % block_size, -1)
    return slots if positions.ndim == 2 else slots[:, 0]


@jax.named_scope("kv_write")
def paged_cache_update(k_pool, v_pool, k_new, v_new, slots,
                       k_scale=None, v_scale=None):
    """Scatter a chunk of new K/V into a block-paged pool.

    k_pool/v_pool: (n_blocks, bs, kv, hd); k_new/v_new: (b, T, kv, hd);
    slots: (b, T) (or legacy (b,) for T = 1) int32 flat pool indices
    ``block_id * bs + offset``; negative slots (padding tokens) are
    dropped.  Idle engine slots point at the reserved scratch block
    (see ``repro.serving.paged_cache``), so duplicate indices only ever
    collide there.

    Quantize-on-write: when ``k_scale``/``v_scale`` ((n_blocks, bs)
    float32 per-token scale pools) are given, the new entries are
    quantized to the pool dtype via ``quantize_kv`` and the scales are
    scattered beside them — returns (k_pool, v_pool, k_scale, v_scale).
    Without scales the entries are cast and (k_pool, v_pool) returned.
    """
    nb, bs, kvh, hd = k_pool.shape
    s2 = slots if slots.ndim == 2 else slots[:, None]
    sw = jnp.where(s2 >= 0, s2, nb * bs).reshape(-1)     # OOB -> dropped
    kf = k_pool.reshape(nb * bs, kvh, hd)
    vf = v_pool.reshape(nb * bs, kvh, hd)
    kn = k_new.reshape(-1, kvh, hd)
    vn = v_new.reshape(-1, kvh, hd)
    if k_scale is not None:
        kq, ks = quantize_kv(kn, k_pool.dtype)
        vq, vs = quantize_kv(vn, v_pool.dtype)
        kf = kf.at[sw].set(kq, mode="drop")
        vf = vf.at[sw].set(vq, mode="drop")
        ksp = k_scale.reshape(nb * bs).at[sw].set(ks, mode="drop")
        vsp = v_scale.reshape(nb * bs).at[sw].set(vs, mode="drop")
        return (kf.reshape(k_pool.shape), vf.reshape(v_pool.shape),
                ksp.reshape(nb, bs), vsp.reshape(nb, bs))
    kf = kf.at[sw].set(kn.astype(kf.dtype), mode="drop")
    vf = vf.at[sw].set(vn.astype(vf.dtype), mode="drop")
    return kf.reshape(k_pool.shape), vf.reshape(v_pool.shape)


@jax.named_scope("attention")
def paged_chunk_attn(cfg, q, k_pool, v_pool, block_tables, positions,
                     *, impl=None, k_scale=None, v_scale=None):
    """Chunk-of-T-tokens attention against a block-paged pool — the one
    attention op of the paged serving path (prefill chunks, decode
    ticks, speculative verify all lower here).

    q: (b, T, h, hd); k_pool/v_pool: (n_blocks, bs, kv, hd), optionally
    quantized with per-token ``k_scale``/``v_scale`` pools; block_tables
    (b, nbmax) int32; positions (b, T) absolute per-slot query positions
    **already written** to the pool (write-then-attend) — row t attends
    key positions ``<= positions[:, t]``, negative = padding -> zero
    rows.  ``impl`` (default ``cfg.attn_impl``) dispatches like
    ``attention_core``: "auto" compiles the Pallas kernel on TPU and
    uses the jnp gather ref elsewhere; "kernel"/"interpret"/"ref" force
    a path.
    """
    if impl is None:
        impl = getattr(cfg, "attn_impl", "auto")
    from repro.kernels.paged_chunk_attention import paged_chunk_attention
    o = paged_chunk_attention(q, k_pool, v_pool, block_tables, positions,
                              k_scale, v_scale, impl=impl)
    return o.astype(q.dtype)


def paged_decode_attention(cfg, q, k_pool, v_pool, block_tables, lengths,
                           *, impl=None, k_scale=None, v_scale=None):
    """One-token attention against a block-paged pool.

    A T=1 view over ``paged_chunk_attn`` kept for the legacy
    lengths-based signature: ``lengths`` (b,) counts valid cache
    positions *including* the token just written, so the query's
    absolute position is ``lengths - 1`` and "valid keys < lengths" is
    exactly the chunk contract's ``<= position``.
    """
    return paged_chunk_attn(cfg, q, k_pool, v_pool, block_tables,
                            (lengths - 1)[:, None], impl=impl,
                            k_scale=k_scale, v_scale=v_scale)
