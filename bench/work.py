"""Operations and bytes the algorithms need, computed from shapes.

These count what the mathematics requires, not what an implementation
does: a change that cuts work must not move the yardstick.  Causal
attention counts the lower triangle only.  A multiply-add is 2
operations.
"""
from __future__ import annotations

from bench.reference.weights import DecoderSpec

BF16 = 2


def matmul_params(spec: DecoderSpec) -> int:
    """Weights that take part in a matrix product per token: every
    layer's projections and the (tied) output head; the embedding lookup
    is a gather and is not counted."""
    d, hd = spec.d_model, spec.head_dim
    attn = d * hd * (2 * spec.heads + 2 * spec.kv_heads)
    mlp = d * spec.d_ff * (3 if spec.gated else 2)
    return spec.layers * (attn + mlp) + spec.vocab * d


def train_flops_per_token(spec: DecoderSpec, seq: int) -> float:
    """Forward and backward of one token in a sequence of ``seq``:
    6 x the matmul weights (the 6·N·D rule) plus causal attention, whose
    scores and weighted sum cost 2·heads·hd·seq forward per token
    (averaged over the triangle) and twice that backward."""
    attn = 6 * spec.layers * spec.heads * spec.head_dim * seq
    return 6.0 * matmul_params(spec) + attn


def decode_flops(spec: DecoderSpec, keys: int) -> float:
    """One decoded token that attends ``keys`` cached positions: 2 x the
    matmul weights (2·N per token) plus q·k and p·v over every key."""
    return 2.0 * matmul_params(spec) + \
        4.0 * spec.layers * spec.heads * spec.head_dim * keys


def paged_attention(spec: DecoderSpec, keys: int) -> tuple[float, float]:
    """(flops, bytes) of one decode query against ``keys`` cached
    positions, over all layers: q·k and p·v for every head, reading each
    key and value once (all kv heads, bf16) plus q in and the output
    out."""
    L, h, kv, hd = spec.layers, spec.heads, spec.kv_heads, spec.head_dim
    flops = 4.0 * L * h * hd * keys
    nbytes = L * (2 * keys * kv * hd + 2 * h * hd) * BF16
    return flops, float(nbytes)


def flash_attention(kind: str, *, batch: int, heads: int, kv_heads: int,
                    seq: int, head_dim: int) -> tuple[float, float]:
    """(flops, bytes) of one call of a causal flash-attention kernel over
    (batch, heads, seq, head_dim) queries, by the products its outputs
    need from its inputs (each product over the lower triangle costs
    heads·hd·seq^2 per sequence):

    - ``fwd``: q·k^T and p·v (2 products); reads q, k, v, writes o and
      the float32 log-sum-exp.
    - ``dkv``: q·k^T (to rebuild p), do·v^T, p^T·do and ds^T·q (4);
      reads q, k, v, do, lse and delta, writes dk, dv.
    - ``dq``: q·k^T, do·v^T and ds·k (3); reads q, k, v, do, lse and
      delta, writes dq.
    """
    products = {"fwd": 2, "dkv": 4, "dq": 3}[kind]
    flops = float(products * batch * heads * head_dim * seq * seq)
    q = batch * heads * seq * head_dim * BF16
    kvb = batch * kv_heads * seq * head_dim * BF16
    rows = batch * heads * seq * 4
    if kind == "fwd":
        nbytes = 2 * q + 2 * kvb + rows
    elif kind == "dkv":
        nbytes = 2 * q + 2 * kvb + 2 * rows + 2 * kvb
    else:
        nbytes = 2 * q + 2 * kvb + 2 * rows + q
    return flops, float(nbytes)


def roofline_seconds(flops: float, nbytes: float, peaks: dict) -> tuple:
    """(least time, bound) on the chip: the larger of flops over peak
    FLOP/s and bytes over peak bandwidth, and which of the two it is."""
    tc = flops / peaks["bf16_flops"]
    tm = nbytes / peaks["hbm_bytes_per_s"]
    return (tc, "compute") if tc >= tm else (tm, "memory")
