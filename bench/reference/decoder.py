"""Plain float32 dense decoder: forward, loss, gradients and AdamW.

Written from the equations, with no kernels, cache or batching tricks,
and sharing no code with the system under test.  Every matrix product
runs at ``Precision.HIGHEST`` (on a TPU a float32 product is otherwise
done in bfloat16 passes).  Weights arrive in any dtype and are upcast to
float32 one layer at a time inside the layer scan, so a bfloat16-served
model and its float32 reference fit on one chip together.

``precision="fp8"`` is the control: every product's operands are first
rounded to float8 e4m3 with an absmax scale per row or column, the next
precision below the bfloat16 the configurations state.

The equations (pre-norm decoder, GPT-NeoX rotary on the whole head):

    h = norm1(x);  q, k, v = h Wq, h Wk, h Wv;  q, k = rope(q, k, pos)
    x = x + softmax(q k^T / sqrt(hd) + causal) v Wo
    x = x + mlp(norm2(x))           swiglu: (silu(h Wg) * h Wu) Wd
                                    gelu:   gelu_tanh(h Wu) Wd
    logits = norm(x) E^T            (embedding E tied to the head)
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench.reference.weights import DecoderSpec

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST
FP8_MAX = 448.0


def _fp8(x, axis):
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.maximum(amax / FP8_MAX, 1e-30)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(F32) * scale


def _mm(eq, a, b, precision, a_axis, b_axis):
    a, b = a.astype(F32), b.astype(F32)
    if precision == "fp8":
        a, b = _fp8(a, a_axis), _fp8(b, b_axis)
    return jnp.einsum(eq, a, b, precision=HIGHEST)


def _norm(spec, x, scale, bias):
    if spec.norm == "layernorm":
        mu = jnp.mean(x, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
        return (x - mu) * jax.lax.rsqrt(var + spec.norm_eps) * scale + bias
    ms = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(ms + spec.norm_eps) * scale


def _rope(spec, x, pos):
    """x (b, s, heads, hd); pos (s,)."""
    half = spec.head_dim // 2
    inv = 1.0 / (spec.rope_theta ** (jnp.arange(half, dtype=F32) / half))
    ang = pos.astype(F32)[:, None] * inv
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _layer(spec, lp, x, precision):
    """One decoder layer over x (b, s, d), float32."""
    f = {k: v.astype(F32) for k, v in lp.items()}
    s = x.shape[1]
    pos = jnp.arange(s)
    bias = (lambda n: f[n]) if spec.norm == "layernorm" else (lambda n: 0.0)
    h = _norm(spec, x, f["ln1_norm_scale"], bias("ln1_norm_bias"))
    q = _mm("bsd,dhk->bshk", h, f["attn_wq"], precision, -1, 0)
    k = _mm("bsd,dhk->bshk", h, f["attn_wk"], precision, -1, 0)
    v = _mm("bsd,dhk->bshk", h, f["attn_wv"], precision, -1, 0)
    q, k = _rope(spec, q, pos), _rope(spec, k, pos)
    group = spec.heads // spec.kv_heads
    k = jnp.repeat(k, group, axis=2)
    v = jnp.repeat(v, group, axis=2)
    sc = _mm("bqhk,bthk->bhqt", q, k, precision, -1, -1)
    sc = sc / jnp.sqrt(F32(spec.head_dim))
    causal = pos[:, None] >= pos[None, :]
    sc = jnp.where(causal[None, None], sc, -jnp.inf)
    p = jax.nn.softmax(sc, axis=-1)
    o = _mm("bhqt,bthk->bqhk", p, v, precision, -1, 1)
    x = x + _mm("bqhk,hkd->bqd", o, f["attn_wo"], precision, (-2, -1), (0, 1))
    h = _norm(spec, x, f["ln2_norm_scale"], bias("ln2_norm_bias"))
    up = _mm("bsd,df->bsf", h, f["mlp_w_up"], precision, -1, 0)
    if spec.gated:
        g = _mm("bsd,df->bsf", h, f["mlp_w_gate"], precision, -1, 0)
        act = jax.nn.silu(g) * up
    else:
        act = jax.nn.gelu(up, approximate=True)
    return x + _mm("bsf,fd->bsd", act, f["mlp_w_down"], precision, -1, 0)


def hidden(spec: DecoderSpec, params, tokens, precision="f32", remat=False):
    """Final-norm hidden states (b, s, d) of ``tokens`` (b, s)."""
    x = jnp.take(params["embed"], tokens, axis=0).astype(F32)
    body = (lambda x, lp: (_layer(spec, lp, x, precision), None))
    if remat:
        body = jax.checkpoint(body)
    x, _ = jax.lax.scan(body, x, params["layers"])
    fb = params.get("final_norm_bias")
    return _norm(spec, x, params["final_norm_scale"].astype(F32),
                 0.0 if fb is None else fb.astype(F32))


def head(params, h, precision="f32"):
    """Logits h (..., d) @ E^T in float32."""
    return _mm("...d,vd->...v", h, params["embed"], precision, -1, -1)


def row_logits(spec: DecoderSpec, params, tokens, rows, precision="f32"):
    """Logits (R, V) of one sequence ``tokens`` (s,) at positions ``rows``
    (R,): the prediction of the token after each of those positions.
    Padding after the last row does not change them (causal)."""
    h = hidden(spec, params, tokens[None], precision)[0]
    return head(params, jnp.take(h, rows, axis=0), precision)


def loss(spec: DecoderSpec, params, tokens, labels, precision="f32"):
    """Mean next-token cross entropy over every position of the batch."""
    h = hidden(spec, params, tokens, precision, remat=True)
    logits = head(params, h, precision)
    lse = jax.nn.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(lse - ll)


def grads(spec: DecoderSpec, params, tokens, labels, rows_per_block: int,
          precision="f32"):
    """(loss, gradients) of the mean loss over the batch, accumulated over
    blocks of ``rows_per_block`` rows so that the float32 logits fit."""
    b = tokens.shape[0]
    nb = b // rows_per_block
    tb = tokens.reshape(nb, rows_per_block, -1)
    lb = labels.reshape(nb, rows_per_block, -1)
    vg = jax.value_and_grad(lambda p, t, l: loss(spec, p, t, l, precision))

    def acc(carry, inp):
        lsum, gsum = carry
        lv, g = vg(params, *inp)
        return (lsum + lv, jax.tree_util.tree_map(jnp.add, gsum, g)), None

    zero = jax.tree_util.tree_map(lambda x: jnp.zeros(x.shape, F32), params)
    (lsum, gsum), _ = jax.lax.scan(acc, (F32(0), zero), (tb, lb))
    return lsum / nb, jax.tree_util.tree_map(lambda g: g / nb, gsum)


def adamw(opt: dict, params, m, v, g, step):
    """One AdamW step on float32 masters after clipping the gradient to a
    global norm of ``opt["clip_norm"]``.  Returns (params, m, v, g used)."""
    leaves = jax.tree_util.tree_leaves(g)
    gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(x)) for x in leaves))
    g = jax.tree_util.tree_map(
        lambda x: x * jnp.minimum(1.0, opt["clip_norm"] / jnp.maximum(
            gnorm, 1e-12)), g)
    b1, b2 = opt["b1"], opt["b2"]
    t = step.astype(F32)
    bc1, bc2 = 1.0 - b1 ** t, 1.0 - b2 ** t
    m = jax.tree_util.tree_map(lambda m, g: b1 * m + (1 - b1) * g, m, g)
    v = jax.tree_util.tree_map(lambda v, g: b2 * v + (1 - b2) * g * g, v, g)
    params = jax.tree_util.tree_map(
        lambda p, m, v: p - opt["lr"] * (m / bc1 / (jnp.sqrt(v / bc2)
                                                   + opt["eps"])
                                         + opt["weight_decay"] * p),
        params, m, v)
    return params, m, v, g
