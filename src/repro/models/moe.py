"""Mixture-of-Experts FFN with expert parallelism over the "model" axis.

Dispatch uses the GShard/Switch grouped capacity-einsum formulation: tokens
are split into groups (G, S); each group builds an (S, E, C) dispatch tensor
via a cumulative-position rank, and everything is batched over G so GSPMD
can partition it (no sequential loop over a sharded dim).  The dispatched
activations are sharded E->"model", so every expert shard computes locally;
the combine einsum's partial sums trigger exactly one psum over "model" per
layer — the same collective footprint as a TP MLP (HaiScale EP, DESIGN.md §4).

Dispatch-einsum FLOPs overhead is group-size-tunable (``group_size``); the
perf loop iterates on it (EXPERIMENTS.md §Perf).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.common import activate, is_gated
from repro.models.params import p
from repro.parallel.axes import current_resolver, shard_act

# Dispatch-einsum cost per token scales with group size (g*k*cf*d); the
# sweep on qwen3-moe (EXPERIMENTS.md §Perf Cell D) measured per-chip HLO
# FLOPs 1.028e15 / 9.31e14 / 8.83e14 at g=1024/512/256.  512 is the
# default: −9 % compute for ~2 % capacity-variance increase; 256 is the
# aggressive point (−14 % compute, −32 % collectives, higher drop risk).
DEFAULT_GROUP = 512


def moe_defs(cfg):
    m, d = cfg.moe, cfg.d_model
    gated = is_gated(cfg.activation)
    defs = {"router": p((d, m.n_experts), ("embed", "expert"), init="small")}
    shp = (m.n_experts, d, m.d_expert)
    axes = ("expert", "embed", "moe_mlp")
    if gated:
        defs["e_gate"] = p(shp, axes)
        defs["e_up"] = p(shp, axes)
    else:
        defs["e_up"] = p(shp, axes)
    defs["e_down"] = p((m.n_experts, m.d_expert, d),
                       ("expert", "moe_mlp", "embed"))
    if m.d_shared:
        if gated:
            defs["s_gate"] = p((d, m.d_shared), ("embed", "mlp"))
            defs["s_up"] = p((d, m.d_shared), ("embed", "mlp"))
        else:
            defs["s_up"] = p((d, m.d_shared), ("embed", "mlp"))
        defs["s_down"] = p((m.d_shared, d), ("mlp", "embed"))
        defs["s_gate_proj"] = p((d, 1), ("embed", "mlp"), init="small")
    return defs


def _shard_ge(x, g_axis_name, n_experts):
    """Constrain a (G, ..., E, ...) tensor: G->batch axes, E->"model"."""
    r = current_resolver()
    if r is None:
        return x
    axes = ["_"] * x.ndim
    axes[0] = g_axis_name
    for i, d in enumerate(x.shape[1:], start=1):
        if d == n_experts:
            axes[i] = "expert"
            break
    return shard_act(x, *axes)


@jax.named_scope("moe")
def apply_moe(cfg, params, x, *, group_size=DEFAULT_GROUP, dropless=False):
    """x (b, s, d) -> (y (b, s, d), aux_loss).

    ``dropless=True`` removes the capacity constraint (cap = every
    (token, choice) fits): each token's output then depends only on its
    own routing, never on which other tokens share its dispatch group —
    the invariance the chunk-oriented serving path needs so that a
    prompt prefilled in chunks (or padded to a bucket) routes exactly
    like a monolithic prefill.  Training keeps the capacity-limited
    GShard form (the paper's EP cost model assumes it); dropless pays a
    larger dispatch tensor, acceptable at serving batch sizes.
    """
    m = cfg.moe
    b, s, d = x.shape
    T = b * s
    g = min(group_size, T)
    G = T // g
    if dropless:
        # a token holds at most one slot per expert queue (top_k expert
        # indices are distinct), so g capacity slots fit every entry
        cap = g
    else:
        cap = max(int(g * m.top_k / m.n_experts * m.capacity_factor),
                  m.top_k)
        cap = min(cap, g)
    xf = x.reshape(G, g, d)
    # G inherits the batch sharding when it spans >= the batch dim; for
    # decode (G == 1) the token dim S carries it instead.
    g_ax = "batch" if G >= b else "_"
    s_ax = "batch" if g_ax == "_" else "_"
    xf = shard_act(xf, g_ax, s_ax, "embed")

    # ---- router (fp32) ----
    logits = jnp.einsum("gsd,de->gse", xf.astype(jnp.float32),
                        params["router"].astype(jnp.float32))
    def _topk_renorm(scores):
        w, e = jax.lax.top_k(scores, m.top_k)                # (G,S,k)
        return w / jnp.maximum(jnp.sum(w, axis=-1, keepdims=True), 1e-9), e

    if m.router == "sigmoid":
        scores = jax.nn.sigmoid(logits)
        weights, experts = _topk_renorm(scores)
    else:
        # scores stay dense for the aux loss; the top-k selection +
        # renormalization go through the fused topk_gating custom_vjp op
        # (one softmax+argmax pass forward, scattered dlogits backward)
        # on the kernel/interpret paths.  "auto" skips the kernel for
        # one-token decode (pallas_call per token for a tiny tile).
        scores = jax.nn.softmax(logits, axis=-1)
        impl = getattr(cfg, "gate_impl", "auto")
        if impl in ("kernel", "interpret") or (
                impl == "auto" and s > 1 and
                jax.default_backend() == "tpu"):
            from repro.kernels.topk_gating import topk_gating
            w2, i2 = topk_gating(logits.reshape(G * g, m.n_experts),
                                 k=m.top_k, renorm=True,
                                 impl="kernel" if impl == "auto" else impl)
            weights = w2.reshape(G, g, m.top_k)
            experts = i2.reshape(G, g, m.top_k)
        else:
            weights, experts = _topk_renorm(scores)

    # GShard load-balance aux loss
    onehot = jax.nn.one_hot(experts, m.n_experts, dtype=jnp.float32)  # (G,S,k,E)
    probs_mean = jnp.mean(scores, axis=1)                    # (G,E)
    frac = jnp.mean(onehot, axis=(1, 2))                     # (G,E)
    aux = m.n_experts * jnp.mean(
        jnp.sum(probs_mean * frac, axis=-1)) * m.router_aux_weight

    # ---- capacity rank: position of each (token, choice) in expert queue,
    # k-major so first choices win capacity ----
    flat = onehot.transpose(0, 2, 1, 3).reshape(G, m.top_k * g, m.n_experts)
    pos = jnp.cumsum(flat, axis=1) - flat                    # (G,kS,E)
    pos = pos.reshape(G, m.top_k, g, m.n_experts).transpose(0, 2, 1, 3)
    within = jnp.sum(pos * onehot, axis=-1)                  # (G,S,k)
    keep = (within < cap).astype(weights.dtype)
    wkeep = weights * keep
    cap_oh = jax.nn.one_hot(within.astype(jnp.int32), cap, dtype=jnp.float32)

    combine = jnp.einsum("gske,gskc,gsk->gsec", onehot, cap_oh, wkeep)
    combine = _shard_ge(combine, g_ax, m.n_experts)
    dispatch = (combine > 0).astype(x.dtype)                 # (G,S,E,C)

    # ---- dispatch -> expert FFN -> combine ----
    cd = x.dtype
    xe = jnp.einsum("gsd,gsec->gecd", xf, dispatch)          # (G,E,C,d)
    xe = _shard_ge(xe, g_ax, m.n_experts)
    if is_gated(cfg.activation):
        gg = jnp.einsum("gecd,edf->gecf", xe, params["e_gate"].astype(cd))
        uu = jnp.einsum("gecd,edf->gecf", xe, params["e_up"].astype(cd))
        h = activate(cfg.activation, gg, uu)
    else:
        h = activate(cfg.activation,
                     jnp.einsum("gecd,edf->gecf", xe,
                                params["e_up"].astype(cd)))
    ye = jnp.einsum("gecf,efd->gecd", h, params["e_down"].astype(cd))
    ye = _shard_ge(ye, g_ax, m.n_experts)
    y = jnp.einsum("gecd,gsec->gsd", ye, combine.astype(cd))
    y = y.reshape(b, s, d)

    # ---- shared experts (Qwen2-MoE / DeepSeekMoE style) ----
    if m.d_shared:
        if is_gated(cfg.activation):
            h = activate(cfg.activation, x @ params["s_gate"].astype(cd),
                         x @ params["s_up"].astype(cd))
        else:
            h = activate(cfg.activation, x @ params["s_up"].astype(cd))
        sh = h @ params["s_down"].astype(cd)
        gate = jax.nn.sigmoid(x @ params["s_gate_proj"].astype(cd))
        y = y + gate * sh
    return shard_act(y, "batch", "seq", "embed"), aux
