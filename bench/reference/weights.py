"""Seeded weights of a dense decoder, made by the benchmark.

The system under test and the reference are both given weights drawn
here from ``--seed``, so neither takes anything the other made.  The
tree has the layout the system's dense decoder takes (stacked layers
under ``"layers"``); the harness checks it against the model's own
parameter shapes before use.

Initialisation: embedding N(0, 0.02); every projection N(0, 1/fan_in)
with fan_in its input width; norm scales 1, norm biases 0.  Each leaf
is drawn in float32 from its own key and cast to ``dtype`` inside one
jitted call on the device.
"""
from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class DecoderSpec:
    """The shapes and equations of a dense decoder, as a configuration
    file's ``decoder`` block states them."""
    layers: int
    d_model: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    norm: str            # rmsnorm | layernorm
    norm_eps: float
    activation: str      # swiglu | gelu (tanh approximation)
    rope_theta: float

    @classmethod
    def from_block(cls, block: dict) -> "DecoderSpec":
        return cls(**{f.name: block[f.name] for f in dataclasses.fields(cls)})

    @property
    def gated(self) -> bool:
        return self.activation == "swiglu"


def seed_key(seed: int, stream: int = 0):
    """PRNG key from a seed of any size: all 64 bits reach the key
    (``PRNGKey`` alone keeps only the low 32)."""
    key = jax.random.PRNGKey(stream)
    key = jax.random.fold_in(key, seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def leaf_specs(spec: DecoderSpec) -> dict:
    """``{path: (shape, std)}``; std 0 means ones (scales) and None zeros
    (biases)."""
    L, d, h, kv, hd, ff = (spec.layers, spec.d_model, spec.heads,
                           spec.kv_heads, spec.head_dim, spec.d_ff)
    out = {("embed",): ((spec.vocab, d), 0.02),
           ("final_norm_scale",): ((d,), 0.0)}
    if spec.norm == "layernorm":
        out[("final_norm_bias",)] = ((d,), None)
    lay = {"attn_wq": ((L, d, h, hd), d), "attn_wk": ((L, d, kv, hd), d),
           "attn_wv": ((L, d, kv, hd), d), "attn_wo": ((L, h, hd, d), h * hd),
           "mlp_w_up": ((L, d, ff), d), "mlp_w_down": ((L, ff, d), ff)}
    if spec.gated:
        lay["mlp_w_gate"] = ((L, d, ff), d)
    for name, (shape, fan_in) in lay.items():
        out[("layers", name)] = (shape, 1.0 / math.sqrt(fan_in))
    for n in ("ln1", "ln2"):
        out[("layers", f"{n}_norm_scale")] = ((L, d), 0.0)
        if spec.norm == "layernorm":
            out[("layers", f"{n}_norm_bias")] = ((L, d), None)
    return out


def draw(spec: DecoderSpec, base, dtype) -> dict:
    """The weight tree from the key ``base`` (traceable)."""
    tree: dict = {}
    for i, (path, (shape, std)) in enumerate(sorted(leaf_specs(spec).items())):
        if std is None:
            w = jnp.zeros(shape, dtype)
        elif std == 0.0:
            w = jnp.ones(shape, dtype)
        else:
            w = (jax.random.normal(jax.random.fold_in(base, i), shape,
                                   jnp.float32) * std).astype(dtype)
        node = tree
        for p in path[:-1]:
            node = node.setdefault(p, {})
        node[path[-1]] = w
    return tree


def make_params(spec: DecoderSpec, seed: int, dtype="bfloat16", *,
                out_shardings=None):
    """All weights from ``seed`` in ``dtype``, made on the device by one
    jitted call.  The key is an argument, so one compiled program serves
    every seed.  ``out_shardings``: one sharding for every leaf, or
    None."""
    return _maker(spec, str(dtype), out_shardings)(seed_key(seed, stream=1))


@functools.lru_cache(maxsize=None)
def _maker(spec: DecoderSpec, dtype: str, out_shardings):
    return jax.jit(lambda key: draw(spec, key, jnp.dtype(dtype)),
                   out_shardings=out_shardings)


def param_shapes(spec: DecoderSpec, dtype="float32") -> dict:
    """The tree ``make_params`` returns, as ShapeDtypeStructs."""
    return jax.eval_shape(lambda key: draw(spec, key, jnp.dtype(dtype)),
                          seed_key(0, stream=1))


def check_layout(spec: DecoderSpec, model, dtype) -> None:
    """Refuse a model whose parameter tree differs from the weights this
    module makes (the system's layout changed under the benchmark)."""
    def shapes(tree):
        return {jax.tree_util.keystr(k): tuple(v.shape)
                for k, v in jax.tree_util.tree_leaves_with_path(tree)}
    if shapes(param_shapes(spec, dtype)) != shapes(model.param_shapes(dtype)):
        raise RuntimeError("the benchmark's weights do not fit the model's "
                           "parameter layout")
