"""Uniform chunk-oriented model API over the zoo.

Every model exposes one state-carrying serving call (DESIGN.md §8):

  init_seq_state(params, max_len, ...) -> SeqState
  forward(params, state, tokens, positions) -> (SeqState, logits)

``tokens`` is (b, T) for **any** T >= 1: T = prompt length is a
monolithic prefill, T = 1 is a decode step, and anything between is a
prefill *chunk*.  ``positions`` (b, T) carries each token's absolute
position **per slot** (no shared scalar index), so late-arriving slots
and mid-prompt chunks are first-class; negative positions mark padding
(dropped from the cache, excluded from the position-indexed last-token
logit gather).  The ``SeqState`` pytree unifies every family's
sequence state behind that one contract: dense KV, paged block pools
(with ``lengths``/``block_tables`` *inside* the state), Zamba's
mamba+KV hybrid state, xLSTM block states, and Whisper cross-KV.
Leaves a model does not recognize (e.g. the serving engine's per-slot
PRNG keys) pass through untouched.

``seq_state_specs(shape)`` / ``seq_state_axes(shape)`` describe the
state layout for AOT lowering.  The pre-chunk API (``prefill`` /
``decode_step`` / ``paged_decode_step`` and their cache specs) is
gone — the chunk calls above are the only serving surface, and CI
guards that the old symbols stay deleted.

Training API is unchanged: param_defs() / init(rng) / loss(params,
batch).  ``build_model(cfg)`` dispatches on ``cfg.family``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, ShapeConfig
from repro.models import attention as attn
from repro.models import moe as moe_mod
from repro.models import xlstm as xlstm_mod
from repro.models import zamba as zamba_mod
from repro.models.common import (apply_norm, cross_entropy, norm_defs,
                                 sinusoidal_pe, sinusoidal_positions)
from repro.models.params import init_tree, p, shape_tree
from repro.models.transformer import (chunk_layer, dense_layer, layer_defs,
                                      paged_chunk_layer, stack_defs, _sub)
from repro.parallel.axes import shard_act

WHISPER_DECODE_ENC_FRAMES = 1500


def _embed_defs(cfg):
    defs = {"embed": p((cfg.vocab_size, cfg.d_model), ("vocab", "embed"),
                       init="embed", scale=0.02)}
    if not cfg.tie_embeddings:
        defs["unembed"] = p((cfg.d_model, cfg.vocab_size),
                            ("embed", "vocab"))
    defs.update({f"final_{k}": v for k, v in norm_defs(cfg).items()})
    return defs


def arange_positions(batch: int, length: int, offset: int = 0):
    """Lockstep (b, T) positions ``offset + [0..T)`` for every slot."""
    return jnp.broadcast_to(jnp.arange(offset, offset + length,
                                       dtype=jnp.int32), (batch, length))


def last_valid_index(positions):
    """Index of each slot's last non-padding token within the chunk."""
    return jnp.maximum(jnp.sum(positions >= 0, axis=1) - 1, 0)


class BaseLM:
    def __init__(self, cfg: ModelConfig):
        self.cfg = cfg
        self.compute_dtype = cfg.compute_dtype

    # -- shared pieces ------------------------------------------------------

    def init(self, rng):
        return init_tree(self.param_defs(), rng)

    def param_shapes(self, dtype=None):
        return shape_tree(self.param_defs(), dtype)

    @jax.named_scope("embed")
    def _embed(self, params, tokens):
        x = jnp.take(params["embed"], tokens, axis=0)
        return x.astype(self.compute_dtype)

    def _logits(self, params, x):
        x = apply_norm(self.cfg, _sub(params, "final_"), x, name="norm")
        if self.cfg.tie_embeddings:
            w = params["embed"].T
        else:
            w = params["unembed"]
        logits = x @ w.astype(x.dtype)
        return shard_act(logits, "batch", "seq", "vocab")

    def _gather_logits(self, params, x, positions):
        """Position-indexed last-token logit gather: project only each
        slot's last valid chunk row to (b, V)."""
        idx = last_valid_index(positions)
        xl = jnp.take_along_axis(x, idx[:, None, None], axis=1)
        return self._logits(params, xl)[:, 0]

    @jax.named_scope("lm_head")
    def _chunk_logits(self, params, x, positions, all_logits):
        """Chunk output head: (b, V) at each slot's last valid position
        by default, or — ``all_logits`` — the full (b, T, V) so callers
        can read the model's prediction after *every* chunk row (the
        speculative-verify consumer; padding rows produce garbage the
        caller must mask by ``positions``)."""
        if all_logits:
            return self._logits(params, x)
        return self._gather_logits(params, x, positions)

    @jax.named_scope("lm_head")
    def _ce(self, params, x, labels, mask=None):
        logits = self._logits(params, x)
        return cross_entropy(logits, labels, mask)

    # -- API (must be overridden) ------------------------------------------

    def param_defs(self):
        raise NotImplementedError

    def loss(self, params, batch):
        raise NotImplementedError

    def init_seq_state(self, params, max_len, *, batch=None,
                       batch_size=None, dtype="bfloat16"):
        """Fresh SeqState for ``batch_size`` slots and ``max_len`` cache
        capacity.  Families with non-token inputs (Whisper frames, VLM
        patches) take them via ``batch``."""
        raise NotImplementedError

    @property
    def prefill_padding_ok(self) -> bool:
        """Whether padding tokens (positions < 0) may ride through a
        chunk: True only when every sequence mixer is position-masked
        attention (dropped writes, masked reads).  A carried recurrence
        (SSD, xLSTM) would absorb the padding into its state, so those
        families require exact-length chunks."""
        return False

    def forward(self, params, state, tokens, positions, *, embeds=None,
                fresh=False, all_logits=False):
        """Advance ``state`` by one chunk of T >= 1 tokens per slot.

        tokens (b, T) int32 (ignored when ``embeds`` (b, T, d) is
        given); positions (b, T) int32 absolute per-slot positions,
        negative = padding.  Returns (state', logits (b, V)) with
        logits gathered at each slot's last valid position — or, with
        ``all_logits=True`` (static), the full per-row (b, T, V): the
        multi-token-per-step emission mode speculative verify needs
        (row t is the model's next-token prediction after the token at
        ``positions[:, t]``; padding rows are garbage to mask).

        ``fresh=True`` is a static caller promise that ``state`` is
        factory-fresh and valid positions are lockstep arange rows —
        models may then take the fused whole-sequence paths (flash
        attention, chunked SSD kernels).  Recurrent families reject
        padding; attention families tolerate trailing padding (their
        dropped writes are later overwritten by decode).
        """
        raise NotImplementedError

    def prompt_inputs(self, params, batch):
        """(tokens, positions, embeds) for a whole-prompt chunk."""
        tokens = batch["tokens"]
        b, s = tokens.shape
        return tokens, arange_positions(b, s), None

    def prompt_length(self, batch) -> int:
        """Sequence positions a prompt occupies (incl. non-token rows
        such as VLM patches) — where decode continues from."""
        return batch["tokens"].shape[1]

    def _paged_chunk_driver(self, params, state, tokens, positions,
                            step_token, all_logits=False):
        """Per-token scaffolding for paged forwards of families with a
        carried recurrence (hybrid mamba states advance one token at a
        time): embed token t, run ``step_token(x, pos) -> x`` (which
        advances the pools / recurrent carries in its closure), then
        gather per-slot last-valid logits (or project every row with
        ``all_logits``).  Pure-attention families run the whole chunk
        through one fused op instead (DecoderLM).
        Returns (logits, lengths)."""
        T = positions.shape[1]
        per_step = [step_token(self._embed(params, tokens[:, t])[:, None, :],
                               positions[:, t])
                    for t in range(T)]
        x = jnp.concatenate(per_step, axis=1) if T > 1 else per_step[0]
        logits = self._chunk_logits(params, x, positions, all_logits)
        lengths = jnp.max(positions, axis=1).astype(jnp.int32) + 1
        return logits, lengths

    def batch_specs(self, shape: ShapeConfig):
        b, s = shape.global_batch, shape.seq_len
        if shape.kind == "train":
            return {"tokens": jax.ShapeDtypeStruct((b, s), "int32"),
                    "labels": jax.ShapeDtypeStruct((b, s), "int32")}
        if shape.kind == "prefill":
            return {"tokens": jax.ShapeDtypeStruct((b, s), "int32")}
        t = shape.chunk if shape.kind == "chunk" else 1
        return {"tokens": jax.ShapeDtypeStruct((b, t), "int32"),
                "positions": jax.ShapeDtypeStruct((b, t), "int32")}

    def seq_state_specs(self, shape: ShapeConfig):
        raise NotImplementedError

    def seq_state_axes(self, shape: ShapeConfig):
        raise NotImplementedError


# =========================== decoder-only ==================================


class DecoderLM(BaseLM):
    """Dense / MoE / VLM decoder-only LM with scan-over-layers."""

    def __init__(self, cfg, moe_group=moe_mod.DEFAULT_GROUP):
        super().__init__(cfg)
        self.is_moe = cfg.moe is not None
        self.is_vlm = cfg.family == "vlm"
        self.moe_group = moe_group

    def _layer_defs(self):
        if not self.is_moe:
            return layer_defs(self.cfg)
        defs = {}
        defs.update({f"ln1_{k}": v
                     for k, v in norm_defs(self.cfg).items()})
        defs.update({f"attn_{k}": v
                     for k, v in attn.attn_defs(self.cfg).items()})
        defs.update({f"ln2_{k}": v
                     for k, v in norm_defs(self.cfg).items()})
        defs.update({f"moe_{k}": v for k, v in moe_mod.moe_defs(self.cfg).items()})
        return defs

    def param_defs(self):
        defs = _embed_defs(self.cfg)
        defs["layers"] = stack_defs(self._layer_defs(), self.cfg.n_layers)
        return defs

    # ---- forward over stacked layers (training) ----

    def _moe_layer(self, lp, x, aux):
        cfg = self.cfg
        h = apply_norm(cfg, _sub(lp, "ln1_"), x, name="norm")
        q, k, v = attn.project_qkv(cfg, _sub(lp, "attn_"), h)
        o = attn.attention_core(cfg, q, k, v, causal=True)
        x = x + attn.out_proj(cfg, _sub(lp, "attn_"), o)
        h = apply_norm(cfg, _sub(lp, "ln2_"), x, name="norm")
        y, a = moe_mod.apply_moe(cfg, _sub(lp, "moe_"), h,
                                 group_size=self.moe_group)
        return x + y, aux + a

    def _forward(self, params, x, remat=True):
        cfg = self.cfg
        if self.is_moe:
            def body(carry, lp):
                x, aux = carry
                x, aux = self._moe_layer(lp, x, aux)
                return (x, aux), None
            f = jax.checkpoint(body) if remat else body
            with jax.named_scope("layers"):
                (x, aux), _ = jax.lax.scan(
                    f, (x, jnp.zeros((), jnp.float32)), params["layers"])
            return x, aux
        def body(carry, lp):
            return dense_layer(cfg, lp, carry, causal=True), None
        f = jax.checkpoint(body) if remat else body
        with jax.named_scope("layers"):
            x, _ = jax.lax.scan(f, x, params["layers"])
        return x, jnp.zeros((), jnp.float32)

    def _inputs(self, params, batch):
        x = self._embed(params, batch["tokens"])
        if self.is_vlm:
            patches = batch["patches"].astype(self.compute_dtype)
            x = jnp.concatenate([patches, x], axis=1)
        return shard_act(x, "batch", "seq", "embed")

    def loss(self, params, batch):
        x = self._inputs(params, batch)
        x, aux = self._forward(params, x)
        if self.is_vlm:
            npatch = self.cfg.n_frontend_tokens
            x = x[:, npatch:]
        ce = self._ce(params, x, batch["labels"], batch.get("mask"))
        return ce + aux, {"ce": ce, "aux_loss": aux}

    # ---- chunk-oriented serving ----

    def prompt_inputs(self, params, batch):
        if not self.is_vlm:
            return super().prompt_inputs(params, batch)
        x = self._inputs(params, batch)     # (b, npatch + s, d)
        b, t = x.shape[:2]
        return None, arange_positions(b, t), x

    def prompt_length(self, batch) -> int:
        npatch = self.cfg.n_frontend_tokens if self.is_vlm else 0
        return batch["tokens"].shape[1] + npatch

    def init_seq_state(self, params, max_len, *, batch=None,
                       batch_size=None, dtype="bfloat16"):
        cfg = self.cfg
        b = batch_size if batch_size is not None else len(batch["tokens"])
        kv, hd, L = cfg.n_kv_heads, cfg.head_dim, cfg.n_layers
        return {"k": jnp.zeros((L, b, max_len, kv, hd), dtype),
                "v": jnp.zeros((L, b, max_len, kv, hd), dtype)}

    def forward(self, params, state, tokens, positions, *, embeds=None,
                fresh=False, all_logits=False):
        if "block_tables" in state:
            return self._forward_paged(params, state, tokens, positions,
                                       all_logits=all_logits)
        cfg = self.cfg
        x = embeds if embeds is not None else self._embed(params, tokens)
        x = shard_act(x, "batch", "seq", "embed")

        if self.is_moe:
            def body(carry, inp):
                x, aux = carry
                lp, ck, cv = inp
                h = apply_norm(cfg, _sub(lp, "ln1_"), x, name="norm")
                q, k, v = attn.project_qkv(cfg, _sub(lp, "attn_"), h,
                                           positions=positions)
                ck, cv = attn.chunk_cache_update(ck, cv, k, v, positions)
                if fresh:
                    o = attn.attention_core(cfg, q, k, v, causal=True)
                else:
                    o = attn.chunk_attention(cfg, q, ck, cv, positions)
                x = x + attn.out_proj(cfg, _sub(lp, "attn_"), o)
                h = apply_norm(cfg, _sub(lp, "ln2_"), x, name="norm")
                y, a = moe_mod.apply_moe(cfg, _sub(lp, "moe_"), h,
                                         group_size=self.moe_group,
                                         dropless=True)
                return (x + y, aux + a), (ck, cv)
            with jax.named_scope("layers"):
                (x, _), (ck, cv) = jax.lax.scan(
                    body, (x, jnp.zeros((), jnp.float32)),
                    (params["layers"], state["k"], state["v"]))
        else:
            def body(x, inp):
                lp, ck, cv = inp
                x, ck, cv = chunk_layer(cfg, lp, x, ck, cv, positions,
                                        fresh=fresh)
                return x, (ck, cv)
            with jax.named_scope("layers"):
                x, (ck, cv) = jax.lax.scan(
                    body, x, (params["layers"], state["k"], state["v"]))

        logits = self._chunk_logits(params, x, positions, all_logits)
        return {**state, "k": ck, "v": cv}, logits

    def _forward_paged(self, params, state, tokens, positions,
                       all_logits=False):
        """Chunk forward against the block-paged pool: the whole (b, T)
        chunk runs as **one** fused ``paged_chunk_attn`` per layer
        (write-then-attend with per-slot position masking), so decode
        ticks (T=1), prefill chunks, and speculative verify windows all
        lower to the same op — no per-token inner loop, no dense (T, S)
        score tensor.  Quantized pools ("k_scale"/"v_scale" in the
        state) thread their per-token scale pools through the scan."""
        cfg = self.cfg
        tables = state["block_tables"]
        quant = "k_scale" in state
        x = self._embed(params, tokens)
        slots = attn.paged_slot_index(tables, positions, state["k"].shape[2])
        xs = (params["layers"], state["k"], state["v"])
        if quant:
            xs = xs + (state["k_scale"], state["v_scale"])

        if self.is_moe:
            def body(carry, inp):
                x, aux = carry
                lp, kp, vp = inp[:3]
                ks, vs = inp[3:] if quant else (None, None)
                h = apply_norm(cfg, _sub(lp, "ln1_"), x, name="norm")
                q, k, v = attn.project_qkv(cfg, _sub(lp, "attn_"), h,
                                           positions=positions)
                if quant:
                    kp, vp, ks, vs = attn.paged_cache_update(
                        kp, vp, k, v, slots, ks, vs)
                else:
                    kp, vp = attn.paged_cache_update(kp, vp, k, v, slots)
                o = attn.paged_chunk_attn(cfg, q, kp, vp, tables,
                                          positions, k_scale=ks, v_scale=vs)
                x = x + attn.out_proj(cfg, _sub(lp, "attn_"), o)
                h = apply_norm(cfg, _sub(lp, "ln2_"), x, name="norm")
                y, a = moe_mod.apply_moe(cfg, _sub(lp, "moe_"), h,
                                         group_size=self.moe_group,
                                         dropless=True)
                ys = (kp, vp, ks, vs) if quant else (kp, vp)
                return (x + y, aux + a), ys
            with jax.named_scope("layers"):
                (x, _), ys = jax.lax.scan(
                    body, (x, jnp.zeros((), jnp.float32)), xs)
        else:
            def body(x, inp):
                lp, kp, vp = inp[:3]
                ks, vs = inp[3:] if quant else (None, None)
                x, kp, vp, ks, vs = paged_chunk_layer(
                    cfg, lp, x, kp, vp, tables, positions, slots,
                    k_scale=ks, v_scale=vs)
                return x, ((kp, vp, ks, vs) if quant else (kp, vp))
            with jax.named_scope("layers"):
                x, ys = jax.lax.scan(body, x, xs)

        logits = self._chunk_logits(params, x, positions, all_logits)
        lengths = jnp.max(positions, axis=1).astype(jnp.int32) + 1
        new = {**state, "k": ys[0], "v": ys[1], "lengths": lengths}
        if quant:
            new["k_scale"], new["v_scale"] = ys[2], ys[3]
        return new, logits

    # ---- specs ----

    @property
    def prefill_padding_ok(self) -> bool:
        return True

    @property
    def paged_kv_layers(self) -> int:
        return self.cfg.n_layers

    def paged_state_extras(self, n_slots: int) -> dict:
        return {}

    def batch_specs(self, shape: ShapeConfig):
        b, s = shape.global_batch, shape.seq_len
        cd = self.compute_dtype
        if not self.is_vlm:
            return super().batch_specs(shape)
        npatch = self.cfg.n_frontend_tokens
        if shape.kind == "train":
            return {
                "patches": jax.ShapeDtypeStruct((b, npatch, self.cfg.d_model), cd),
                "tokens": jax.ShapeDtypeStruct((b, s - npatch), "int32"),
                "labels": jax.ShapeDtypeStruct((b, s - npatch), "int32"),
            }
        if shape.kind == "prefill":
            return {
                "patches": jax.ShapeDtypeStruct((b, npatch, self.cfg.d_model), cd),
                "tokens": jax.ShapeDtypeStruct((b, s - npatch), "int32"),
            }
        return super().batch_specs(shape)

    def seq_state_specs(self, shape: ShapeConfig):
        cfg = self.cfg
        b, s = shape.global_batch, shape.seq_len
        kv, hd, L = cfg.n_kv_heads, cfg.head_dim, cfg.n_layers
        return {
            "k": jax.ShapeDtypeStruct((L, b, s, kv, hd), "bfloat16"),
            "v": jax.ShapeDtypeStruct((L, b, s, kv, hd), "bfloat16"),
        }

    def seq_state_axes(self, shape: ShapeConfig):
        kvax = ("_", "batch", "kv_seq", "_", "_")
        return {"k": kvax, "v": kvax}


# ========================= whisper (enc-dec) ================================


class WhisperLM(BaseLM):
    @property
    def prefill_padding_ok(self) -> bool:
        return True     # decoder mixes only via position-masked attention

    def param_defs(self):
        cfg = self.cfg
        defs = _embed_defs(cfg)
        defs["encoder"] = stack_defs(layer_defs(cfg), cfg.encoder_layers)
        defs["enc_final"] = norm_defs(cfg)
        defs["decoder"] = stack_defs(layer_defs(cfg, cross_attention=True),
                                     cfg.n_layers)
        return defs

    def _encode(self, params, frames, remat=True):
        cfg = self.cfg
        pos = sinusoidal_positions(frames.shape[1], cfg.d_model)
        x = frames.astype(self.compute_dtype) + pos.astype(self.compute_dtype)
        x = shard_act(x, "batch", "seq", "embed")

        def body(x, lp):
            return dense_layer(cfg, lp, x, causal=False), None
        f = jax.checkpoint(body) if remat else body
        x, _ = jax.lax.scan(f, x, params["encoder"])
        return apply_norm(cfg, params["enc_final"], x, name="norm")

    def _cross_kv(self, params, enc):
        """Per-decoder-layer cross K/V from encoder output: (L,b,se,kv,hd)."""
        cfg = self.cfg

        def body(_, lp):
            xp = _sub(lp, "xattn_")
            cd = enc.dtype
            k = jnp.einsum("bsd,dhk->bshk", enc, xp["wk"].astype(cd))
            v = jnp.einsum("bsd,dhk->bshk", enc, xp["wv"].astype(cd))
            return 0, (k, v)
        _, (ks, vs) = jax.lax.scan(body, 0, params["decoder"])
        return ks, vs

    def _decode_stack(self, params, x, xks, xvs, remat=True):
        cfg = self.cfg

        def body(x, inp):
            lp, xk, xv = inp
            return dense_layer(cfg, lp, x, causal=True,
                               cross_kv=(xk, xv)), None
        f = jax.checkpoint(body) if remat else body
        x, _ = jax.lax.scan(f, x, (params["decoder"], xks, xvs))
        return x

    def _dec_inputs(self, params, tokens, positions):
        """Token embeddings + sinusoidal PE at per-slot positions."""
        x = self._embed(params, tokens)
        pe = sinusoidal_pe(positions, self.cfg.d_model)           # (b,T,d)
        x = x + pe.astype(x.dtype)
        return shard_act(x, "batch", "seq", "embed")

    def loss(self, params, batch):
        enc = self._encode(params, batch["frames"])
        xks, xvs = self._cross_kv(params, enc)
        b, s = batch["tokens"].shape
        x = self._dec_inputs(params, batch["tokens"],
                             arange_positions(b, s))
        x = self._decode_stack(params, x, xks, xvs)
        ce = self._ce(params, x, batch["labels"], batch.get("mask"))
        return ce, {"ce": ce}

    # ---- chunk-oriented serving ----

    def init_seq_state(self, params, max_len, *, batch=None,
                       batch_size=None, dtype="bfloat16"):
        cfg = self.cfg
        assert batch is not None and "frames" in batch, \
            "Whisper SeqState init needs batch['frames'] for the encoder"
        enc = self._encode(params, batch["frames"], remat=False)
        xks, xvs = self._cross_kv(params, enc)
        b = enc.shape[0]
        kv, hd, L = cfg.n_kv_heads, cfg.head_dim, cfg.n_layers
        return {"k": jnp.zeros((L, b, max_len, kv, hd), dtype),
                "v": jnp.zeros((L, b, max_len, kv, hd), dtype),
                "xk": xks.astype(dtype), "xv": xvs.astype(dtype)}

    def forward(self, params, state, tokens, positions, *, embeds=None,
                fresh=False, all_logits=False):
        cfg = self.cfg
        x = embeds if embeds is not None else self._dec_inputs(
            params, tokens, positions)

        def body(x, inp):
            lp, ck, cv, xk, xv = inp
            x, ck, cv = chunk_layer(cfg, lp, x, ck, cv, positions,
                                    fresh=fresh, cross_kv=(xk, xv))
            return x, (ck, cv)

        with jax.named_scope("layers"):
            x, (ck, cv) = jax.lax.scan(
                body, x, (params["decoder"], state["k"], state["v"],
                          state["xk"], state["xv"]))
        logits = self._chunk_logits(params, x, positions, all_logits)
        return {**state, "k": ck, "v": cv}, logits

    def batch_specs(self, shape: ShapeConfig):
        cfg = self.cfg
        b, s = shape.global_batch, shape.seq_len
        cd = self.compute_dtype
        if shape.kind == "train":
            return {"frames": jax.ShapeDtypeStruct((b, s, cfg.d_model), cd),
                    "tokens": jax.ShapeDtypeStruct((b, s), "int32"),
                    "labels": jax.ShapeDtypeStruct((b, s), "int32")}
        if shape.kind == "prefill":
            return {"frames": jax.ShapeDtypeStruct((b, s, cfg.d_model), cd),
                    "tokens": jax.ShapeDtypeStruct((b, s), "int32")}
        return super().batch_specs(shape)

    def seq_state_specs(self, shape: ShapeConfig):
        cfg = self.cfg
        b, s = shape.global_batch, shape.seq_len
        kv, hd, L = cfg.n_kv_heads, cfg.head_dim, cfg.n_layers
        se = WHISPER_DECODE_ENC_FRAMES
        return {
            "k": jax.ShapeDtypeStruct((L, b, s, kv, hd), "bfloat16"),
            "v": jax.ShapeDtypeStruct((L, b, s, kv, hd), "bfloat16"),
            "xk": jax.ShapeDtypeStruct((L, b, se, kv, hd), "bfloat16"),
            "xv": jax.ShapeDtypeStruct((L, b, se, kv, hd), "bfloat16"),
        }

    def seq_state_axes(self, shape: ShapeConfig):
        kvax = ("_", "batch", "kv_seq", "_", "_")
        xax = ("_", "batch", "_", "_", "_")
        return {"k": kvax, "v": kvax, "xk": xax, "xv": xax}


# ============================ zamba hybrid ==================================


class ZambaLM(BaseLM):
    def param_defs(self):
        defs = _embed_defs(self.cfg)
        defs.update(zamba_mod.zamba_defs(self.cfg))
        return defs

    def loss(self, params, batch):
        x = self._embed(params, batch["tokens"])
        x = shard_act(x, "batch", "seq", "embed")
        x = zamba_mod.zamba_forward(self.cfg, params, x)
        ce = self._ce(params, x, batch["labels"], batch.get("mask"))
        return ce, {"ce": ce}

    # ---- chunk-oriented serving ----

    def init_seq_state(self, params, max_len, *, batch=None,
                       batch_size=None, dtype="bfloat16"):
        cfg = self.cfg
        b = batch_size if batch_size is not None else len(batch["tokens"])
        inv = zamba_mod.n_attn_invocations(cfg)
        kv, hd = cfg.n_kv_heads, cfg.head_dim
        return {
            "mamba": zamba_mod.zamba_mamba_init(cfg, b, self.compute_dtype),
            "k": jnp.zeros((inv, b, max_len, kv, hd), dtype),
            "v": jnp.zeros((inv, b, max_len, kv, hd), dtype),
        }

    def forward(self, params, state, tokens, positions, *, embeds=None,
                fresh=False, all_logits=False):
        if "block_tables" in state:
            return self._forward_paged(params, state, tokens, positions,
                                       all_logits=all_logits)
        cfg = self.cfg
        x = embeds if embeds is not None else self._embed(params, tokens)
        x, mamba_states, ks, vs = zamba_mod.zamba_chunk(
            cfg, params, x, positions, state, fresh=fresh)
        logits = self._chunk_logits(params, x, positions, all_logits)
        return {**state, "mamba": mamba_states,
                "k": jnp.stack(ks).astype(state["k"].dtype),
                "v": jnp.stack(vs).astype(state["v"].dtype)}, logits

    def _forward_paged(self, params, state, tokens, positions,
                       all_logits=False):
        cfg = self.cfg
        tables = state["block_tables"]
        kp, vp, mamba = state["k"], state["v"], state["mamba"]
        ks, vs = state.get("k_scale"), state.get("v_scale")

        def step_token(x, pos):
            nonlocal kp, vp, mamba, ks, vs
            x, mamba, kp, vp, ks, vs = zamba_mod.zamba_paged_step(
                cfg, params, x, mamba, kp, vp, tables, pos, ks, vs)
            return x

        logits, lengths = self._paged_chunk_driver(params, state, tokens,
                                                   positions, step_token,
                                                   all_logits=all_logits)
        new = {**state, "mamba": mamba, "k": kp, "v": vp,
               "lengths": lengths}
        if ks is not None:
            new["k_scale"], new["v_scale"] = ks, vs
        return new, logits

    @property
    def paged_kv_layers(self) -> int:
        return zamba_mod.n_attn_invocations(self.cfg)

    def paged_state_extras(self, n_slots: int) -> dict:
        """Per-slot mamba state pools riding beside the paged KV blocks —
        what lets the hybrid family join the paged path."""
        return {"mamba": zamba_mod.zamba_mamba_init(self.cfg, n_slots,
                                                    self.compute_dtype)}

    def seq_state_specs(self, shape: ShapeConfig):
        return zamba_mod.zamba_state_specs(self.cfg, shape.global_batch,
                                           shape.seq_len)

    def seq_state_axes(self, shape: ShapeConfig):
        mst = {"ssm": ("batch", "_", "_", "_"), "conv": ("batch", "_", "_")}
        kvax = ("_", "batch", "kv_seq", "_", "_")
        return {"mamba": [mst for _ in range(self.cfg.n_layers)],
                "k": kvax, "v": kvax}


# ============================== xLSTM =======================================


class XLSTMLM(BaseLM):
    def param_defs(self):
        cfg = self.cfg
        defs = _embed_defs(cfg)
        for i, kind in enumerate(cfg.block_pattern):
            if kind == "m":
                defs[f"block_{i}"] = xlstm_mod.mlstm_block_defs(cfg)
            else:
                defs[f"block_{i}"] = xlstm_mod.slstm_block_defs(cfg)
        return defs

    def loss(self, params, batch):
        cfg = self.cfg
        x = self._embed(params, batch["tokens"])
        x = shard_act(x, "batch", "seq", "embed")
        for i, kind in enumerate(cfg.block_pattern):
            blk = params[f"block_{i}"]
            if kind == "m":
                f = jax.checkpoint(
                    lambda bp, xx: xlstm_mod.apply_mlstm_block(cfg, bp, xx))
            else:
                f = jax.checkpoint(
                    lambda bp, xx: xlstm_mod.apply_slstm_block(cfg, bp, xx))
            x = f(blk, x)
        ce = self._ce(params, x, batch["labels"], batch.get("mask"))
        return ce, {"ce": ce}

    # ---- chunk-oriented serving ----

    def init_seq_state(self, params, max_len, *, batch=None,
                       batch_size=None, dtype="bfloat16"):
        b = batch_size if batch_size is not None else len(batch["tokens"])
        return {"blocks": xlstm_mod.xlstm_init_states(self.cfg, b,
                                                      self.compute_dtype)}

    def forward(self, params, state, tokens, positions, *, embeds=None,
                fresh=False, all_logits=False):
        cfg = self.cfg
        x = embeds if embeds is not None else self._embed(params, tokens)
        T = x.shape[1]
        new_states = []
        for i, kind in enumerate(cfg.block_pattern):
            blk = params[f"block_{i}"]
            st = None if fresh else state["blocks"][i]
            if kind == "m":
                if T == 1 and not fresh:
                    x, st = xlstm_mod.mlstm_block_decode(cfg, blk, x, st)
                else:
                    x, st = xlstm_mod.mlstm_block_prefill(cfg, blk, x,
                                                          state=st)
            else:
                if T == 1 and not fresh:
                    x, st = xlstm_mod.slstm_block_decode(cfg, blk, x, st)
                else:
                    x, st = xlstm_mod.slstm_block_prefill(cfg, blk, x,
                                                          state=st)
            new_states.append(st)
        logits = self._chunk_logits(params, x, positions, all_logits)
        return {**state, "blocks": new_states}, logits

    def seq_state_specs(self, shape: ShapeConfig):
        return {
            "blocks": xlstm_mod.xlstm_state_specs(self.cfg,
                                                  shape.global_batch),
        }

    def seq_state_axes(self, shape: ShapeConfig):
        mst = {"C": ("batch", "_", "_", "_"), "n": ("batch", "_", "_"),
               "m": ("batch", "_"), "conv": ("batch", "_", "_")}
        sst = {"c": ("batch", "_", "_"), "n": ("batch", "_", "_"),
               "m": ("batch", "_", "_"), "h": ("batch", "_", "_")}
        return {"blocks": [mst if k == "m" else sst
                           for k in self.cfg.block_pattern]}


# ============================== factory =====================================


def build_model(cfg: ModelConfig, *, moe_group: int | None = None) -> BaseLM:
    if cfg.family in ("dense", "moe", "vlm"):
        return DecoderLM(cfg, moe_group=moe_group or moe_mod.DEFAULT_GROUP)
    if cfg.family == "audio":
        return WhisperLM(cfg)
    if cfg.family == "hybrid":
        return ZambaLM(cfg)
    if cfg.family == "ssm":
        return XLSTMLM(cfg)
    raise ValueError(cfg.family)
