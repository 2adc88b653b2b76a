"""Paged KV cache: fixed-size blocks, block tables, refcounted sharing.

The decode KV cache lives in two device pools of shape
(layers, n_blocks, block_size, kv_heads, head_dim); a sequence owns an
ordered list of physical block ids (its *block table*) and logical
position ``p`` lives at block ``table[p // bs]``, offset ``p % bs``.
This is the vLLM/PagedAttention layout, which is also what the paper's
serving story needs: KV capacity is the binding constraint at scale
(§VI-B4; arXiv:2505.09343 §KV), and paging turns "longest request
reserves worst-case memory for everyone" into "every request holds
exactly ``ceil(len / bs)`` blocks".

Three host-side mechanisms around the device pools:

* **free-list allocator** — LIFO over block ids 1..n_blocks-1.  Block 0
  is reserved as a scratch block: idle engine slots point their table
  (and therefore their token writes) at it, so the jitted decode step
  never needs a batch-size-dependent active mask.
* **refcounts** — a block returns to the free list only when its last
  owner drops it, which is what makes prefix sharing safe: a prefix
  entry and any number of live sequences can reference the same block.
* **prefix index** — rolling-hash(token prefix) -> (block ids, length,
  first greedy token).  A hit *restores by block reference*: full
  blocks are shared via incref, and only the trailing partial block is
  copied (the new sequence appends into it — copy-on-write).  The
  registering sequence keeps appending its own decode tokens into its
  partial tail block, but only at offsets >= length, which a restored
  sequence masks (attention is masked to ``< length``) and then
  overwrites as it decodes — so registration never blocks the owner.
  Contrast ``serve_lib.KVContextCache``, which round-trips the whole
  dense cache through 3FS bytes; here a hit is O(1 block copy).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.attention import KV_DTYPES, quantize_kv
# One prefix-identity function across the serving stack: the paged index
# and the 3FS context cache must agree on what "same prompt" means.
from repro.serve_lib import _prefix_key


def _pool_update(n_pools: int):
    """jit a pool-writing function, donating its first ``n_pools``
    arguments where donation works, so admissions/COW copies update in
    place instead of rewriting O(pool) HBM; CPU rejects donation with a
    warning, so keep it off there.  The backend is asked at the first
    call, never at import.  Callers immediately rebind self.k/v."""
    def wrap(fn):
        @functools.cache
        def jitted():
            donate = (tuple(range(n_pools))
                      if jax.default_backend() in ("tpu", "gpu") else ())
            return jax.jit(fn, donate_argnums=donate)

        @functools.wraps(fn)
        def call(*args):
            return jitted()(*args)
        return call
    return wrap


@_pool_update(2)
def _scatter_blocks(k_pool, v_pool, k, v, block_ids):
    """Write dense prefill K/V (L, nblk*bs, kv, hd) into pool blocks."""
    L, nb, bs, kvh, hd = k_pool.shape
    kb = k.reshape(L, -1, bs, kvh, hd).astype(k_pool.dtype)
    vb = v.reshape(L, -1, bs, kvh, hd).astype(v_pool.dtype)
    return k_pool.at[:, block_ids].set(kb), v_pool.at[:, block_ids].set(vb)


@_pool_update(4)
def _scatter_blocks_quant(k_pool, v_pool, ks_pool, vs_pool, k, v, block_ids):
    """Quantize-on-write for sub-bf16 pools: dense prefill K/V
    (L, nblk*bs, kv, hd) is quantized per token entry (absmax over
    kv x hd) and scattered with its scales beside it."""
    L, nb, bs, kvh, hd = k_pool.shape
    kq, ks = quantize_kv(k, k_pool.dtype)
    vq, vs = quantize_kv(v, v_pool.dtype)
    kb = kq.reshape(L, -1, bs, kvh, hd)
    vb = vq.reshape(L, -1, bs, kvh, hd)
    ksb = ks.reshape(L, -1, bs)
    vsb = vs.reshape(L, -1, bs)
    return (k_pool.at[:, block_ids].set(kb),
            v_pool.at[:, block_ids].set(vb),
            ks_pool.at[:, block_ids].set(ksb),
            vs_pool.at[:, block_ids].set(vsb))


@_pool_update(2)
def _set_blocks(k_pool, v_pool, kb, vb, block_ids):
    """Write already-blocked K/V (L, n, bs, kv, hd) into pool blocks —
    the cross-replica import path (contents arrive pre-blocked and, for
    quantized pools, pre-quantized: no requantization, bit-identical)."""
    return (k_pool.at[:, block_ids].set(kb.astype(k_pool.dtype)),
            v_pool.at[:, block_ids].set(vb.astype(v_pool.dtype)))


@_pool_update(4)
def _set_blocks_quant(k_pool, v_pool, ks_pool, vs_pool, kb, vb, ksb, vsb,
                      block_ids):
    return (k_pool.at[:, block_ids].set(kb.astype(k_pool.dtype)),
            v_pool.at[:, block_ids].set(vb.astype(v_pool.dtype)),
            ks_pool.at[:, block_ids].set(ksb.astype(ks_pool.dtype)),
            vs_pool.at[:, block_ids].set(vsb.astype(vs_pool.dtype)))


@_pool_update(2)
def _copy_block(k_pool, v_pool, src, dst):
    return (k_pool.at[:, dst].set(k_pool[:, src]),
            v_pool.at[:, dst].set(v_pool[:, src]))


@_pool_update(4)
def _copy_block_quant(k_pool, v_pool, ks_pool, vs_pool, src, dst):
    """COW copy carrying the per-token scale rows with the block — a
    quantized block without its scales dequantizes to garbage, so the
    two must never separate (the prefix-restore regression)."""
    return (k_pool.at[:, dst].set(k_pool[:, src]),
            v_pool.at[:, dst].set(v_pool[:, src]),
            ks_pool.at[:, dst].set(ks_pool[:, src]),
            vs_pool.at[:, dst].set(vs_pool[:, src]))


class PagedKVCache:
    """Device block pools + host allocator/refcounts/prefix index."""

    def __init__(self, *, layers: int, n_blocks: int, block_size: int,
                 kv_heads: int, head_dim: int, dtype: str = "bfloat16",
                 kv_dtype: str | None = None):
        assert n_blocks >= 2, "need at least scratch + 1 allocatable block"
        # kv_dtype (one of models.attention.KV_DTYPES) takes precedence
        # over dtype; sub-bf16 choices flip the cache into quantized mode
        # where per-token absmax scales (L, n_blocks, bs) f32 live beside
        # the pools and every write goes through quantize_kv.
        pool_dtype = KV_DTYPES[kv_dtype] if kv_dtype is not None else dtype
        self.quantized = jnp.dtype(pool_dtype) not in (
            jnp.dtype(jnp.bfloat16), jnp.dtype(jnp.float32))
        shape = (layers, n_blocks, block_size, kv_heads, head_dim)
        self.k = jnp.zeros(shape, pool_dtype)
        self.v = jnp.zeros(shape, pool_dtype)
        if self.quantized:
            self.k_scale = jnp.ones((layers, n_blocks, block_size),
                                    jnp.float32)
            self.v_scale = jnp.ones((layers, n_blocks, block_size),
                                    jnp.float32)
        else:
            self.k_scale = None
            self.v_scale = None
        self.n_blocks = n_blocks
        self.block_size = block_size
        self.refcount = np.zeros(n_blocks, np.int64)
        self.refcount[0] = 1                       # scratch, never freed
        self._free = list(range(n_blocks - 1, 0, -1))   # pop() -> low ids
        # key -> (block ids, length, first greedy token, extras pytree)
        self._prefix: dict[str, tuple] = {}
        self._prefix_lru: list[str] = []
        self.hits = 0
        self.misses = 0
        # Cluster hook: called as on_prefix_evict(key, ids, length,
        # first_token, extras) *before* an LRU-reclaimed prefix entry's
        # blocks are freed — the engine publishes the block contents to
        # the 3FS-backed cluster prefix store here (DESIGN.md §11).
        self.on_prefix_evict = None

    # ------------------------------ allocator ------------------------------

    @property
    def num_free(self) -> int:
        return len(self._free)

    def blocks_for(self, n_tokens: int) -> int:
        return -(-n_tokens // self.block_size)

    def alloc(self, n: int) -> list[int] | None:
        """n fresh blocks at refcount 1, or None if the pool is exhausted
        (caller decides: reclaim prefixes, evict, or wait)."""
        if n > len(self._free):
            return None
        ids = [self._free.pop() for _ in range(n)]
        self.refcount[ids] = 1
        return ids

    def incref(self, ids) -> None:
        for i in ids:
            self.refcount[i] += 1

    def free(self, ids) -> None:
        """Drop one reference per id; exhausted blocks rejoin the free
        list (their stale K/V needs no scrubbing — readers mask by
        length and writers overwrite before extending it)."""
        for i in ids:
            self.refcount[i] -= 1
            assert self.refcount[i] >= 0, f"double free of block {i}"
            if self.refcount[i] == 0:
                self._free.append(i)

    def rollback(self, blocks: list, n_tokens: int) -> list:
        """SeqState rollback primitive: truncate a sequence's block
        table to cover exactly ``n_tokens`` cached positions, dropping
        the tail references (speculative verify wrote K/V past the
        accepted position; un-accepted blocks return to the pool here).

        Cheap by construction: rollback is pure host bookkeeping —
        device pools are never touched.  Stale entries left *inside*
        the kept tail block are invisible (readers mask to the caller's
        length) and are later overwritten by the identical
        quantize-on-write path (``quantize_kv`` is a pure function of
        the value, so a re-written fp8/int8 entry and its scale are
        bit-identical — the re-quantize consistency tests pin this).
        Shared/COW prefix blocks before the boundary keep their
        refcounts: only references *past* ``blocks_for(n_tokens)`` are
        dropped.  Returns the truncated table (a new list).
        """
        keep = self.blocks_for(n_tokens)
        if keep >= len(blocks):
            return list(blocks)
        self.free(blocks[keep:])
        return list(blocks[:keep])

    # ---------------------------- device writes ----------------------------

    def write_prompt(self, k, v, block_ids) -> None:
        """Scatter fresh prefill K/V (L, s, kv, hd) into ``block_ids``."""
        bs = self.block_size
        s = k.shape[1]
        pad = -s % bs
        if pad:
            cfgpad = ((0, 0), (0, pad), (0, 0), (0, 0))
            k = jnp.pad(k, cfgpad)
            v = jnp.pad(v, cfgpad)
        ids = jnp.asarray(block_ids, jnp.int32)
        if self.quantized:
            self.k, self.v, self.k_scale, self.v_scale = (
                _scatter_blocks_quant(self.k, self.v,
                                      self.k_scale, self.v_scale,
                                      k, v, ids))
        else:
            self.k, self.v = _scatter_blocks(self.k, self.v, k, v, ids)

    def export_blocks(self, block_ids) -> dict:
        """Device-get the contents of ``block_ids`` as host arrays:
        ``{"k", "v"[, "k_scale", "v_scale"]}`` shaped (L, n, bs, ...).
        Quantized pools export their raw sub-bf16 codes *with* the
        per-token scale rows, so a later import is bit-identical — the
        SeqState-handoff / cluster-prefix-cache wire format."""
        ids = np.asarray(list(block_ids), np.int32)
        out = {"k": np.asarray(jax.device_get(self.k[:, ids])),
               "v": np.asarray(jax.device_get(self.v[:, ids]))}
        if self.quantized:
            out["k_scale"] = np.asarray(jax.device_get(self.k_scale[:, ids]))
            out["v_scale"] = np.asarray(jax.device_get(self.v_scale[:, ids]))
        return out

    def import_blocks(self, block_ids, data: dict) -> None:
        """Write exported block contents into ``block_ids`` of *this*
        pool (caller allocs).  Shapes must match the pool layout — a
        mismatch means the artifact came from a differently-configured
        replica, which the cluster key scheme is meant to preclude."""
        L, _, bs, kvh, hd = self.k.shape
        kb = np.asarray(data["k"])
        if kb.shape[0] != L or kb.shape[2:] != (bs, kvh, hd):
            raise ValueError(
                f"imported blocks {kb.shape} do not fit pool layout "
                f"(L={L}, bs={bs}, kv={kvh}, hd={hd})")
        if len(block_ids) != kb.shape[1]:
            raise ValueError(f"{len(block_ids)} target blocks for "
                             f"{kb.shape[1]} imported blocks")
        ids = jnp.asarray(list(block_ids), jnp.int32)
        if self.quantized:
            if "k_scale" not in data:
                raise ValueError("quantized pool import needs scale rows")
            self.k, self.v, self.k_scale, self.v_scale = _set_blocks_quant(
                self.k, self.v, self.k_scale, self.v_scale,
                jnp.asarray(kb, self.k.dtype),
                jnp.asarray(np.asarray(data["v"]), self.v.dtype),
                jnp.asarray(np.asarray(data["k_scale"]), jnp.float32),
                jnp.asarray(np.asarray(data["v_scale"]), jnp.float32), ids)
        else:
            self.k, self.v = _set_blocks(
                self.k, self.v, jnp.asarray(kb, self.k.dtype),
                jnp.asarray(np.asarray(data["v"]), self.v.dtype), ids)

    def copy_block(self, src: int) -> int | None:
        """Copy-on-write: duplicate one block into a fresh allocation."""
        dst = self.alloc(1)
        if dst is None:
            return None
        if self.quantized:
            self.k, self.v, self.k_scale, self.v_scale = _copy_block_quant(
                self.k, self.v, self.k_scale, self.v_scale, src, dst[0])
        else:
            self.k, self.v = _copy_block(self.k, self.v, src, dst[0])
        return dst[0]

    # --------------------------- prefix sharing ----------------------------

    def register_prefix(self, tokens: np.ndarray, block_ids, length: int,
                        first_token: int, extras=None) -> None:
        """Pin ``block_ids`` (incref) under the prefix hash so later
        identical prompts restore by reference.  ``first_token`` is the
        greedy continuation from the prefill logits — the one piece of
        state a block-level restore cannot reconstruct.  ``extras`` is
        an optional pytree of non-KV sequence state the blocks cannot
        carry (the hybrid family's mamba states after the prompt)."""
        key = _prefix_key(tokens)
        if key in self._prefix:
            return
        self.incref(block_ids)
        self._prefix[key] = (tuple(block_ids), length, first_token, extras)
        self._prefix_lru.append(key)

    def lookup_prefix(self, tokens: np.ndarray):
        """Exact-prefix hit -> (block_ids, length, first_token, extras)
        with the new sequence holding its own references; None on miss.

        Full blocks are shared (incref).  A partial trailing block is
        copied because the restored sequence will append into it; if the
        prompt ends exactly on a block boundary every block is shared
        and the first decode token opens a fresh block anyway.
        """
        key = _prefix_key(tokens)
        ent = self._prefix.get(key)
        if ent is None:
            self.misses += 1
            return None
        ids, length, first_token, extras = ent
        if length % self.block_size == 0:
            self.incref(ids)
            blocks = list(ids)
        else:
            tail = self.copy_block(ids[-1])
            if tail is None:
                # exhausted pool: drop other LRU prefixes before giving
                # up a restore that needs exactly one block
                self.reclaim(1, keep=(key,))
                tail = self.copy_block(ids[-1])
            if tail is None:
                self.misses += 1
                return None
            self.incref(ids[:-1])
            blocks = list(ids[:-1]) + [tail]
        self.hits += 1
        if key in self._prefix_lru:     # refresh LRU position
            self._prefix_lru.remove(key)
            self._prefix_lru.append(key)
        return blocks, length, first_token, extras

    def _drop_prefix_entry(self, key: str) -> None:
        """Release one prefix entry, publishing it through the
        ``on_prefix_evict`` hook (while its blocks are still readable)
        before dropping the index's references."""
        self._prefix_lru.remove(key)
        ids, length, first, extras = self._prefix.pop(key)
        if self.on_prefix_evict is not None:
            self.on_prefix_evict(key, ids, length, first, extras)
        self.free(ids)

    def reclaim(self, n_blocks: int, *, keep: tuple = ()) -> bool:
        """Release LRU prefix entries until ``n_blocks`` are allocatable.
        Entries named in ``keep`` are spared (e.g. the prefix currently
        being restored, whose blocks must not be decref'd mid-restore)."""
        while self.num_free < n_blocks:
            key = next((k for k in self._prefix_lru if k not in keep), None)
            if key is None:
                break
            self._drop_prefix_entry(key)
        return self.num_free >= n_blocks

    def drop_prefixes(self) -> int:
        """Release every prefix entry (each publishes through the
        ``on_prefix_evict`` hook first) — the cluster's write-back flush
        to the 3FS store.  Returns the number of entries dropped."""
        keys = list(self._prefix_lru)
        for key in keys:
            self._drop_prefix_entry(key)
        return len(keys)

    @property
    def hit_rate(self) -> float:
        total = self.hits + self.misses
        return self.hits / total if total else 0.0
