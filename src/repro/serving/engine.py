"""Continuous-batching decode engine over the paged KV cache.

``ServingEngine`` keeps a fixed number of decode *slots* (the jitted
step's batch dimension) and a FIFO request queue.  Each engine step:

1. **admits** queued requests into free slots — prefilling their prompt
   (or restoring it by block reference on a prefix-cache hit) and
   scattering the K/V into freshly allocated blocks;
2. runs **one fused chunk (T=1) for every occupied slot at once** via
   ``model.forward`` on the paged SeqState: per-slot lengths and block
   tables live *inside* the state, so a request that joined this step
   decodes beside one that is 500 tokens deep — no lockstep, no
   re-prefill of the running batch;
3. **samples** the next token per slot (per-request temperature/top-k
   with per-slot PRNG keys threaded through the SeqState; greedy is
   the deterministic default) and **retires** finished requests,
   returning their blocks to the pool.

Compilation discipline: the decode step's shapes depend only on
(max_slots, table_width), with table widths bucketed to powers of two
— O(log n_blocks) compiled variants.  Prompt prefill is **bucketed**
too: the dense scratch SeqState's capacity rounds up to a power of
two, the prompt runs through ``model.forward`` as one padded chunk (or
``prefill_chunk``-sized chunks, interleaved with decode ticks so
admission never stalls the running batch), and the position-indexed
last-token logit gather reads the real last token — so prompts of N
distinct lengths compile O(log max_prompt) variants instead of N.
The hybrid family pages its attention blocks while its per-slot mamba
states ride in the engine's extras pools (padding would corrupt a
recurrence, so hybrid chunks are exact-length: compile count is
bounded by the chunk size, not the prompt length).

Eviction: ``evict(rid)`` (or pool exhaustion mid-decode) frees a
running request's blocks and re-queues it from scratch; decode is
deterministic given (seed, position) — greedy trivially, sampling via
``fold_in(seed, rid, position)`` keys — so a re-admitted request reproduces
the same tokens.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.serve_lib import _prefix_key
from repro.serving.paged_cache import PagedKVCache
from repro.serving.speculative import (longest_accept, make_drafter,
                                       spec_accept)
from repro.serving.stats import serving_stats
from repro.telemetry import Registry, now, span

_PAGED_FAMILIES = ("dense", "moe", "hybrid")


def _pow2_at_least(n: int, floor: int = 1) -> int:
    w = max(floor, 1)
    while w < n:
        w *= 2
    return w


@dataclasses.dataclass
class Request:
    prompt: np.ndarray                 # (s,) int32 token ids
    max_new_tokens: int
    arrival: int = 0                   # earliest admissible engine step
    rid: int = -1
    # -- sampling (greedy when temperature == 0) --
    temperature: float = 0.0
    top_k: int = 0
    seed: int = 0
    base_key: np.ndarray | None = None      # fold_in(PRNGKey(seed), rid)
    # -- runtime state (engine-owned) --
    tokens: list = dataclasses.field(default_factory=list)   # generated
    blocks: list = dataclasses.field(default_factory=list)   # block table
    length: int = 0                    # cache occupancy (tokens written)
    slot: int = -1
    admitted_at: int = -1
    status: str = "queued"             # queued | prefilling | running | done
    # -- cluster handoff (prefill/decode disaggregation) --
    keep_blocks: bool = False          # retain blocks at done for export
    artifact: dict | None = None       # imported prefill (skips prefill)
    export_extras: dict | None = None  # non-KV state stashed for export
    # -- telemetry (host wall clock; recorded at completion, not drain) --
    t_submit: float | None = None      # submit() call
    t_admit: float | None = None       # first admission attempt starts
    t_first: float | None = None       # first token exists (TTFT endpoint)
    t_last: float | None = None        # previous token (TPOT interval base)
    n_evictions: int = 0
    tpot_sum: float = 0.0              # per-token decode intervals
    tpot_n: int = 0

    @property
    def done(self) -> bool:
        return len(self.tokens) >= self.max_new_tokens

    @property
    def greedy(self) -> bool:
        return self.temperature <= 0.0


class _PrefillJob:
    """An in-flight chunked prefill: one chunk advances per engine step,
    interleaved with decode ticks for the running slots.  Pool blocks
    are reserved up front so a full pool stalls admission *before* any
    prefill compute is spent."""

    def __init__(self, req, state, chunks, blocks):
        self.req = req
        self.state = state
        self.chunks = chunks           # list of (tokens, positions) np
        self.blocks = blocks           # pre-allocated pool blocks
        self.next = 0
        self.logits = None

    @property
    def finished(self) -> bool:
        return self.next >= len(self.chunks)


class ServingEngine:
    def __init__(self, model, params, *, n_blocks: int = 256,
                 block_size: int = 16, max_slots: int = 4,
                 pool_dtype: str = "bfloat16", share_prefixes: bool = True,
                 min_table_width: int = 2, prefill_chunk: int = 0,
                 temperature: float = 0.0, top_k: int = 0, seed: int = 0,
                 kv_dtype: str | None = None, prefill_role: bool = False,
                 prefix_store=None, spec_mode: str = "off",
                 draft_k: int = 4, draft_model=None, draft_params=None,
                 draft_max_len: int = 512, ngram_max: int = 3,
                 ngram_min: int = 1):
        cfg = model.cfg
        if cfg.family not in _PAGED_FAMILIES:
            raise ValueError(
                f"paged serving needs per-layer attention KV blocks; "
                f"family {cfg.family!r} is unsupported (use decode_impl="
                f"'dense')")
        self.model = model
        self.params = params
        self.cfg = cfg
        self.max_slots = max_slots
        self.share_prefixes = share_prefixes
        # Floor for the bucketed block-table width: size it to the
        # expected max context to pin the step to one compiled shape
        # (e.g. benchmarking, or latency-critical serving).
        self.min_table_width = min_table_width
        # Prefill chunking: 0 = one bucketed whole-prompt chunk per
        # admission; >0 = advance one prefill chunk per engine step,
        # interleaved with decode ticks.  Families with a carried
        # recurrence get exact-length chunks (no padding through state).
        self.prefill_chunk = prefill_chunk
        self.pad_prefill = model.prefill_padding_ok
        # Disaggregation: a prefill-role replica runs prompts to their
        # first token (max_new_tokens=1, keep_blocks=True) and hands the
        # blocks off via export_request(); chunked prefill advances even
        # with no running decode slots so the cluster loop can interleave
        # replicas.  prefix_store (cluster-wide 3FS-backed cache) makes
        # locally-evicted prefix entries restorable by any replica.
        self.prefill_role = prefill_role
        self.prefix_store = prefix_store
        # Engine-level sampling defaults; submit() overrides per request.
        self.temperature = temperature
        self.top_k = top_k
        self.seed = seed
        self.cache = PagedKVCache(
            layers=model.paged_kv_layers, n_blocks=n_blocks,
            block_size=block_size, kv_heads=cfg.n_kv_heads,
            head_dim=cfg.head_dim, dtype=pool_dtype, kv_dtype=kv_dtype)
        # Non-KV per-slot sequence state (hybrid mamba); {} otherwise.
        self._extras = model.paged_state_extras(max_slots)
        self._extras_keys = tuple(self._extras)

        # Speculative decode mode (DESIGN.md §12): a drafter proposes up
        # to draft_k tokens per slot, the step verifies them as one
        # (max_slots, draft_k + 1) chunk through the same paged kernel,
        # and the engine keeps the longest accepted prefix plus one
        # bonus token — 1..draft_k+1 tokens per cache sweep.  "off"
        # keeps the plain one-token tick.
        self.spec_mode = spec_mode
        self.draft_k = int(draft_k)
        self.drafter = make_drafter(
            spec_mode, ngram_max=ngram_max, ngram_min=ngram_min,
            draft_model=draft_model, draft_params=draft_params,
            draft_max_len=draft_max_len, target_vocab=cfg.vocab_size)
        if self.drafter is not None and self.draft_k < 1:
            raise ValueError("draft_k must be >= 1 when speculating")

        # Per-engine metrics registry (standalone instance: concurrent
        # engines must not share counters).  Trace counters live here:
        # each jit cache miss re-traces the wrapped fn, so they count
        # compiled variants (the O(log) assertions); request latency
        # histograms (TTFT / per-token TPOT / queue wait) are recorded
        # at request completion in step(), *before* run() clears _done.
        self.metrics = Registry("engine")
        self._c_prefill_traces = self.metrics.counter("engine.prefill_traces")
        self._c_decode_traces = self.metrics.counter("engine.decode_traces")
        self._c_evictions = self.metrics.counter("engine.evictions")
        self._c_completed = self.metrics.counter("engine.requests_completed")
        self._h_ttft = self.metrics.histogram("engine.ttft_s")
        self._h_tpot = self.metrics.histogram("engine.tpot_s")
        self._h_queue = self.metrics.histogram("engine.queue_wait_s")
        self._c_store_hits = self.metrics.counter("engine.store_hits")
        # speculation: tokens emitted per slot per verify chunk (1..k+1)
        # and per-chunk acceptance fraction (accepted drafts / proposed)
        self._h_spec_tps = self.metrics.histogram(
            "engine.spec_tokens_per_step")
        self._h_spec_acc = self.metrics.histogram("engine.spec_accept_rate")
        if prefix_store is not None:
            # write-back: LRU-evicted prefix entries publish to the
            # cluster store while their blocks are still readable
            self.cache.on_prefix_evict = self._publish_prefix

        def _chunk_fn(params, state, tokens, positions, fresh):
            self._c_prefill_traces.inc()
            return model.forward(params, state, tokens, positions,
                                 fresh=fresh)
        self._chunk = jax.jit(_chunk_fn, static_argnames=("fresh",))

        def _decode_fn(params, state, tokens, positions):
            self._c_decode_traces.inc()
            return model.forward(params, state, tokens, positions)
        # Donate the paged state where donation works (accelerators):
        # the step updates one token per slot, so without buffer
        # aliasing XLA would copy the whole O(pool) cache every step.
        # CPU rejects donation with a warning, so keep it off there.
        donate = (1,) if jax.default_backend() in ("tpu", "gpu") else ()
        self._step = jax.jit(_decode_fn, donate_argnums=donate)

        def _verify_fn(params, state, tokens, positions):
            self._c_decode_traces.inc()
            return model.forward(params, state, tokens, positions,
                                 all_logits=True)
        # The verify chunk must NOT donate when recurrent extras exist:
        # the hybrid rollback re-runs the chunk from the pre-verify
        # extras snapshot, which donation would have invalidated.
        self._verify = jax.jit(
            _verify_fn,
            donate_argnums=donate if not self._extras_keys else ())
        self._accept = jax.jit(spec_accept)

        def _sample_fn(logits, base_keys, positions, temps, topks):
            # per-token key = fold_in(request base key, position), folded
            # on device so the decode loop pays no host dispatches
            keys = jax.vmap(jax.random.fold_in)(base_keys, positions)
            V = logits.shape[-1]
            greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            lf = logits.astype(jnp.float32)
            srt = jnp.sort(lf, axis=-1)                        # ascending
            kidx = jnp.clip(V - topks, 0, V - 1)
            thr = jnp.take_along_axis(srt, kidx[:, None], axis=1)[:, 0]
            mask = (topks > 0)[:, None] & (lf < thr[:, None])
            scaled = jnp.where(mask, -jnp.inf, lf) \
                / jnp.maximum(temps, 1e-6)[:, None]
            sampled = jax.vmap(jax.random.categorical)(keys, scaled)
            return jnp.where(temps > 0, sampled.astype(jnp.int32), greedy)
        self._sample = jax.jit(_sample_fn)

        self._scatter_extras = jax.jit(
            lambda pools, one, slot: jax.tree_util.tree_map(
                lambda P, o: P.at[slot].set(o[0].astype(P.dtype)),
                pools, one))

        self._slots: list[Request | None] = [None] * max_slots
        self._queue: list[Request] = []
        self._done: dict[int, Request] = {}
        self._job: _PrefillJob | None = None
        self._next_rid = 0
        self._admission_seq = 0    # monotone: exact FIFO eviction priority
        self.step_count = 0
        # Per-request completion records ({rid, ttft_s, ...}); bounded so
        # a long-lived server doesn't retain every historical request.
        self._request_log: list[dict] = []
        self._request_log_cap = 10_000

    # compat accessors over the registry-backed counters (pre-telemetry
    # these were plain ints mutated in place)
    @property
    def prefill_traces(self) -> int:
        return self._c_prefill_traces.value

    @property
    def decode_traces(self) -> int:
        return self._c_decode_traces.value

    @property
    def evictions(self) -> int:
        return self._c_evictions.value

    # ------------------------------- intake --------------------------------

    def submit(self, prompt, max_new_tokens: int, arrival: int = 0,
               temperature: float | None = None, top_k: int | None = None,
               seed: int | None = None, *, keep_blocks: bool = False,
               t_submit: float | None = None) -> int:
        """Queue a request.  ``keep_blocks`` retains its pool blocks at
        completion for ``export_request`` (the cluster's prefill leg —
        pair with ``max_new_tokens=1``); ``t_submit`` carries the true
        submit time through a multi-engine pipeline so TTFT covers the
        whole path, not just this engine."""
        req = Request(prompt=np.asarray(prompt, np.int32).reshape(-1),
                      max_new_tokens=max_new_tokens, arrival=arrival,
                      temperature=self.temperature if temperature is None
                      else temperature,
                      top_k=self.top_k if top_k is None else top_k,
                      seed=self.seed if seed is None else seed,
                      rid=self._next_rid, keep_blocks=keep_blocks,
                      t_submit=now() if t_submit is None else t_submit)
        self._next_rid += 1
        self._queue.append(req)
        return req.rid

    def submit_prefilled(self, artifact: dict, max_new_tokens: int,
                         arrival: int = 0, temperature: float | None = None,
                         top_k: int | None = None,
                         seed: int | None = None) -> int:
        """Queue a request whose prompt KV arrives as an exported
        handoff artifact (see ``export_request``): admission imports the
        blocks instead of prefilling, so this engine never runs the
        prompt — the decode leg of a disaggregated cluster."""
        req = Request(prompt=np.asarray(artifact["prompt"],
                                        np.int32).reshape(-1),
                      max_new_tokens=max_new_tokens, arrival=arrival,
                      temperature=self.temperature if temperature is None
                      else temperature,
                      top_k=self.top_k if top_k is None else top_k,
                      seed=self.seed if seed is None else seed,
                      rid=self._next_rid, artifact=artifact,
                      # explicit None check: a legitimate t_submit of 0.0
                      # (epoch-anchored clocks, synthetic traces) must not
                      # silently reset the TTFT clock to "now"
                      t_submit=(now() if artifact.get("t_submit") is None
                                else artifact["t_submit"]))
        self._next_rid += 1
        self._queue.append(req)
        return req.rid

    # ------------------------------ sampling -------------------------------

    def _base_key(self, req: Request) -> np.ndarray:
        """Per-request PRNG base: fold_in(PRNGKey(seed), rid) — stable
        across eviction/requeue (so replay resamples identically) and
        rid-decorrelated between same-prompt requests sharing the
        engine-level seed.  The per-token key adds a fold over the
        token's absolute position, on device inside ``_sample``."""
        if req.base_key is None:
            req.base_key = np.asarray(jax.random.fold_in(
                jax.random.PRNGKey(req.seed), req.rid), np.uint32)
        return req.base_key

    def _pick_token(self, req: Request, logits_row, position: int) -> int:
        if req.greedy:
            return int(jnp.argmax(logits_row))
        tok = self._sample(logits_row[None],
                           jnp.asarray(self._base_key(req))[None],
                           jnp.asarray([position], jnp.int32),
                           jnp.asarray([req.temperature], jnp.float32),
                           jnp.asarray([req.top_k], jnp.int32))
        return int(tok[0])

    # ------------------------------ admission ------------------------------

    def _admit(self) -> None:
        """FIFO admission: prefill-or-restore into free slots while the
        pool can hold the prompt (strict order — no head-of-line skip,
        so admission latency stays predictable)."""
        while self._queue and None in self._slots and self._job is None:
            req = self._queue[0]
            if req.arrival > self.step_count:
                break
            with span("engine.admit", rid=req.rid,
                      prompt_len=len(req.prompt)) as admit:
                hits = self.cache.hits
                started = self._start(req)
                admit.set_metadata(prefix_hit=self.cache.hits > hits)
            if not started:
                break
            self._queue.pop(0)

    def _prefill_chunks(self, prompt: np.ndarray, cap: int) -> list:
        """Split a prompt into (tokens, positions) chunk inputs.

        Attention families pad to the capacity bucket (position -1 marks
        padding: its cache write is dropped and the logit gather skips
        it), so the compiled-shape count stays O(log max_prompt).
        Recurrent-carrying families get exact-length chunks instead."""
        s = len(prompt)
        C = min(self.prefill_chunk or cap, cap)
        chunks = []
        if self.pad_prefill:
            toks = np.zeros(cap, np.int32)
            toks[:s] = prompt
            pos = np.where(np.arange(cap) < s,
                           np.arange(cap), -1).astype(np.int32)
            for lo in range(0, cap, C):
                chunks.append((toks[None, lo:lo + C], pos[None, lo:lo + C]))
                if lo + C >= s:
                    break
        else:
            for lo in range(0, s, C):
                hi = min(lo + C, s)
                chunks.append((prompt[None, lo:hi],
                               np.arange(lo, hi, dtype=np.int32)[None]))
        return chunks

    def _start_job(self, req: Request) -> _PrefillJob | None:
        cache = self.cache
        s = len(req.prompt)
        n = cache.blocks_for(s)
        if cache.num_free < n:
            cache.reclaim(n)
        blocks = cache.alloc(n)
        if blocks is None:
            return None
        cap = _pow2_at_least(s, self.cache.block_size)
        if self.pad_prefill and self.prefill_chunk:
            # keep every padded chunk the same shape: round the capacity
            # bucket up to a chunk multiple so no ragged tail compiles
            # an extra variant per (chunk, cap) pair
            C = min(self.prefill_chunk, cap)
            cap = -(-cap // C) * C
        state = self.model.init_seq_state(
            self.params, cap, batch_size=1,
            dtype=self.cfg.compute_dtype)
        return _PrefillJob(req, state, self._prefill_chunks(req.prompt, cap),
                           blocks)

    def _advance_job(self, job: _PrefillJob) -> None:
        toks, pos = job.chunks[job.next]
        # dispatch at the jit boundary, not device sync — blocking here
        # would serialize the prefill/decode interleave
        with span("engine.prefill_chunk", rid=job.req.rid,
                  chunk=job.next, width=toks.shape[1]):
            job.state, job.logits = self._chunk(
                self.params, job.state, jnp.asarray(toks), jnp.asarray(pos),
                job.next == 0)
        job.next += 1

    def _finish_job(self, job: _PrefillJob) -> None:
        """Write the prefilled K/V into the reserved pool blocks and
        occupy the slot."""
        req, cache = job.req, self.cache
        s = len(req.prompt)
        # (L, b=1, cap, kv, hd) -> (L, s, kv, hd)
        with span("engine.write_prompt", rid=req.rid,
                  blocks=len(job.blocks)):
            cache.write_prompt(job.state["k"][:, 0, :s],
                               job.state["v"][:, 0, :s], job.blocks)
        extras1 = {k: job.state[k] for k in self._extras_keys}
        # the host waits here for the prefill to finish on the device
        with span("engine.first_token", rid=req.rid):
            first = self._pick_token(req, job.logits[0], s)
        if self.share_prefixes and req.greedy:
            cache.register_prefix(req.prompt, job.blocks, s, first,
                                  extras=extras1 or None)
        self._occupy(req, job.blocks, s, first, extras1)

    def _occupy(self, req: Request, blocks, length: int, first: int,
                extras1: dict | None) -> None:
        req.blocks = blocks
        req.length = length
        req.tokens = [first]
        tnow = now()
        if req.t_first is None:   # survives eviction replay: TTFT is the
            req.t_first = tnow    # *first* time the first token existed
        req.t_last = tnow
        if req.done:        # max_new_tokens == 1: the prefill was enough
            if req.keep_blocks:
                # handoff: blocks stay allocated (and extras stashed)
                # until export_request() harvests them
                req.export_extras = extras1
            else:
                self.cache.free(blocks)
                req.blocks = []
            req.status = "done"
            self._record_request(req)
            self._done[req.rid] = req
            return
        req.slot = self._slots.index(None)
        if extras1:
            self._extras = self._scatter_extras(
                self._extras, extras1, jnp.asarray(req.slot))
        self._admission_seq += 1   # ties would invert FIFO preemption
        req.admitted_at = self._admission_seq
        req.status = "running"
        self._slots[req.slot] = req

    def _start(self, req: Request) -> bool:
        if req.t_admit is None:   # queue wait ends at first admission try
            req.t_admit = now()
        if req.artifact is not None:
            return self._start_from_artifact(req)
        restored = None
        if self.share_prefixes and req.greedy:
            restored = self.cache.lookup_prefix(req.prompt)
            if restored is None and self.prefix_store is not None:
                # local miss -> cluster store: another replica may have
                # published this prefix; a restore lands it in the local
                # index, so the retry below hits
                if self._restore_from_store(req.prompt):
                    restored = self.cache.lookup_prefix(req.prompt)
        if restored is not None:
            blocks, length, first, extras = restored
            self._occupy(req, blocks, length, first, extras)
            return True
        job = self._start_job(req)
        if job is None:
            return False
        if self.prefill_chunk and (self.prefill_role or
                                   any(r is not None for r in self._slots)):
            # chunked + a running batch: advance one chunk per step so
            # admission interleaves with decode ticks
            req.status = "prefilling"
            self._job = job
            return True
        while not job.finished:
            self._advance_job(job)
        self._finish_job(job)
        return True

    # --------------------------- cluster handoff ---------------------------
    #
    # The SeqState handoff contract (DESIGN.md §11): because the chunk
    # API keeps *all* per-sequence state in the paged pools (KV blocks +
    # scale rows) plus a small extras pytree, a request's entire serving
    # state serializes as host arrays — block contents, length, the
    # first sampled token (the one thing blocks can't reconstruct), and
    # extras.  Any same-config engine can import it and keep decoding.

    def export_request(self, rid: int) -> dict:
        """Harvest a finished ``keep_blocks`` request as a handoff
        artifact and release its blocks.  The artifact is self-contained
        host data: safe to ship to another replica (or through 3FS)."""
        req = self._done.pop(rid)
        art = {
            "prompt": req.prompt,
            "length": req.length,
            "first_token": int(req.tokens[0]),
            "blocks": self.cache.export_blocks(req.blocks),
            "extras": jax.device_get(req.export_extras or {}),
            "t_submit": req.t_submit,
            "t_first": req.t_first,
            "n_evictions": req.n_evictions,
        }
        self.cache.free(req.blocks)
        req.blocks, req.export_extras = [], None
        return art

    def _start_from_artifact(self, req: Request) -> bool:
        """Admit by importing an exported prefill instead of running the
        prompt.  TTFT stays anchored at the prefill replica's first
        token; eviction after import falls back to a local (prefix-hit
        or cold) prefill, which determinism makes token-identical."""
        art = req.artifact
        length = int(art["length"])
        n = self.cache.blocks_for(length)
        if self.cache.num_free < n:
            self.cache.reclaim(n)
        ids = self.cache.alloc(n)
        if ids is None:
            return False
        with span("engine.import_artifact", rid=req.rid, blocks=n):
            self.cache.import_blocks(ids, art["blocks"])
        extras = dict(art.get("extras") or {})
        first = int(art["first_token"])
        req.t_first = art.get("t_first")
        req.n_evictions += int(art.get("n_evictions") or 0)
        if self.share_prefixes and req.greedy:
            self.cache.register_prefix(req.prompt, ids, length, first,
                                       extras=extras or None)
        req.artifact = None     # imported; drop the host copy
        self._occupy(req, ids, length, first, extras)
        return True

    def _restore_from_store(self, prompt: np.ndarray) -> bool:
        """Pull a published prefix from the cluster store into the local
        index (alloc -> import -> register -> drop our ref: the index
        owns the blocks, exactly as after a local prefill)."""
        art = self.prefix_store.fetch(_prefix_key(prompt))
        if art is None:
            return False
        length = int(art["length"])
        n = self.cache.blocks_for(length)
        if self.cache.num_free < n:
            self.cache.reclaim(n)
        ids = self.cache.alloc(n)
        if ids is None:
            return False
        with span("engine.store_restore", blocks=n):
            self.cache.import_blocks(ids, art["blocks"])
        extras = dict(art.get("extras") or {})
        self.cache.register_prefix(prompt, ids, length,
                                   int(art["first_token"]),
                                   extras=extras or None)
        self.cache.free(ids)    # the prefix index holds the live ref
        self._c_store_hits.inc()
        return True

    def _publish_prefix(self, key, ids, length, first, extras) -> None:
        """``on_prefix_evict`` hook: write a locally-evicted prefix
        entry back to the cluster store while its blocks are still
        readable, so any replica can restore it later."""
        with span("engine.store_publish", blocks=len(ids)):
            self.prefix_store.publish(key, {
                "length": int(length),
                "first_token": int(first),
                "blocks": self.cache.export_blocks(ids),
                "extras": jax.device_get(extras) if extras else {},
            })

    # ------------------------------- decode --------------------------------

    def _bucket(self, n: int) -> int:
        return _pow2_at_least(n, max(self.min_table_width, 2))

    def _ensure_block(self, req: Request) -> bool:
        """Make sure the block table covers the next write position."""
        return self._ensure_blocks(req, req.length + 1)

    def _ensure_blocks(self, req: Request, n_tokens: int) -> bool:
        """Grow the block table to cover ``n_tokens`` cached positions
        (a speculative verify writes ``1 + n_drafts`` at once)."""
        need = self.cache.blocks_for(n_tokens) - len(req.blocks)
        if need <= 0:
            return True
        if self.cache.num_free < need:
            self.cache.reclaim(need)
        got = self.cache.alloc(need)
        if got is None:
            return False
        req.blocks.extend(got)
        return True

    def _cancel_job(self) -> None:
        """Requeue the in-flight prefill job, releasing its reserved
        blocks (the prefill compute is discarded — determinism makes the
        redo exact)."""
        job, self._job = self._job, None
        req = job.req
        self.cache.free(job.blocks)
        req.status, req.arrival = "queued", self.step_count
        self._queue.insert(0, req)
        req.n_evictions += 1
        self._c_evictions.inc()

    def _evict_for_space(self, needy: Request) -> bool:
        """Pool exhausted mid-decode: preempt the *youngest* claimant —
        the in-flight prefill job first (it holds reserved blocks and is
        always younger than any runner), else the youngest running
        request, possibly ``needy`` itself — back to the queue.  The
        oldest admission is never preempted by younger ones, so it
        monotonically runs to completion and frees its blocks: FIFO-
        priority preemption cannot livelock (evicting only "others"
        can ping-pong two requests that jointly exceed the pool
        forever).  False iff ``needy`` is the sole claimant — then the
        pool simply cannot hold one request and the caller raises."""
        if self._job is not None:
            self._cancel_job()
            return True
        running = [r for r in self._slots if r is not None]
        if running == [needy]:
            return False
        self.evict(max(running, key=lambda r: r.admitted_at).rid)
        return True

    def evict(self, rid: int) -> None:
        """Free a running (or still-prefilling) request's blocks and
        restart it from the queue (decode is deterministic given
        (seed, position) -> identical tokens on re-entry, greedy or
        sampled)."""
        if self._job is not None and self._job.req.rid == rid:
            self._cancel_job()
            return
        for slot, req in enumerate(self._slots):
            if req is not None and req.rid == rid:
                self._slots[slot] = None
                if self.drafter is not None:
                    # replay re-prefills from scratch; drop any per-rid
                    # drafter state (draft-model SeqState) with it
                    self.drafter.release(rid)
                self.cache.free(req.blocks)
                req.blocks, req.tokens, req.length = [], [], 0
                req.slot, req.status = -1, "queued"
                req.arrival = self.step_count
                self._queue.insert(0, req)
                req.n_evictions += 1
                self._c_evictions.inc()
                return
        raise KeyError(f"request {rid} is not running")

    def step(self) -> int:
        """Advance the in-flight prefill by one chunk, admit, decode one
        token for every running request, sample, retire.  Returns the
        number of tokens produced."""
        with span("engine.step", step=self.step_count,
                  queue=len(self._queue),
                  active=sum(r is not None for r in self._slots),
                  free_blocks=self.cache.num_free):
            return self._step_once()

    def _step_once(self) -> int:
        if self._job is not None:
            self._advance_job(self._job)
            if self._job.finished:
                job, self._job = self._job, None
                self._finish_job(job)
        self._admit()
        active = [r for r in self._slots if r is not None]
        if not active:
            # Done-but-unharvested keep_blocks requests hold pool blocks;
            # admission may be waiting on the cluster to export them, so
            # an idle tick is progress, not a stall.
            held = any(r.blocks for r in self._done.values())
            if (self._job is None and self._queue and not held
                    and self._queue[0].arrival <= self.step_count):
                raise RuntimeError(
                    f"request {self._queue[0].rid} cannot be admitted even "
                    f"into an empty engine: prompt needs "
                    f"{self.cache.blocks_for(len(self._queue[0].prompt))} "
                    f"blocks, pool has {self.cache.num_free} free")
            self.step_count += 1
            return 0
        if self.drafter is not None:
            return self._spec_step()
        with span("engine.prepare_tick") as prep:
            evictions = self.evictions
            # Walk slots (not a snapshot): _evict_for_space can clear any
            # slot mid-loop, and an evicted request must not be handed a
            # block it would never free.
            for slot in range(self.max_slots):
                req = self._slots[slot]
                if req is None:
                    continue
                while (self._slots[slot] is req
                       and not self._ensure_block(req)):
                    if not self._evict_for_space(req):
                        raise RuntimeError(
                            f"KV pool exhausted: request {req.rid} needs "
                            f"a block and nothing is evictable")
            active = [r for r in self._slots if r is not None]

            width = self._bucket(max(len(r.blocks) for r in active))
            tables = np.zeros((self.max_slots, width), np.int32)
            lengths = np.zeros(self.max_slots, np.int32)
            tokens = np.zeros(self.max_slots, np.int32)
            temps = np.zeros(self.max_slots, np.float32)
            topks = np.zeros(self.max_slots, np.int32)
            keys = np.zeros((self.max_slots, 2), np.uint32)
            for r in active:
                tables[r.slot, :len(r.blocks)] = r.blocks
                lengths[r.slot] = r.length
                tokens[r.slot] = r.tokens[-1]
                temps[r.slot] = r.temperature
                topks[r.slot] = r.top_k
                if not r.greedy:
                    keys[r.slot] = self._base_key(r)

            # the paged SeqState: block tables, per-slot lengths, and the
            # per-slot PRNG keys ride inside the state pytree
            state = {"k": self.cache.k, "v": self.cache.v,
                     "block_tables": jnp.asarray(tables),
                     "lengths": jnp.asarray(lengths),
                     "rng": jnp.asarray(keys), **self._extras}
            if self.cache.quantized:
                state["k_scale"] = self.cache.k_scale
                state["v_scale"] = self.cache.v_scale
            prep.set_metadata(width=width,
                              evicted=self.evictions - evictions)
        with span("engine.decode_tick", step=self.step_count,
                  active=len(active), width=width):
            state, logits = self._step(self.params, state,
                                       jnp.asarray(tokens)[:, None],
                                       jnp.asarray(lengths)[:, None])
        self.cache.k, self.cache.v = state["k"], state["v"]
        if self.cache.quantized:
            self.cache.k_scale = state["k_scale"]
            self.cache.v_scale = state["v_scale"]
        self._extras = {k: state[k] for k in self._extras_keys}
        # pick on device: ship (max_slots,) int32 to host, not the
        # (max_slots, vocab) logits; an all-greedy step (the default)
        # skips the full-vocab sort the top-k sampler needs.  The host
        # waits here for the tick to finish on the device.
        with span("engine.fetch_tokens", active=len(active)):
            if all(r.greedy for r in active):
                next_toks = np.asarray(jnp.argmax(logits, axis=-1),
                                       np.int32)
            else:
                # token about to be sampled lands at position length + 1
                next_toks = np.asarray(self._sample(
                    logits, state["rng"], jnp.asarray(lengths) + 1,
                    jnp.asarray(temps), jnp.asarray(topks)), np.int32)

        with span("engine.retire") as ret:
            produced = finished = 0
            tnow = now()      # one clock read for the whole batched tick
            for r in active:
                r.length += 1
                r.tokens.append(int(next_toks[r.slot]))
                produced += 1
                if r.t_last is not None:
                    # per-token TPOT: interval since this request's
                    # previous token (includes eviction-replay gaps —
                    # what the user saw)
                    dt = tnow - r.t_last
                    self._h_tpot.record(dt)
                    r.tpot_sum += dt
                    r.tpot_n += 1
                r.t_last = tnow
                if r.done:
                    self._slots[r.slot] = None
                    self.cache.free(r.blocks)
                    r.slot, r.status = -1, "done"
                    # telemetry is captured *here*, at completion — run()
                    # clears _done, so drain-time recording would lose it
                    self._record_request(r)
                    self._done[r.rid] = r
                    finished += 1
            ret.set_metadata(finished=finished)
        self.step_count += 1
        return produced

    # ---------------------------- speculation ------------------------------

    def _spec_step(self) -> int:
        """One speculative decode tick (DESIGN.md §12).

        Draft: the drafter proposes up to ``draft_k`` tokens per slot
        from that request's own token history.  Verify: one
        (max_slots, draft_k + 1) chunk — row 0 is the slot's last
        emitted token at its write position, rows 1..n its drafts, rows
        beyond padded with position -1 (ragged proposals share one
        compiled shape per table bucket; an empty proposal degrades to
        a plain decode tick inside the same chunk).  Accept: longest
        matching prefix per slot (greedy exact argmax match; sampled
        via the rejection rule, position-keyed) plus one bonus token
        from the stop row.  Rollback: block refs past the accepted
        region are dropped (``cache.rollback``) and — hybrid — the
        mamba extras are re-advanced from the pre-chunk snapshot
        through only the accepted rows."""
        k = self.draft_k
        T = k + 1
        cache = self.cache
        # -- propose + reserve blocks (walk slots, not a snapshot:
        #    _evict_for_space can clear any slot mid-loop) --
        proposals: dict[int, list] = {}
        for slot in range(self.max_slots):
            req = self._slots[slot]
            if req is None:
                continue
            cap = min(k, req.max_new_tokens - len(req.tokens) - 1)
            prop: list = []
            if cap > 0:
                hist = np.concatenate(
                    [req.prompt, np.asarray(req.tokens, np.int32)])
                prop = [int(t) for t in
                        self.drafter.propose(req.rid, hist, cap)][:cap]
            # the verify chunk writes positions length..length+n; under
            # pool pressure shrink the proposal to a plain decode tick
            # before resorting to eviction
            while self._slots[slot] is req and not self._ensure_blocks(
                    req, req.length + 1 + len(prop)):
                if prop:
                    prop = []
                    continue
                if not self._evict_for_space(req):
                    raise RuntimeError(
                        f"KV pool exhausted: request {req.rid} needs a "
                        f"block and nothing is evictable")
            if self._slots[slot] is req:
                proposals[req.rid] = prop
        active = [r for r in self._slots if r is not None]
        if not active:
            self.step_count += 1
            return 0

        width = self._bucket(max(len(r.blocks) for r in active))
        tables = np.zeros((self.max_slots, width), np.int32)
        lengths = np.zeros(self.max_slots, np.int32)
        toks = np.zeros((self.max_slots, T), np.int32)
        pos = np.full((self.max_slots, T), -1, np.int32)
        dnext = np.zeros((self.max_slots, T), np.int32)
        temps = np.zeros(self.max_slots, np.float32)
        topks = np.zeros(self.max_slots, np.int32)
        keys = np.zeros((self.max_slots, 2), np.uint32)
        for r in active:
            prop = proposals.get(r.rid) or []
            n = len(prop)
            tables[r.slot, :len(r.blocks)] = r.blocks
            lengths[r.slot] = r.length
            toks[r.slot, 0] = r.tokens[-1]
            toks[r.slot, 1:n + 1] = prop
            pos[r.slot, :n + 1] = np.arange(r.length, r.length + n + 1)
            dnext[r.slot, :n] = prop
            temps[r.slot] = r.temperature
            topks[r.slot] = r.top_k
            if not r.greedy:
                keys[r.slot] = self._base_key(r)

        state = {"k": cache.k, "v": cache.v,
                 "block_tables": jnp.asarray(tables),
                 "lengths": jnp.asarray(lengths),
                 "rng": jnp.asarray(keys), **self._extras}
        if cache.quantized:
            state["k_scale"] = cache.k_scale
            state["v_scale"] = cache.v_scale
        # pre-chunk extras snapshot: the recurrent-state rollback anchor
        # (_verify never donates when extras exist, so this stays live)
        snap_extras = dict(self._extras) if self._extras_keys else None
        jtoks, jpos = jnp.asarray(toks), jnp.asarray(pos)
        with span("engine.spec_tick", step=self.step_count,
                  active=len(active), draft_k=k):
            state, logits = self._verify(self.params, state, jtoks, jpos)
        cache.k, cache.v = state["k"], state["v"]
        if cache.quantized:
            cache.k_scale = state["k_scale"]
            cache.v_scale = state["v_scale"]
        self._extras = {kk: state[kk] for kk in self._extras_keys}

        # -- acceptance (host combines per slot) --
        if all(r.greedy for r in active):
            gn = np.asarray(jnp.argmax(logits, axis=-1), np.int32)
            acc = rej = plain = None
        else:
            gn, acc, rej, plain = (np.asarray(a) for a in self._accept(
                logits, jnp.asarray(dnext), state["rng"], jpos,
                jnp.asarray(temps), jnp.asarray(topks)))
        emitted: dict[int, list] = {}
        for r in active:
            emitted[r.rid] = longest_accept(
                r.greedy, proposals.get(r.rid) or [], gn[r.slot],
                None if acc is None else acc[r.slot],
                None if rej is None else rej[r.slot],
                None if plain is None else plain[r.slot])

        # -- hybrid correction pass: any partially-accepted slot has
        #    advanced its mamba recurrence through rejected rows; re-run
        #    the same chunk from the snapshot with those rows padded
        #    out.  KV rewrites at accepted positions are bit-identical
        #    (deterministic ops + per-row position masking), so only
        #    the recurrent extras change.
        if snap_extras is not None and any(
                len(emitted[r.rid]) - 1 < len(proposals.get(r.rid) or [])
                for r in active):
            pos2 = np.full((self.max_slots, T), -1, np.int32)
            for r in active:
                mm = len(emitted[r.rid])       # accepted rows = m + 1
                pos2[r.slot, :mm] = np.arange(r.length, r.length + mm)
            state2 = {"k": cache.k, "v": cache.v,
                      "block_tables": jnp.asarray(tables),
                      "lengths": jnp.asarray(lengths),
                      "rng": jnp.asarray(keys), **snap_extras}
            if cache.quantized:
                state2["k_scale"] = cache.k_scale
                state2["v_scale"] = cache.v_scale
            with span("engine.spec_fixup", step=self.step_count):
                state2, _ = self._verify(self.params, state2, jtoks,
                                         jnp.asarray(pos2))
            cache.k, cache.v = state2["k"], state2["v"]
            if cache.quantized:
                cache.k_scale = state2["k_scale"]
                cache.v_scale = state2["v_scale"]
            self._extras = {kk: state2[kk] for kk in self._extras_keys}

        # -- emit + rollback + retire --
        produced = 0
        tnow = now()
        for r in active:
            out = emitted[r.rid]
            n = len(proposals.get(r.rid) or [])
            m = len(out) - 1                   # accepted drafts
            self._h_spec_tps.record(len(out))
            if n:
                self._h_spec_acc.record(m / n)
            # rollback: keep block refs covering the accepted writes
            # (positions 0..length+m); the rejected tail's refs drop
            r.blocks = cache.rollback(r.blocks, r.length + m + 1)
            r.length += m + 1
            r.tokens.extend(out)
            produced += len(out)
            if r.t_last is not None:
                # one verify sweep produced len(out) tokens: spread the
                # wall-clock interval across them so TPOT keeps meaning
                # "time per emitted token"
                dt = (tnow - r.t_last) / len(out)
                for _ in range(len(out)):
                    self._h_tpot.record(dt)
                r.tpot_sum += dt * len(out)
                r.tpot_n += len(out)
            r.t_last = tnow
            if r.done:
                self._slots[r.slot] = None
                self.drafter.release(r.rid)
                cache.free(r.blocks)
                r.blocks = []
                r.slot, r.status = -1, "done"
                self._record_request(r)
                self._done[r.rid] = r
        self.step_count += 1
        return produced

    # -------------------------------- drive --------------------------------

    def run(self, max_steps: int = 100_000) -> dict[int, np.ndarray]:
        """Step until queue and slots drain; {rid: (max_new_tokens,)}."""
        for _ in range(max_steps):
            if (not self._queue and self._job is None
                    and all(s is None for s in self._slots)):
                break
            self.step()
        else:
            raise RuntimeError("serving trace did not drain")
        out = {rid: np.asarray(req.tokens[:req.max_new_tokens], np.int32)
               for rid, req in self._done.items()}
        self._done.clear()      # a long-lived server must not retain
        return out              # every historical request


    # ------------------------------ telemetry ------------------------------

    def _record_request(self, req: Request) -> None:
        """Fold a finished request into the latency histograms and the
        bounded per-request log.  Called once, at completion."""
        self._c_completed.inc()
        ttft = queue_wait = None
        if req.t_submit is not None and req.t_first is not None:
            ttft = req.t_first - req.t_submit
            self._h_ttft.record(ttft)
        if req.t_submit is not None and req.t_admit is not None:
            queue_wait = req.t_admit - req.t_submit
            self._h_queue.record(queue_wait)
        if len(self._request_log) < self._request_log_cap:
            self._request_log.append({
                "rid": req.rid, "prompt_len": len(req.prompt),
                "n_tokens": len(req.tokens), "ttft_s": ttft,
                "queue_wait_s": queue_wait,
                "tpot_mean_s": (req.tpot_sum / req.tpot_n
                                if req.tpot_n else None),
                "evictions": req.n_evictions,
            })

    def request_metrics(self) -> dict:
        """Per-request latency percentiles over every *completed* request
        (recorded at completion time — surviving ``run()``'s drain).

        TTFT = submit -> first token exists; TPOT = interval between a
        request's consecutive tokens (per token, not per request);
        queue_wait = submit -> first admission attempt.  All seconds.
        """
        def dist(h):
            return {"count": h.count, "mean_s": h.mean,
                    "p50_s": h.percentile(50), "p95_s": h.percentile(95),
                    "p99_s": h.percentile(99)}
        return {
            "completed": self._c_completed.value,
            "evictions": self._c_evictions.value,
            "ttft": dist(self._h_ttft),
            "tpot": dist(self._h_tpot),
            "queue_wait": dist(self._h_queue),
            "requests": list(self._request_log),
        }

    @property
    def stats(self) -> dict:
        """Unified serving stats schema (``serving/stats.py``) plus
        engine-specific extras."""
        speculating = self.drafter is not None
        extra = {}
        if speculating:
            extra["spec_accept_rate"] = (self._h_spec_acc.mean
                                         if self._h_spec_acc.count else 0.0)
        return serving_stats(
            requests_completed=self._c_completed.value,
            queue_depth=len(self._queue) + (1 if self._job is not None
                                            else 0),
            evictions=self.evictions,
            ttft=self._h_ttft, tpot=self._h_tpot,
            tokens_per_step=(self._h_spec_tps.mean
                            if speculating and self._h_spec_tps.count
                            else 1.0),
            **extra,
            steps=self.step_count,
            active_slots=sum(r is not None for r in self._slots),
            prefix_hit_rate=self.cache.hit_rate,
            store_hits=self._c_store_hits.value,
            free_blocks=self.cache.num_free,
            prefill_traces=self.prefill_traces,
            decode_traces=self.decode_traces,
        )
