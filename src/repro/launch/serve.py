"""Batched serving driver: dense lockstep decode or the paged
continuous-batching engine (``--decode-impl paged``).

  PYTHONPATH=src python -m repro.launch.serve --arch zamba2-1.2b --smoke \\
      --batch 4 --prompt-len 32 --gen 16
  PYTHONPATH=src python -m repro.launch.serve --arch codeqwen1.5-7b \\
      --smoke --decode-impl paged --stagger 2 --block-size 16 \\
      --prefill-chunk 8 --temperature 0.8 --top-k 40
  PYTHONPATH=src python -m repro.launch.serve --arch codeqwen1.5-7b \\
      --smoke --decode-impl paged --replicas 2 --prefill-replicas 2 \\
      --slo-ttft-ms 500 --slo-tpot-ms 100
"""
from __future__ import annotations

import argparse
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.telemetry import now, span


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--decode-impl", choices=("dense", "paged"),
                    default=None, help="override cfg.decode_impl")
    ap.add_argument("--block-size", type=int, default=16,
                    help="paged: KV block size (tokens)")
    ap.add_argument("--n-blocks", type=int, default=0,
                    help="paged: pool size in blocks (0 = sized to fit)")
    ap.add_argument("--stagger", type=int, default=0,
                    help="paged: admit request i at engine step i*stagger")
    ap.add_argument("--prefill-chunk", type=int, default=0,
                    help="paged: prefill in chunks of this many tokens, "
                         "interleaved with decode ticks (0 = one bucketed "
                         "whole-prompt chunk)")
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="paged: sampling temperature (0 = greedy)")
    ap.add_argument("--top-k", type=int, default=0,
                    help="paged: top-k truncation (0 = full vocab)")
    ap.add_argument("--kv-dtype", default=None,
                    choices=("bfloat16", "float8_e4m3", "int8"),
                    help="paged: quantized KV block dtype (default: the "
                         "model compute dtype, unquantized)")
    ap.add_argument("--spec-mode", default="off",
                    choices=("off", "ngram", "draft-model"),
                    help="paged: speculative decoding — n-gram prompt-"
                         "lookup drafting, or a smaller same-arch draft "
                         "model (demo: the target arch at half the "
                         "layers, randomly initialized); greedy streams "
                         "stay bit-identical to --spec-mode off")
    ap.add_argument("--draft-k", type=int, default=4,
                    help="spec: draft tokens proposed/verified per slot "
                         "per step")
    ap.add_argument("--replicas", type=int, default=0,
                    help="paged: decode replicas in a disaggregated "
                         "ServingCluster (0 = single-engine paths)")
    ap.add_argument("--prefill-replicas", type=int, default=1,
                    help="cluster: prefill replicas (with --replicas > 0)")
    ap.add_argument("--slo-ttft-ms", type=float, default=1000.0,
                    help="cluster: TTFT SLO target for the router (ms)")
    ap.add_argument("--slo-tpot-ms", type=float, default=200.0,
                    help="cluster: TPOT SLO target for the router (ms)")
    ap.add_argument("--metrics", action="store_true",
                    help="print the unified serving stats (and per-request "
                         "percentiles) after the run")
    ap.add_argument("--trace", default="",
                    help="record a JAX profiler trace of the run under "
                         "this directory: the program's spans and the "
                         "device's ops on one clock (.xplane.pb, and a "
                         "Perfetto trace.json.gz)")
    args = ap.parse_args(argv)
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    from repro.configs.registry import get_arch, smoke_config
    from repro.data.synthetic import batch_for_model
    from repro.models import build_model

    cfg = smoke_config(args.arch) if args.smoke else get_arch(args.arch)
    if args.smoke:
        cfg = dataclasses.replace(cfg, compute_dtype="float32")
    impl = args.decode_impl or cfg.decode_impl
    model = build_model(cfg)
    params = init_params(model, args.seed)

    batch = batch_for_model(cfg, "prefill", 0, args.batch, args.prompt_len,
                            args.seed)
    batch = {k: jnp.asarray(v) for k, v in batch.items()}
    if args.trace:
        jax.profiler.start_trace(args.trace, create_perfetto_trace=True)
    try:
        if impl == "paged" and args.replicas > 0:
            return _serve_cluster(model, params, batch, args)
        if impl == "paged":
            return _serve_paged(model, params, batch, args)
        return _serve_dense(model, params, batch, args)
    finally:
        if args.trace:
            jax.profiler.stop_trace()
            print(f"trace written under {args.trace}")


def init_params(model, seed: int):
    """Random weights from ``seed``, held in the model's compute dtype.
    Each fp32 leaf is drawn and cast inside one jit, so a full-width
    model's fp32 masters are never materialized on the device: serving
    keeps no masters (phi4-mini-3.8b: 7.7 GB in bf16, not 15.4 GB)."""
    dtype = jnp.dtype(model.compute_dtype)

    def make(key):
        return jax.tree_util.tree_map(lambda w: w.astype(dtype),
                                      model.init(key))
    return jax.jit(make)(jax.random.PRNGKey(seed))


def _print_stats(stats, request_metrics=None):
    """The one ``--metrics`` code path: every serving backend (dense,
    paged, cluster) funnels its unified stats dict here, so the keys the
    schema guarantees are the keys an operator greps for."""
    import json

    from repro.serving.stats import check_schema
    check_schema(stats)
    print("serving stats:")
    print(json.dumps(stats, indent=2, default=str, sort_keys=True))
    if request_metrics is not None:
        print("request metrics:")
        print(json.dumps(request_metrics, indent=2, default=str))


def _serve_dense(model, params, batch, args):
    """Lockstep decode through the chunk-oriented API: the prompt is one
    fresh chunk, every decode step a T=1 chunk; the SeqState's capacity
    covers prompt + gen up front (no mid-decode growth)."""
    fwd = jax.jit(model.forward, static_argnames=("fresh",))
    tokens, positions, embeds = model.prompt_inputs(params, batch)
    b, s = positions.shape
    t0 = now()
    with span("serve.dense_prefill", batch=b, prompt_len=s):
        state = jax.jit(model.init_seq_state,
                        static_argnames=("max_len", "batch_size", "dtype"))(
            params, max_len=s + args.gen, batch=batch, batch_size=b)
        state, logits = fwd(params, state, tokens, positions,
                            embeds=embeds, fresh=True)
        jax.block_until_ready(logits)
    t_prefill = now() - t0

    toks = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    out = [np.asarray(toks)]
    t0 = now()
    for i in range(args.gen - 1):
        pos = jnp.full((b, 1), s + i, jnp.int32)
        with span("serve.dense_decode", step=i):
            state, logits = fwd(params, state, toks[:, None], pos)
            toks = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            out.append(np.asarray(toks))
    jax.block_until_ready(logits)
    t_decode = now() - t0

    if args.metrics:
        from repro.serving.stats import serving_stats
        from repro.telemetry import Histogram
        h_ttft = Histogram("serve.ttft_s")
        h_tpot = Histogram("serve.tpot_s")
        per_step = t_decode / max(args.gen - 1, 1)
        for _ in range(b):
            h_ttft.record(t_prefill)
            for _ in range(max(args.gen - 1, 1)):
                h_tpot.record(per_step)
        _print_stats(serving_stats(
            requests_completed=b, queue_depth=0, evictions=0,
            ttft=h_ttft, tpot=h_tpot, backend="dense"))

    gen = np.stack(out, axis=1)
    print(f"prefill {args.prompt_len} toks x{args.batch}: {t_prefill:.3f}s")
    print(f"decode  {args.gen} steps: {t_decode:.3f}s "
          f"({t_decode / max(args.gen - 1, 1) * 1e3:.1f} ms/step)")
    print("sample generations:")
    for row in gen[: min(4, args.batch)]:
        print("  ", row.tolist())
    return gen


def _spec_kwargs(model, args):
    """Engine kwargs for ``--spec-mode``.  The draft-model demo builds
    the target arch at half the layers with its own random init — a
    stand-in for a distilled small model sharing the tokenizer (real
    deployments load trained draft params instead)."""
    if args.spec_mode == "off":
        return {}
    kw = {"spec_mode": args.spec_mode, "draft_k": args.draft_k}
    if args.spec_mode == "draft-model":
        from repro.models import build_model
        dcfg = dataclasses.replace(model.cfg,
                                   n_layers=max(1, model.cfg.n_layers // 2))
        dmodel = build_model(dcfg)
        kw["draft_model"] = dmodel
        kw["draft_params"] = init_params(dmodel, args.seed + 1)
    return kw


def _serve_paged(model, params, batch, args):
    """Continuous batching: requests enter a *running* decode batch at
    their arrival step instead of waiting for a fresh lockstep batch."""
    from repro.serving import ServingEngine

    tokens = np.asarray(batch["tokens"])
    n_blocks = args.n_blocks or (
        args.batch * (-(-(args.prompt_len + args.gen) // args.block_size))
        * 2 + 1)
    engine = ServingEngine(model, params, n_blocks=n_blocks,
                           block_size=args.block_size,
                           max_slots=args.batch,
                           prefill_chunk=args.prefill_chunk,
                           temperature=args.temperature,
                           top_k=args.top_k, seed=args.seed,
                           kv_dtype=args.kv_dtype,
                           **_spec_kwargs(model, args))
    rids = [engine.submit(row, args.gen, arrival=i * args.stagger)
            for i, row in enumerate(tokens)]
    t0 = now()
    outs = engine.run()
    t_total = now() - t0

    produced = args.batch * args.gen
    mode = (f"sampled(T={args.temperature},k={args.top_k})"
            if args.temperature > 0 else "greedy")
    if args.spec_mode != "off":
        mode += f"+spec:{args.spec_mode}(draft_k={args.draft_k})"
    print(f"paged decode_impl ({mode}): {produced} tokens "
          f"({args.batch} seeded by prefill logits) over "
          f"{engine.step_count} engine steps in {t_total:.3f}s total "
          f"(engine steps include prefill admissions — "
          f"{t_total / max(engine.step_count, 1) * 1e3:.1f} ms/step "
          f"amortized)")
    print(f"engine stats: {engine.stats}")
    if args.metrics:
        _print_stats(dict(engine.stats), engine.request_metrics())
    gen = np.stack([outs[r] for r in rids])
    print("sample generations:")
    for row in gen[: min(4, args.batch)]:
        print("  ", row.tolist())
    return gen


def _serve_cluster(model, params, batch, args):
    """Disaggregated serving: M prefill + N decode replicas behind the
    SLO-aware router, SeqState handed off between roles per request."""
    from repro.serving import ServingCluster

    tokens = np.asarray(batch["tokens"])
    n_blocks = args.n_blocks or (
        args.batch * (-(-(args.prompt_len + args.gen) // args.block_size))
        * 2 + 1)
    clu = ServingCluster(model, params,
                         prefill_replicas=args.prefill_replicas,
                         decode_replicas=args.replicas,
                         slo_ttft_ms=args.slo_ttft_ms,
                         slo_tpot_ms=args.slo_tpot_ms,
                         temperature=args.temperature,
                         top_k=args.top_k, seed=args.seed,
                         engine_kwargs=dict(n_blocks=n_blocks,
                                            block_size=args.block_size,
                                            max_slots=args.batch,
                                            prefill_chunk=args.prefill_chunk,
                                            kv_dtype=args.kv_dtype),
                         # speculation rides the decode leg only —
                         # prefill replicas never run decode ticks
                         decode_engine_kwargs=_spec_kwargs(model, args))
    crids = [clu.submit(row, args.gen, arrival=i * args.stagger)
             for i, row in enumerate(tokens)]
    t0 = now()
    outs = clu.run()
    t_total = now() - t0

    stats = clu.stats()
    print(f"cluster ({args.prefill_replicas}P+{args.replicas}D, "
          f"SLO ttft<{args.slo_ttft_ms:g}ms tpot<{args.slo_tpot_ms:g}ms): "
          f"{args.batch * args.gen} tokens over {clu.step_count} cluster "
          f"steps in {t_total:.3f}s")
    print(f"cluster stats: {stats}")
    if args.metrics:
        _print_stats(stats, clu.request_metrics())
    gen = np.stack([outs[r] for r in crids])
    print("sample generations:")
    for row in gen[: min(4, args.batch)]:
        print("  ", row.tolist())
    return gen


if __name__ == "__main__":
    main()
