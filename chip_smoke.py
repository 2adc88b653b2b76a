"""Bring-up smoke run of the system's two main paths on TPU chips.

  python chip_smoke.py               # one chip
  python chip_smoke.py --four-chips  # one host of four chips

One chip (no arguments), all in this one process:

1. kernels — the paged chunk-attention kernel (phi4-mini head geometry)
   and the flash-attention kernel with its backward (gpt2-medium head
   geometry) against their jnp references on small inputs;
2. serve — phi4-mini-3.8b at full width through ``ServingEngine``
   (the paged path of ``repro.launch.serve``): random bf16 weights from
   ``--seed``, four requests of mixed prompt length with staggered
   arrivals, prefill chunks interleaved with decode ticks;
3. train — gpt2-medium at full width through ``ParallelPlan`` (the gspmd
   executor of ``repro.launch.train``) for a few steps at seq 1024 on
   synthetic data, fused flash attention forward and backward.

``--four-chips`` runs only gpt2-medium under the explicit executors —
HaiScale DDP with overlapped bucket sync, DDP + ZeRO-1, and a 4-stage
1F1B pipeline — and under the gspmd executor as their reference, and
compares per-step losses.

Each phase prints one ``PHASE {json}`` line.  The last line of stdout is
``{"ok": true, "device": {...}}``; any failure exits non-zero before it.
Off a TPU the script exits non-zero without running a phase.
"""
from __future__ import annotations

import argparse
import gc
import json
import math
import os
import sys

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

SEED = 0
# Serving traffic: four requests, prompt lengths mixed within one
# capacity bucket (one compiled prefill shape per chunk kind), arriving
# two engine steps apart, 32 new tokens each.
PROMPT_LENS = (320, 512, 400, 448)
GEN = 32
STAGGER = 2
PREFILL_CHUNK = 128
BLOCK_SIZE = 16
# Training: full-width gpt2-medium, seq 1024.
TRAIN_BATCH = 8
TRAIN_SEQ = 1024
TRAIN_STEPS = 4
TRAIN_LR = 3e-4
# Four-chip paths against the gspmd reference: every step's loss within
# this relative bound.  The paths run the same bf16 math in a different
# reduction order (per-shard grads summed by collectives, pipeline
# microbatches); bf16 keeps 8 significant bits (2^-8 ~ 3.9e-3 relative),
# so allow ~2.5 bf16 ulps of the loss.
LOSS_RTOL = 1e-2
# Kernel-vs-reference tolerance on bf16 inputs of unit scale (the
# interpret-mode parity tests use the same bf16 bound).
KERNEL_ATOL = 2e-2


class Timer:
    """Wall time of a phase, split into compilation and the rest.

    Compilation is the sum of JAX's XLA backend-compile events inside the
    phase (tracing and lowering events nest across jit levels, so they
    stay in the rest); run time is the wall time less that."""

    def __init__(self):
        import jax
        self.compile_s = 0.0

        def listen(event, duration, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.compile_s += duration
        jax.monitoring.register_event_duration_secs_listener(listen)

    def phase(self):
        return _Span(self)


class _Span:
    def __init__(self, timer):
        self.timer = timer

    def __enter__(self):
        from repro.telemetry import now
        self.t0 = now()
        self.c0 = self.timer.compile_s
        return self

    def __exit__(self, *exc):
        from repro.telemetry import now
        self.wall_s = now() - self.t0
        self.compile_s = self.timer.compile_s - self.c0
        self.run_s = self.wall_s - self.compile_s


def peak_bytes():
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def report(name, **fields):
    print(f"PHASE {json.dumps({'phase': name, **fields})}", flush=True)


def has_kernel(compiled) -> bool:
    return "tpu_custom_call" in compiled.as_text()


# ------------------------------- kernels ---------------------------------


def phase_kernels(timer):
    """Both attention kernels against their jnp references, on the chip."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels.flash_attention import flash_attention
    from repro.kernels.paged_chunk_attention import paged_chunk_attention

    rng = np.random.default_rng(SEED)

    def arr(shape, dtype=jnp.bfloat16):
        return jnp.asarray(rng.standard_normal(shape), dtype)

    errs = {}
    with timer.phase() as t:
        # paged: phi4-mini heads (24 over 8 kv, d 128), a fragmented table
        b, T, h, kvh, d, bs, nbmax, nb = 2, 16, 24, 8, 128, 16, 8, 24
        q, kp, vp = arr((b, T, h, d)), arr((nb, bs, kvh, d)), \
            arr((nb, bs, kvh, d))
        tables = jnp.asarray(rng.permutation(nb)[:b * nbmax]
                             .reshape(b, nbmax), jnp.int32)
        pos = jnp.asarray([[40 + t for t in range(T)],
                           [100 + t for t in range(T)]], jnp.int32)
        got, want = (paged_chunk_attention(q, kp, vp, tables, pos,
                                           impl=impl)
                     for impl in ("kernel", "ref"))
        errs["paged"] = float(jnp.max(jnp.abs(
            got.astype(jnp.float32) - want.astype(jnp.float32))))

        # flash fwd + bwd: gpt2-medium heads (16 of d 64), seq 256
        q, k, v = (arr((2, 16, 256, 64)) for _ in range(3))

        def loss(impl):
            def f(q, k, v):
                o = flash_attention(q, k, v, impl=impl)
                return jnp.sum(o.astype(jnp.float32) ** 2) / o.size
            return jax.jit(jax.value_and_grad(f, argnums=(0, 1, 2)))
        (lk, gk), (lr, gr) = (loss(i)(q, k, v) for i in ("kernel", "ref"))
        errs["flash_loss"] = abs(float(lk) - float(lr))
        errs["flash_grad"] = max(
            float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                  - b_.astype(jnp.float32))))
            for a, b_ in zip(gk, gr))
    report("kernels", max_abs_err=errs, atol=KERNEL_ATOL,
           compile_s=t.compile_s, run_s=t.run_s)
    bad = {k: v for k, v in errs.items() if not v <= KERNEL_ATOL}
    if bad:
        raise AssertionError(f"kernel/reference mismatch: {bad}")


# -------------------------------- serve ----------------------------------


def phase_serve(timer):
    """phi4-mini-3.8b through the paged engine, as launch/serve.py runs it."""
    import jax
    import numpy as np

    from repro.configs.registry import get_arch
    from repro.launch.serve import init_params
    from repro.models import build_model
    from repro.serving import ServingEngine

    cfg = get_arch("phi4-mini-3.8b")
    model = build_model(cfg)
    rng = np.random.default_rng(SEED)
    prompts = [rng.integers(0, cfg.vocab_size, n, dtype=np.int32)
               for n in PROMPT_LENS]
    max_ctx = max(PROMPT_LENS) + GEN
    blocks_per_req = -(-max_ctx // BLOCK_SIZE)

    with timer.phase() as t_init:
        params = init_params(model, SEED)
        jax.block_until_ready(params)
    n_params = sum(x.size for x in jax.tree_util.tree_leaves(params))
    param_dtypes = sorted({str(x.dtype)
                           for x in jax.tree_util.tree_leaves(params)})

    engine = ServingEngine(
        model, params, n_blocks=len(prompts) * blocks_per_req * 2 + 1,
        block_size=BLOCK_SIZE, max_slots=len(prompts),
        prefill_chunk=PREFILL_CHUNK, seed=SEED,
        # one decode-step shape for the whole run
        min_table_width=1 << (blocks_per_req - 1).bit_length())

    # record the decode step's argument shapes (its state is donated)
    step_args = []
    jitted_step = engine._step

    def recording_step(*args):
        if not step_args:
            step_args.append(jax.tree_util.tree_map(
                lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                               sharding=x.sharding), args))
        return jitted_step(*args)
    engine._step = recording_step

    rids = [engine.submit(p, GEN, arrival=i * STAGGER)
            for i, p in enumerate(prompts)]
    with timer.phase() as t:
        outs = engine.run()
    n_tokens = sum(len(outs.get(r, ())) for r in rids)
    complete = all(len(outs.get(r, ())) == GEN for r in rids)
    in_vocab = all(0 <= int(x) < cfg.vocab_size
                   for r in rids for x in outs.get(r, ()))
    step_kernel = has_kernel(jitted_step.lower(*step_args[0]).compile())
    report("serve", model=cfg.name, n_params=int(n_params),
           param_dtypes=param_dtypes, prompt_lens=list(PROMPT_LENS),
           requests_submitted=len(rids),
           requests_completed=engine.stats["requests_completed"],
           tokens_generated=n_tokens, engine_steps=engine.step_count,
           init_s=t_init.wall_s, compile_s=t.compile_s, run_s=t.run_s,
           peak_bytes_in_use=peak_bytes(),
           decode_step_has_tpu_custom_call=step_kernel)
    if not (complete and in_vocab and step_kernel
            and param_dtypes == ["bfloat16"]):
        raise AssertionError(
            f"serve: complete={complete} in_vocab={in_vocab} "
            f"kernel={step_kernel} dtypes={param_dtypes}")


# -------------------------------- train ----------------------------------


def gpt2_medium():
    from repro.configs.registry import get_arch
    return get_arch("gpt2-medium")


def train_losses(timer, plan, mesh, cfg, *, check_kernel=False,
                 steps=TRAIN_STEPS, seed=SEED):
    """Per-step losses of ``plan`` on ``mesh`` (plan.make_train_step /
    init_state, as launch/train.py calls them) with compile and run
    times, and — ``check_kernel`` — whether the compiled step holds a
    Pallas kernel."""
    import jax
    import jax.numpy as jnp

    from repro.data.synthetic import batch_for_model
    from repro.models import build_model
    from repro.optim import AdamW
    from repro.parallel.plan import init_state, make_train_step

    model = build_model(cfg)
    opt = AdamW(lr=TRAIN_LR, param_dtype=cfg.compute_dtype)
    params = model.init(jax.random.PRNGKey(seed))
    state = init_state(plan, opt, params, mesh)
    step = make_train_step(plan, model, opt, mesh, params_template=params,
                           donate=True)
    del params

    def batch(i):
        return {k: jnp.asarray(v) for k, v in batch_for_model(
            cfg, "train", i, TRAIN_BATCH, TRAIN_SEQ, seed).items()}

    losses = []
    with timer.phase() as t:
        for i in range(steps):
            state, metrics = step(state, batch(i))
            losses.append(float(metrics["loss"]))
    kernel = None
    if check_kernel:
        kernel = has_kernel(step.jitted.lower(state, batch(0)).compile())
    del state
    gc.collect()
    return {"losses": losses, "compile_s": t.compile_s, "run_s": t.run_s,
            "has_kernel": kernel}


def phase_train(timer):
    from repro.launch.train import build_mesh
    from repro.parallel.plan import ParallelPlan

    cfg = gpt2_medium()
    # launch/train.py's gspmd plan
    plan = ParallelPlan(mode="gspmd", tp=1, fsdp=False, zero1=False,
                        batch_axes=("data",))
    r = train_losses(timer, plan, build_mesh("gspmd"), cfg,
                     check_kernel=True)
    report("train", model=cfg.name, plan="gspmd", batch=TRAIN_BATCH,
           seq=TRAIN_SEQ, steps=len(r["losses"]), losses=r["losses"],
           compile_s=r["compile_s"], run_s=r["run_s"],
           peak_bytes_in_use=peak_bytes(),
           train_step_has_tpu_custom_call=r["has_kernel"])
    if not (len(r["losses"]) >= 3
            and all(math.isfinite(x) for x in r["losses"])
            and r["has_kernel"]):
        raise AssertionError(f"train: {r}")


# ------------------------------ four chips -------------------------------


def phase_four_chips(timer):
    """gpt2-medium under ddp (overlap), ddp+zero1 and pp 1f1b over four
    stages, each against the gspmd executor on the same four chips.

    The reference runs the jnp attention core: GSPMD cannot partition a
    Pallas kernel across chips (the explicit executors run it per chip
    inside shard_map)."""
    import dataclasses

    from repro.launch.mesh import make_mesh
    from repro.launch.train import build_mesh
    from repro.parallel.plan import ParallelPlan

    cfg = gpt2_medium()
    paths = {
        # data-parallel GSPMD over the four chips: the reference
        "gspmd": (ParallelPlan(mode="gspmd", tp=1, fsdp=False, zero1=False,
                               batch_axes=("data",)),
                  make_mesh((4, 1), ("data", "model"))),
        "ddp_overlap": (ParallelPlan(mode="ddp", batch_axes=("pod", "data"),
                                     overlap=True),
                        build_mesh("ddp")),
        "ddp_zero1": (ParallelPlan(mode="ddp", batch_axes=("pod", "data"),
                                   overlap=False, zero1=True),
                      build_mesh("ddp")),
        "pp_1f1b": (ParallelPlan(mode="pp", batch_axes=("pod", "data"),
                                 pp_schedule="1f1b", pp_microbatches=4),
                    build_mesh("pp", 4)),
    }
    results = {}
    for name, (plan, mesh) in paths.items():
        r = train_losses(timer, plan, mesh, dataclasses.replace(
            cfg, attn_impl="ref") if name == "gspmd" else cfg)
        results[name] = r
        report(f"four_chips.{name}", mesh=dict(mesh.shape),
               losses=r["losses"], compile_s=r["compile_s"],
               run_s=r["run_s"], peak_bytes_in_use=peak_bytes())
    ref = results["gspmd"]["losses"]
    worst = {}
    for name, r in results.items():
        if name == "gspmd":
            continue
        worst[name] = max(abs(a - b) / abs(b)
                          for a, b in zip(r["losses"], ref))
    report("four_chips.compare", reference="gspmd", max_rel_diff=worst,
           rtol=LOSS_RTOL)
    finite = all(math.isfinite(x) for r in results.values()
                 for x in r["losses"])
    if not finite or any(not v <= LOSS_RTOL for v in worst.values()):
        raise AssertionError(f"four chips: finite={finite} worst={worst}")


# --------------------------------- main ----------------------------------


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip training paths")
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    dev = {"platform": devices[0].platform, "kind": devices[0].device_kind,
           "count": len(devices)}
    if dev["platform"] != "tpu":
        print(f"chip_smoke: no TPU (JAX sees {dev}); nothing run",
              file=sys.stderr)
        return 1
    want = 4 if args.four_chips else 1
    if dev["count"] < want:
        print(f"chip_smoke: needs {want} chips, found {dev['count']}",
              file=sys.stderr)
        return 1

    from repro.launch.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    print(f"device {json.dumps(dev)}; compile cache {cache_dir}",
          flush=True)

    timer = Timer()
    if args.four_chips:
        phase_four_chips(timer)
    else:
        phase_kernels(timer)
        phase_serve(timer)
        gc.collect()
        phase_train(timer)
    print(json.dumps({"ok": True, "device": dev}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
