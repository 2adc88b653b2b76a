"""End-to-end training driver.

  # CPU smoke (reduced config, 1 device):
  PYTHONPATH=src python -m repro.launch.train --arch llama3-405b --smoke \\
      --steps 20 --batch 8 --seq 128

  # explicit HFReduce DDP path (overlapped bucket sync):
  PYTHONPATH=src python -m repro.launch.train --arch phi4-mini-3.8b \\
      --smoke --parallel ddp --steps 20 --batch 8 --seq 128

  # pipelined path (1F1B over a "pipe" mesh axis):
  PYTHONPATH=src python -m repro.launch.train --arch phi4-mini-3.8b \\
      --smoke --parallel pp --pp-microbatches 4 --steps 20 --batch 8

The executor is selected by ``--parallel {gspmd,ddp,pp}``, which builds a
``repro.parallel.plan.ParallelPlan`` (DESIGN.md §3) and hands it to the
single entry point ``plan.make_train_step``.  The production lowering path
is exercised by launch/dryrun.py; this driver runs real steps on whatever
devices exist, with checkpointing + the fault-tolerant platform runner.
"""
from __future__ import annotations

import argparse
import dataclasses
import warnings

import jax
import jax.numpy as jnp
import numpy as np

from repro.launch.mesh import make_mesh


def build_mesh(parallel: str, pp_stages: int = 1):
    """Axis layout per executor (all degenerate axes keep size 1)."""
    n = len(jax.devices())
    if parallel == "ddp":
        # weak "pod" axis first: a single host has no pod boundary, so
        # pods=1 and HFReduce's cross-pod phase is a no-op
        return make_mesh((1, n), ("pod", "data"))
    if parallel == "pp":
        if n % pp_stages:
            raise SystemExit(f"--pp-stages {pp_stages} does not divide "
                             f"{n} devices")
        return make_mesh((pp_stages, 1, n // pp_stages),
                             ("pipe", "pod", "data"))
    return make_mesh((1, len(jax.devices())), ("data", "model")) \
        if n > 1 else make_mesh((1, 1), ("data", "model"))


def build_plan(args) -> "object":
    from repro.parallel.plan import ParallelPlan
    bucket_bytes = args.bucket_mb * (1 << 20) if args.bucket_mb else None
    if args.parallel != "ddp":
        # refuse rather than silently ignore explicit-DDP-only knobs
        for flag, name in ((args.zero1, "--zero1"),
                           (args.no_overlap, "--no-overlap")):
            if flag:
                raise SystemExit(
                    f"{name} applies to --parallel ddp only (the gspmd "
                    "path takes ZeRO-1 from parallel/spec.py profiles; "
                    "the pp path has no overlap hooks)")
    if args.parallel == "gspmd":
        if args.compress or args.bucket_mb:
            raise SystemExit("--compress/--bucket-mb apply to the "
                             "explicit paths (--parallel ddp/pp) only")
        return ParallelPlan(mode="gspmd", tp=1, fsdp=False, zero1=False,
                            batch_axes=("data",),
                            microbatch=args.microbatch)
    if args.parallel == "ddp":
        return ParallelPlan(
            mode="ddp", batch_axes=("pod", "data"),
            compress=args.compress,
            bucket_bytes=bucket_bytes,
            overlap=not args.no_overlap and not args.zero1,
            zero1=args.zero1)
    return ParallelPlan(
        mode="pp", batch_axes=("pod", "data"),
        compress=args.compress,
        bucket_bytes=bucket_bytes,
        pp_schedule=args.pp_schedule,
        pp_microbatches=args.pp_microbatches)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced same-family config (CPU-runnable)")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--microbatch", type=int, default=1)
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=10)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--ckpt-async", action="store_true",
                    help="write periodic checkpoints off the critical "
                         "path (D2H snapshot + background chunk write)")
    ap.add_argument("--ckpt-fs3", action="store_true",
                    help="checkpoint into an in-process 3FS cluster "
                         "(CRAQ-replicated) under --ckpt-dir instead of "
                         "plain files")
    ap.add_argument("--resume-plan", action="store_true",
                    help="allow resuming a checkpoint stamped under a "
                         "different ParallelPlan/device count (cross-plan "
                         "reshard of the flat masters)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--log-every", type=int, default=5)
    ap.add_argument("--parallel", choices=("gspmd", "ddp", "pp"),
                    default="gspmd",
                    help="executor: GSPMD sharding rules, explicit "
                         "HFReduce DDP (shard_map), or the pipelined path")
    ap.add_argument("--ddp", action="store_true",
                    help="deprecated alias for --parallel ddp")
    # --- ParallelPlan knobs (ddp / pp) ---
    ap.add_argument("--compress", default="",
                    choices=("", "bf16", "fp8", "int8"),
                    help="cross-pod gradient wire format")
    ap.add_argument("--bucket-mb", type=int, default=0,
                    help="gradient bucket budget in MiB (0: default)")
    ap.add_argument("--no-overlap", action="store_true",
                    help="post-hoc whole-tree grad sync (parity baseline)")
    ap.add_argument("--zero1", action="store_true",
                    help="explicit ZeRO-1: flat-sharded fp32 masters")
    ap.add_argument("--pp-stages", type=int, default=0,
                    help="pipeline stages (default: all devices)")
    ap.add_argument("--pp-schedule", choices=("gpipe", "1f1b"),
                    default="1f1b")
    ap.add_argument("--pp-microbatches", type=int, default=4)
    ap.add_argument("--trace", default="",
                    help="record a JAX profiler trace of the run under "
                         "this directory: the program's spans and the "
                         "device's ops on one clock (.xplane.pb, and a "
                         "Perfetto trace.json.gz)")
    args = ap.parse_args(argv)
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    if args.ddp:
        warnings.warn("--ddp is deprecated; use --parallel ddp",
                      DeprecationWarning, stacklevel=2)
        args.parallel = "ddp"

    from repro.configs.registry import get_arch, smoke_config
    from repro.data import make_synthetic_loader
    from repro.models import build_model
    from repro.optim import AdamW, warmup_cosine
    from repro.parallel import plan as plan_lib

    cfg = smoke_config(args.arch) if args.smoke else get_arch(args.arch)
    if args.smoke:
        cfg = dataclasses.replace(cfg, compute_dtype="float32")
    model = build_model(cfg)
    opt = AdamW(lr=warmup_cosine(args.lr, 5, args.steps),
                param_dtype=cfg.compute_dtype)

    if args.parallel == "pp" and not args.pp_stages:
        args.pp_stages = max(d for d in range(1, len(jax.devices()) + 1)
                             if cfg.n_layers % d == 0
                             and len(jax.devices()) % d == 0)
    mesh = build_mesh(args.parallel, args.pp_stages)
    plan = build_plan(args)

    rng = jax.random.PRNGKey(args.seed)
    params = model.init(rng)
    state = plan_lib.init_state(plan, opt, params, mesh)
    step_fn = plan_lib.make_train_step(
        plan, model, opt, mesh, params_template=params, donate=True)

    manager = None
    start_step = 0
    if args.ckpt_dir:
        from repro.elastic import ElasticCheckpointer, PlanMismatchError
        backend = args.ckpt_dir
        if args.ckpt_fs3:
            from repro.ckpt import fs3_backend
            backend = fs3_backend(args.ckpt_dir)
        manager = ElasticCheckpointer(backend, plan, mesh)
        if args.resume:
            if args.resume_plan:
                restored = manager.restore_for(plan, mesh, params)
            else:
                try:
                    restored = manager.restore_latest(state)
                except PlanMismatchError as e:
                    raise SystemExit(f"{e}") from e
            if restored is not None:
                state, start_step = restored
                print(f"resumed from step {start_step}")

    from repro.telemetry import now
    loader = make_synthetic_loader(cfg, args.batch, args.seq,
                                   seed=args.seed, start_step=start_step)
    t0 = now()
    losses = []
    if args.trace:
        jax.profiler.start_trace(args.trace, create_perfetto_trace=True)
    try:
        for step, batch in loader:
            if step >= args.steps:
                break
            batch = {k: jnp.asarray(v) for k, v in batch.items()}
            state, metrics = step_fn(state, batch)
            loss = float(metrics["loss"])
            losses.append(loss)
            if step % args.log_every == 0 or step == args.steps - 1:
                dt = now() - t0
                print(f"step {step:5d} loss {loss:.4f} "
                      f"({dt / max(step - start_step + 1, 1):.3f}s/step)")
            if manager and args.ckpt_every and step and \
                    step % args.ckpt_every == 0:
                manager.save(state, step, blocking=not args.ckpt_async)
    finally:
        loader.stop()
        if manager:
            manager.wait()
        if args.trace:
            jax.profiler.stop_trace()
            print(f"trace written under {args.trace}")

    if manager:
        manager.save(state, min(args.steps, step), blocking=True)
    print(f"final loss {losses[-1]:.4f} (first {losses[0]:.4f})")
    return losses


if __name__ == "__main__":
    main()
