"""End-to-end training driver: a GPT2-medium-family LM on synthetic data
with the full substrate — prefetching loader, periodic chunked checkpoints
to a 3FS cluster, resume, LR schedule.

  PYTHONPATH=src python examples/train_lm.py --steps 300      # ~100M-class
  PYTHONPATH=src python examples/train_lm.py --steps 40 --small   # quick
"""
import argparse
import dataclasses
import os
import tempfile
import time

import jax
import jax.numpy as jnp

from repro.ckpt import CheckpointManager
from repro.ckpt.manager import _FS3Backend
from repro.configs.registry import get_arch
from repro.data import make_synthetic_loader
from repro.fs3 import FS3Client, FS3Cluster
from repro.launch.mesh import make_mesh
from repro.models import build_model
from repro.optim import AdamW, warmup_cosine
from repro.parallel.plan import ParallelPlan, init_state, make_train_step


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--small", action="store_true",
                    help="shrink the model for a fast demo")
    ap.add_argument("--workdir", default="")
    args = ap.parse_args()

    cfg = get_arch("gpt2-medium")
    if args.small:
        cfg = dataclasses.replace(cfg, n_layers=4, d_model=256, n_heads=8,
                                  n_kv_heads=8, d_ff=1024, vocab_size=8192)
    cfg = dataclasses.replace(cfg, compute_dtype="float32")
    model = build_model(cfg)
    print(f"training {cfg.name}: {cfg.param_count():,} params")

    opt = AdamW(lr=warmup_cosine(3e-4, 20, args.steps),
                param_dtype="float32")
    params = model.init(jax.random.PRNGKey(0))
    mesh = make_mesh((1, 1), ("data", "model"))
    plan = ParallelPlan(mode="gspmd", tp=1, fsdp=False,
                        batch_axes=("data",))
    state = init_state(plan, opt, params, mesh)
    step_fn = make_train_step(plan, model, opt, mesh,
                              params_template=params, donate=True)

    workdir = args.workdir or tempfile.mkdtemp(prefix="train_lm_")
    cluster = FS3Cluster(os.path.join(workdir, "fs3"), n_nodes=2,
                         targets_per_node=2, replication=2)
    mgr = CheckpointManager(_FS3Backend(FS3Client(cluster)),
                            period_s=60.0)
    start = 0
    restored = mgr.restore_latest(state)
    if restored:
        state, start = restored
        print(f"resumed from step {start}")

    loader = make_synthetic_loader(cfg, args.batch, args.seq,
                                   start_step=start)
    t0 = time.time()
    try:
        for step, batch in loader:
            if step >= args.steps:
                break
            batch = {k: jnp.asarray(v) for k, v in batch.items()}
            state, metrics = step_fn(state, batch)
            if step % 10 == 0:
                print(f"step {step:4d} loss {float(metrics['loss']):.4f} "
                      f"({(time.time() - t0) / max(step - start + 1, 1):.2f}"
                      f"s/step)")
            mgr.maybe_save(state, step)
    finally:
        loader.stop()
        mgr.wait()
    mgr.save(state, min(step, args.steps), blocking=True)
    print(f"done; checkpoints in {workdir} (3FS-backed, CRAQ-replicated)")


if __name__ == "__main__":
    main()
