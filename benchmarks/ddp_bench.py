"""Explicit-DDP suite: overlapped vs post-hoc HFReduce, bucketed vs
monolithic.

Runs the ``core/ddp.py`` shard_map train step on an 8-fake-device
(2 pods x 4) CPU mesh in a subprocess (the parent process must keep its
single-device jax, same trick as tests/test_collectives.py) and reports,
per variant:

  * steps/s of the jitted step (CPU walltime — *relative* cost of the
    schedule structure, not TPU perf), and
  * the analytic weak-link bytes/step each chip pushes across the pod
    boundary (core/hfreduce.py cost model), which is what the paper's
    Fig. 8 scaling argument actually turns on.

Variants: overlap on/off (per-bucket custom_vjp sync inside the backward
vs post-hoc whole-tree sync) x bucketed/monolithic, plus the flat
(non-hierarchical) allreduce baseline for the byte model.  Writes
``BENCH_ddp.json``; ``REPRO_BENCH_SMOKE=1`` shrinks the model and step
counts for the CI lane.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time

from benchmarks.common import emit

OUT_PATH = os.environ.get("REPRO_BENCH_DDP", "BENCH_ddp.json")
_MARK = "DDP_BENCH_JSON:"


def _child():
    """Runs with 8 fake devices; prints one JSON report line."""
    import dataclasses as dc

    import jax
    import jax.numpy as jnp

    from repro.configs.registry import smoke_config
    from repro.core.ddp import make_ddp_train_step
    from repro.core.hfreduce import crosspod_bytes_flat, crosspod_bytes_hier
    from repro.data.synthetic import batch_for_model
    from repro.models import build_model
    from repro.optim import AdamW
    from repro.parallel.plan import ParallelPlan
    from repro.launch.mesh import make_mesh

    smoke = os.environ.get("REPRO_BENCH_SMOKE") == "1"
    n_layers, steps, bucket_kib = (2, 2, 64) if smoke else (4, 8, 256)
    cfg = dc.replace(smoke_config("phi4-mini-3.8b"), n_layers=n_layers,
                     compute_dtype="float32")
    model = build_model(cfg)
    opt = AdamW(lr=1e-3, param_dtype="float32")
    params = model.init(jax.random.PRNGKey(0))
    state = opt.init(params)
    mesh = make_mesh((2, 4), ("pod", "data"))
    batch = {k: jnp.asarray(v)
             for k, v in batch_for_model(cfg, "train", 0, 8, 32).items()}
    loss_fn = lambda p, b: model.loss(p, b)  # noqa: E731

    grad_bytes = sum(l.size * l.dtype.itemsize
                     for l in jax.tree_util.tree_leaves(params))
    pods, intra = mesh.shape["pod"], mesh.shape["data"]

    variants = [
        ("overlap_bucketed", dict(overlap=True, bucketed=True)),
        ("posthoc_bucketed", dict(overlap=False, bucketed=True)),
        ("posthoc_monolithic", dict(overlap=False, bucketed=False)),
    ]
    records = []
    for name, kw in variants:
        plan = ParallelPlan(mode="ddp", bucket_bytes=bucket_kib << 10, **kw)
        step, bplan = make_ddp_train_step(loss_fn, opt, mesh, plan,
                                          params_template=params)
        st = jax.tree_util.tree_map(jnp.copy, state)
        st, _ = jax.block_until_ready(step(st, batch))     # compile
        t0 = time.perf_counter()
        for _ in range(steps):
            st, metrics = step(st, batch)
        jax.block_until_ready(st)
        dt = (time.perf_counter() - t0) / steps
        n_collectives = len(bplan.bucket_slices) if kw["bucketed"] \
            else len(jax.tree_util.tree_leaves(params))
        records.append({
            "variant": name, **kw,
            "n_buckets": n_collectives,
            "steps_per_s": 1.0 / dt,
            "crosspod_bytes_per_step":
                crosspod_bytes_hier(grad_bytes, pods, intra),
            "crosspod_bytes_flat_baseline":
                crosspod_bytes_flat(grad_bytes, pods, intra),
            "loss": float(metrics["loss"]),
        })
    print(_MARK + json.dumps({
        "backend": jax.default_backend(), "smoke": smoke,
        "mesh": {"pod": pods, "data": intra},
        "model": cfg.name, "n_layers": n_layers,
        "grad_bytes": grad_bytes, "steps": steps,
        "variants": records,
    }))


def run():
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["JAX_PLATFORMS"] = "cpu"    # fake devices; never the accelerator
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (src, env.get("PYTHONPATH", "")) if p)
    out = subprocess.run(
        [sys.executable, "-m", "benchmarks.ddp_bench", "--child"],
        capture_output=True, text=True, env=env, timeout=1200,
        cwd=os.path.join(os.path.dirname(__file__), ".."))
    if out.returncode != 0:
        raise RuntimeError("ddp_bench child failed:\n" + out.stderr[-3000:])
    payload = None
    for line in out.stdout.splitlines():
        if line.startswith(_MARK):
            payload = json.loads(line[len(_MARK):])
    if payload is None:
        raise RuntimeError("no report in child output:\n" + out.stdout)

    base = next(v for v in payload["variants"]
                if v["variant"] == "posthoc_bucketed")
    for v in payload["variants"]:
        emit(f"ddp.{v['variant']}.step", 1e6 / v["steps_per_s"],
             f"steps/s={v['steps_per_s']:.2f} buckets={v['n_buckets']} "
             f"weakGB={v['crosspod_bytes_per_step'] / 1e9:.4f} "
             f"vs_posthoc={v['steps_per_s'] / base['steps_per_s']:.2f}x")
    emit("ddp.weaklink_model", 0,
         f"hier={base['crosspod_bytes_per_step'] / 1e6:.2f}MB "
         f"flat={base['crosspod_bytes_flat_baseline'] / 1e6:.2f}MB "
         f"(x{base['crosspod_bytes_flat_baseline'] / max(base['crosspod_bytes_per_step'], 1e-9):.1f})")
    with open(OUT_PATH, "w") as f:
        json.dump(payload, f, indent=2)
    emit("ddp.bench_written", 0,
         f"{OUT_PATH}({len(payload['variants'])}variants)")
    return {"ok": True, **payload}


if __name__ == "__main__":
    if "--child" in sys.argv:
        _child()
    else:
        run()
