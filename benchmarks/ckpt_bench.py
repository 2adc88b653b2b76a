"""Checkpoint pipeline suite (DESIGN.md §13 budget: async save steals
< 5 % of step time).

The elastic checkpointer's critical-path cost is the synchronous part of
``save(..., blocking=False)``: D2H snapshot + manifest build + thread
handoff — chunk packing and backend writes happen off-thread while the
next steps run.  The suite times *paired rounds* of ``every`` train
steps under three regimes — no checkpointing, one async save per round,
one blocking save per round — in rotating order, and takes the median
of the per-round deltas (adjacent pairing cancels machine drift, the
median discards scheduler outliers; same technique as the telemetry
suite).  ``overhead_pct`` is the async delta over the base round;
``blocking_pct`` is what a synchronous save would steal instead — the
gap is what the pipeline hides.  ``ok`` keys off the 5 % target.

Off-TPU the *ratio* is the point, not absolute times.  Emits CSV rows
and writes ``BENCH_ckpt.json``.
"""
from __future__ import annotations

import dataclasses
import json
import os
import shutil
import statistics
import tempfile
import time

import jax
import jax.numpy as jnp

from benchmarks.common import emit

OUT_PATH = os.environ.get("REPRO_BENCH_CKPT", "BENCH_ckpt.json")
OVERHEAD_TARGET_PCT = 5.0


def _cases():
    if jax.default_backend() == "tpu" and \
            os.environ.get("REPRO_BENCH_SMOKE") != "1":
        return dict(n_layers=2, batch=8, seq=256, every=5, rounds=12,
                    warmup=5)
    return dict(n_layers=2, batch=8, seq=128, every=5, rounds=8, warmup=3)


def _setup(c):
    from repro.configs.registry import smoke_config
    from repro.data import make_synthetic_loader
    from repro.launch.mesh import make_mesh
    from repro.models import build_model
    from repro.optim import AdamW
    from repro.parallel import plan as plan_lib
    from repro.parallel.plan import ParallelPlan

    cfg = dataclasses.replace(smoke_config("phi4-mini-3.8b"),
                              n_layers=c["n_layers"],
                              compute_dtype="float32")
    model = build_model(cfg)
    opt = AdamW(lr=1e-3, param_dtype="float32")
    plan = ParallelPlan(mode="gspmd")
    mesh = make_mesh((1, 1), ("data", "model"))
    params = model.init(jax.random.PRNGKey(0))
    state = plan_lib.init_state(plan, opt, params, mesh)
    step_fn = plan_lib.make_train_step(plan, model, opt, mesh,
                                       params_template=params)
    loader = make_synthetic_loader(cfg, c["batch"], c["seq"], seed=0)
    _, batch = next(iter(loader))
    batch = {k: jnp.asarray(v) for k, v in batch.items()}
    loader.stop()
    return plan, mesh, state, step_fn, batch


def run():
    from repro.elastic import ElasticCheckpointer

    c = _cases()
    plan, mesh, state, step_fn, batch = _setup(c)
    for _ in range(c["warmup"]):
        state, _ = step_fn(state, batch)
    jax.block_until_ready(state)

    root = tempfile.mkdtemp(prefix="ckpt_bench_")
    try:
        mgr_a = ElasticCheckpointer(os.path.join(root, "a"), plan, mesh,
                                    keep=3)
        mgr_b = ElasticCheckpointer(os.path.join(root, "b"), plan, mesh,
                                    keep=3)

        def round_of_steps(save):
            """`every` steps; `save(state, step)` fires on the first."""
            nonlocal state
            t0 = time.perf_counter()
            for i in range(c["every"]):
                if i == 0 and save is not None:
                    save(state)
                state, _ = step_fn(state, batch)
                jax.block_until_ready(state)
            return time.perf_counter() - t0

        arms = {
            "base": lambda: round_of_steps(None),
            "async": lambda: round_of_steps(
                lambda s: mgr_a.save(s, next(tick_a), blocking=False)),
            "blocking": lambda: round_of_steps(
                lambda s: mgr_b.save(s, next(tick_b), blocking=True)),
        }
        tick_a, tick_b = iter(range(10_000)), iter(range(10_000))
        order = list(arms)
        walls = {k: [] for k in arms}
        for r in range(c["rounds"]):
            for k in order[r % 3:] + order[:r % 3]:   # rotate arm order
                walls[k].append(arms[k]())
        mgr_a.wait()

        base = statistics.median(walls["base"])
        async_delta = statistics.median(
            a - b for a, b in zip(walls["async"], walls["base"]))
        blocking_delta = statistics.median(
            a - b for a, b in zip(walls["blocking"], walls["base"]))

        t0 = time.perf_counter()
        mgr_b.restore_latest(state)
        restore_wall = time.perf_counter() - t0
    finally:
        shutil.rmtree(root, ignore_errors=True)

    step_us = base / c["every"] * 1e6
    overhead_pct = max(async_delta, 0.0) / base * 100.0
    blocking_pct = max(blocking_delta, 0.0) / base * 100.0
    ok = overhead_pct < OVERHEAD_TARGET_PCT

    emit("ckpt.step.base", step_us, "no checkpointing")
    emit("ckpt.save.async", async_delta * 1e6,
         f"per-round delta pct={overhead_pct:.2f}")
    emit("ckpt.save.blocking", blocking_delta * 1e6,
         f"pct={blocking_pct:.2f}")
    emit("ckpt.restore", restore_wall * 1e6, "cold restore_latest")
    data = {
        "backend": jax.default_backend(),
        "smoke": os.environ.get("REPRO_BENCH_SMOKE") == "1",
        "us_per_step": step_us,
        "ckpt_every": c["every"],
        "rounds": c["rounds"],
        "async_delta_us": async_delta * 1e6,
        "blocking_delta_us": blocking_delta * 1e6,
        "restore_us": restore_wall * 1e6,
        "overhead_pct": overhead_pct,
        "blocking_pct": blocking_pct,
        "overhead_target_pct": OVERHEAD_TARGET_PCT,
        "ok": ok,
    }
    with open(OUT_PATH, "w") as f:
        json.dump(data, f, indent=2)
    emit("ckpt.ok", 0, f"ok={ok} -> {OUT_PATH}")
    return data


if __name__ == "__main__":
    run()
