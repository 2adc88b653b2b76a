"""Elastic fault-tolerance drill (DESIGN.md §13), end to end:

  1. train a 2-stage pipeline-parallel model across all 8 (fake) devices,
     with plan-stamped checkpoints written asynchronously into an
     in-process 3FS cluster;
  2. inject a *fatal* hardware failure drawn from the paper's Table-V
     failure model mid-window (the "kill");
  3. the platform reshards the last checkpoint's flat fp32 masters onto
     a ddp+ZeRO-1 plan over the 4 surviving devices (the "rescale");
  4. training resumes on the smaller gang and the loss keeps tracking
     an unbroken reference run.

  PYTHONPATH=src python examples/fault_tolerant_train.py
"""
import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")
os.environ["JAX_PLATFORMS"] = "cpu"     # 8 fake CPU devices, never a chip

import dataclasses  # noqa: E402
import json  # noqa: E402
import tempfile  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.ckpt import fs3_backend  # noqa: E402
from repro.configs.registry import smoke_config  # noqa: E402
from repro.data.synthetic import batch_for_model  # noqa: E402
from repro.elastic import ElasticCheckpointer  # noqa: E402
from repro.launch.mesh import make_mesh  # noqa: E402
from repro.models import build_model  # noqa: E402
from repro.optim import AdamW  # noqa: E402
from repro.parallel.plan import (ParallelPlan, init_state,  # noqa: E402
                                 make_train_step)
from repro.platform import (FailureInjector, FailureModel,  # noqa: E402
                            FTRunner)

STEPS, KILL_AT, CKPT_EVERY = 14, 7, 5
BATCH, SEQ = 16, 32


def main():
    cfg = dataclasses.replace(smoke_config("phi4-mini-3.8b"),
                              n_layers=2, compute_dtype="float32")
    model = build_model(cfg)
    opt = AdamW(lr=1e-3, param_dtype="float32")
    params = model.init(jax.random.PRNGKey(0))

    # two worlds: healthy = pp over all 8 devices; degraded = ddp+zero1
    # over the 4 survivors.  Both are just ParallelPlans — the elastic
    # layer reshards the checkpoint between them.
    mesh_pp = make_mesh((2, 2, 2), ("pipe", "pod", "data"))
    mesh_dp = make_mesh((1, 4), ("pod", "data"),
                        devices=jax.devices()[:4])
    plan_pp = ParallelPlan(mode="pp", pp_microbatches=2)
    plan_dp = ParallelPlan(mode="ddp", zero1=True, overlap=False)

    def plan_for(world):
        return (plan_pp, mesh_pp) if world >= 2 else (plan_dp, mesh_dp)

    def fetch(i):
        return {k: jnp.asarray(v) for k, v in
                batch_for_model(cfg, "train", i, BATCH, SEQ).items()}

    # paper-calibrated failure schedule: first *fatal* class in the stream
    fm = FailureModel(seed=1)
    print(f"node MTBF {fm.mtbf_node_hours():.0f}h; at 1250 nodes one "
          f"failure every {fm.cluster_mtbf_hours(1250):.2f}h "
          f"-> 5-min checkpoints")
    cls = next(e.cls for e in fm.sample(1250, 48.0) if e.fatal)
    print(f"injecting fatal {cls!r} at step {KILL_AT}")

    # unbroken reference trajectory for comparison
    ref, st = [], init_state(plan_pp, opt, params, mesh_pp)
    step_pp = make_train_step(plan_pp, model, opt, mesh_pp,
                              params_template=params)
    for i in range(STEPS):
        st, mets = step_pp(st, fetch(i))
        ref.append(float(mets["loss"]))

    losses, step_cache = [], {}

    def make_step(world):
        if world not in step_cache:
            p, m = plan_for(world)
            print(f"  [platform] building {p.mode} step for world={world} "
                  f"({len(m.devices.flat)} devices)")
            base = make_train_step(p, model, opt, m, params_template=params)

            def wrapped(state, batch, _base=base):
                state, mets = _base(state, batch)
                losses.append(float(mets["loss"]))
                return state, mets

            step_cache[world] = wrapped
        return step_cache[world]

    with tempfile.TemporaryDirectory() as d:
        # async plan-stamped checkpoints into a CRAQ-replicated 3FS sim
        mgr = ElasticCheckpointer(fs3_backend(d), plan_pp, mesh_pp)

        def restore_fn(_template, new_world):
            p, m = plan_for(new_world)
            return mgr.restore_for(p, m, params)   # cross-plan reshard

        runner = FTRunner(make_step, fetch, mgr,
                          init_state(plan_pp, opt, params, mesh_pp),
                          world_size=2, min_world=1, ckpt_every=CKPT_EVERY,
                          injector=FailureInjector({KILL_AT: cls}),
                          restore_fn=restore_fn,
                          on_event=lambda k, kw: print(f"  [event] {k} "
                                                       f"{kw}"))
        report = runner.run(STEPS)
        events = runner.event_log.events

    print(f"\nsteps={report.steps_done} failures={report.failures} "
          f"restores={report.restores} rescales={report.rescales} "
          f"lost_steps={report.lost_steps} world={runner.world}")
    for e in events:
        print("  " + json.dumps({k: v for k, v in e.items() if k != "t"}))

    # post-restore losses replay the lost window on the shrunken gang
    cont = losses[KILL_AT:]
    err = max(abs(a - b)
              for a, b in zip(cont, ref[KILL_AT - report.lost_steps:]))
    print(f"\nloss: first={losses[0]:.4f} last={losses[-1]:.4f} "
          f"(reshard divergence vs unbroken pp run: {err:.2e})")
    assert runner.world == 1 and report.rescales == 1
    assert err <= 1e-5, err
    assert losses[-1] < losses[0]


if __name__ == "__main__":
    main()
