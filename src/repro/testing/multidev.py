import os
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")
os.environ["JAX_PLATFORMS"] = "cpu"

# ^ must precede any jax import: collective tests need >1 (fake) CPU
# device, and a fake-device process must never open an accelerator.
"""Multi-device numerics checks, run as a subprocess from pytest so the
main test process keeps its single-device jax. Prints one JSON report."""
import json
import sys

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.launch.mesh import make_mesh


def _smap(f, mesh, in_specs, x):
    """``f`` per shard of ``x`` under jit, with a replicated result."""
    return jax.jit(jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                                 out_specs=P(), check_vma=False))(x)


def _mesh():
    return make_mesh((2, 4), ("pod", "data"))


def check_hfreduce():
    from repro.core.hfreduce import hfreduce, flat_allreduce
    mesh = _mesh()
    x = jnp.arange(8 * 1000, dtype=jnp.float32).reshape(8, 1000) / 100.0

    def f(v):
        return hfreduce(v[0], strong_axis="data", weak_axis="pod")

    def g(v):
        return flat_allreduce(v[0], axes=("pod", "data"))

    spec = P(("pod", "data"))
    out_h = _smap(f, mesh, spec, x)
    out_f = _smap(g, mesh, spec, x)
    ref = jnp.sum(x, axis=0)
    return (float(jnp.max(jnp.abs(out_h - ref))),
            float(jnp.max(jnp.abs(out_f - ref))))


def check_tree_allreduce():
    from repro.core.tree_allreduce import tree_allreduce, ring_allreduce
    mesh = make_mesh((8,), ("n",))
    x = jnp.asarray(np.random.default_rng(0).standard_normal((8, 257)),
                    jnp.float32)

    def t(v):
        return tree_allreduce(v[0], "n")

    def r(v):
        return ring_allreduce(v[0], "n")

    ref = jnp.sum(x, axis=0)
    out_t = _smap(t, mesh, P("n"), x)
    out_r = _smap(r, mesh, P("n"), x)
    return (float(jnp.max(jnp.abs(out_t - ref))),
            float(jnp.max(jnp.abs(out_r - ref))))


def check_compressed_psum():
    from repro.core.compression import bf16_psum, int8_psum
    mesh = make_mesh((8,), ("n",))
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.standard_normal((8, 4096)), jnp.float32)
    ref = np.asarray(jnp.sum(x, axis=0))

    def fb(v):
        return bf16_psum(v[0], "n")

    def fi(v):
        return int8_psum(v[0], "n")

    out_b = np.asarray(_smap(fb, mesh, P("n"), x))
    out_i = np.asarray(_smap(fi, mesh, P("n"), x))
    scale = np.abs(ref).max() + 1e-9
    return (float(np.max(np.abs(out_b - ref)) / scale),
            float(np.max(np.abs(out_i - ref)) / scale))


def check_hfreduce_tree_combo():
    from repro.core.hfreduce import hfreduce_tree
    mesh = _mesh()
    x = jnp.asarray(np.random.default_rng(2).standard_normal((8, 333)),
                    jnp.float32)

    def f(v):
        return hfreduce_tree(v[0], strong_axis="data", weak_axis="pod")

    out = _smap(f, mesh, P(("pod", "data")), x)
    ref = jnp.sum(x, axis=0)
    return float(jnp.max(jnp.abs(out - ref)))


def _small_dense():
    import dataclasses as dc
    from repro.configs.registry import smoke_config
    from repro.models import build_model
    from repro.optim import AdamW

    cfg = dc.replace(smoke_config("phi4-mini-3.8b"), n_layers=2,
                     compute_dtype="float32")
    model = build_model(cfg)
    opt = AdamW(lr=1e-2, param_dtype="float32")
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, opt, params


def check_ddp_step():
    """DDP shard_map step (overlapped HFReduce) == single-device step."""
    from repro.configs.base import ParallelConfig
    from repro.core.ddp import make_ddp_train_step
    from repro.parallel.plan import ParallelPlan
    from repro.data.synthetic import batch_for_model

    cfg, model, opt, params = _small_dense()
    state = opt.init(params)
    mesh = _mesh()
    step, _ = make_ddp_train_step(
        lambda p, b: model.loss(p, b), opt, mesh,
        ParallelPlan(mode="ddp"), params_template=params)
    batch = {k: jnp.asarray(v)
             for k, v in batch_for_model(cfg, "train", 0, 8, 32).items()}
    new_state, metrics = step(state, batch)

    # reference: plain single-device full-batch step
    import repro.train_lib as tl
    pcfg = ParallelConfig(tp=1, fsdp=False, batch_axes=())
    ref_step = jax.jit(tl.make_train_step(model, opt, pcfg, mesh))
    ref_state, ref_metrics = ref_step(state, batch)
    dl = jax.tree_util.tree_leaves(new_state["master"])
    rl = jax.tree_util.tree_leaves(ref_state["master"])
    err = max(float(jnp.max(jnp.abs(a - b))) for a, b in zip(dl, rl))
    return err, float(metrics["loss"]), float(ref_metrics["loss"])


def check_ddp_compressed():
    """int8-compressed hierarchical DDP still trains (bounded grad error)."""
    import dataclasses as dc
    from repro.configs.registry import smoke_config
    from repro.models import build_model
    from repro.optim import AdamW
    from repro.core.ddp import make_ddp_train_step
    from repro.parallel.plan import ParallelPlan
    from repro.data.synthetic import batch_for_model

    cfg = dc.replace(smoke_config("xlstm-125m"), block_pattern="ms",
                     n_layers=2, compute_dtype="float32")
    model = build_model(cfg)
    opt = AdamW(lr=1e-2, param_dtype="float32")
    state = opt.init(model.init(jax.random.PRNGKey(0)))
    mesh = _mesh()
    step, _ = make_ddp_train_step(
        lambda p, b: model.loss(p, b), opt, mesh,
        ParallelPlan(mode="ddp", compress="int8"),
        params_template=state["params"])
    losses = []
    for i in range(3):
        batch = {k: jnp.asarray(v)
                 for k, v in batch_for_model(cfg, "train", i, 8, 32).items()}
        state, metrics = step(state, batch)
        losses.append(float(metrics["loss"]))
    return losses


def check_ddp_overlap():
    """Overlapped (in-backward custom_vjp hooks) bucket sync == post-hoc
    whole-tree sync, across bucket budgets and wire compression."""
    import dataclasses as dc
    from repro.core.ddp import make_ddp_train_step
    from repro.parallel.plan import ParallelPlan
    from repro.data.synthetic import batch_for_model

    cfg, model, opt, params = _small_dense()
    state = opt.init(params)
    mesh = _mesh()
    loss_fn = lambda p, b: model.loss(p, b)
    batch = {k: jnp.asarray(v)
             for k, v in batch_for_model(cfg, "train", 0, 8, 32).items()}
    rows = []
    for bucket_bytes in (1 << 16, 1 << 22):
        for compress in ("", "int8"):
            plan_o = ParallelPlan(mode="ddp", overlap=True,
                                  compress=compress,
                                  bucket_bytes=bucket_bytes)
            step_o, bplan = make_ddp_train_step(
                loss_fn, opt, mesh, plan_o, params_template=params)
            step_p, _ = make_ddp_train_step(
                loss_fn, opt, mesh, dc.replace(plan_o, overlap=False),
                params_template=params)
            so, mo = step_o(jax.tree_util.tree_map(jnp.copy, state), batch)
            sp, mp = step_p(jax.tree_util.tree_map(jnp.copy, state), batch)
            err = max(float(jnp.max(jnp.abs(a - b))) for a, b in zip(
                jax.tree_util.tree_leaves(so["master"]),
                jax.tree_util.tree_leaves(sp["master"])))
            rows.append([bucket_bytes, compress,
                         len(bplan.bucket_slices), err,
                         abs(float(mo["loss"]) - float(mp["loss"]))])
    return rows


def check_ddp_zero1():
    """Explicit ZeRO-1 (reduce-scattered grads, flat-sharded masters,
    param all-gather) tracks the replicated-optimizer DDP step."""
    from repro.core.ddp import make_ddp_train_step, init_zero1_state
    from repro.parallel.plan import ParallelPlan
    from repro.data.synthetic import batch_for_model

    cfg, model, opt, params = _small_dense()
    mesh = _mesh()
    loss_fn = lambda p, b: model.loss(p, b)

    plan_z = ParallelPlan(mode="ddp", zero1=True, overlap=False)
    step_z, _ = make_ddp_train_step(loss_fn, opt, mesh, plan_z,
                                    params_template=params)
    state_z = init_zero1_state(params, opt, mesh, plan_z)

    plan_r = ParallelPlan(mode="ddp", overlap=False)
    step_r, _ = make_ddp_train_step(loss_fn, opt, mesh, plan_r,
                                    params_template=params)
    state_r = opt.init(params)

    losses_z, losses_r = [], []
    for i in range(3):
        batch = {k: jnp.asarray(v)
                 for k, v in batch_for_model(cfg, "train", i, 8, 32).items()}
        state_z, mz = step_z(state_z, batch)
        state_r, mr = step_r(state_r, batch)
        losses_z.append(float(mz["loss"]))
        losses_r.append(float(mr["loss"]))
    err = max(float(jnp.max(jnp.abs(a - b))) for a, b in zip(
        jax.tree_util.tree_leaves(state_z["params"]),
        jax.tree_util.tree_leaves(state_r["params"])))
    return err, losses_z, losses_r


def check_fp8_prescale():
    """Folding the 1/n_shards mean before the compressed cross-pod phase
    keeps fp8 wire values in range; dividing after decompression saturates
    e4m3 (max 448 -> NaN) on pod-sum-magnitude values."""
    from repro.core.hfreduce import hfreduce
    from repro.core.compression import fp8_psum

    mesh = _mesh()
    rng = np.random.default_rng(7)
    # per-shard grads ~150: the intra-pod reduce-scatter sums 4 shards
    # (~600), beyond e4m3's 448 — only the pre-scaled mean survives fp8.
    x = jnp.asarray(150.0 + rng.standard_normal((8, 1024)), jnp.float32)
    ref = np.asarray(jnp.mean(x, axis=0))
    scale = np.abs(ref).max()

    def fold(v):
        return hfreduce(v[0], strong_axis="data", weak_axis="pod",
                        weak_psum=fp8_psum, prescale=1.0 / 8.0)

    def after(v):
        return hfreduce(v[0], strong_axis="data", weak_axis="pod",
                        weak_psum=fp8_psum) / 8.0

    spec = P(("pod", "data"))
    out_fold = np.asarray(_smap(fold, mesh, spec, x))
    out_after = np.asarray(_smap(after, mesh, spec, x))
    err_fold = float(np.max(np.abs(out_fold - ref)) / scale)
    err_after = float(np.max(np.abs(out_after - ref)) / scale)
    if not np.isfinite(err_after):
        err_after = 1e9       # e4m3 overflow -> NaN; report as huge
    return err_fold, err_after


def check_pipeline():
    """4-stage GPipe == sequential layers; grads flow through ppermute."""
    from repro.parallel.pp import make_pipelined_forward
    rng = np.random.default_rng(3)
    L, d, b, m = 8, 16, 8, 4
    W = jnp.asarray(rng.standard_normal((L, d, d)) * 0.3, jnp.float32)
    x = jnp.asarray(rng.standard_normal((b, d)), jnp.float32)

    def layer_fn(w, h):
        return jnp.tanh(h @ w)

    mesh = make_mesh((4, 2), ("pipe", "dp"))
    pp = jax.jit(make_pipelined_forward(layer_fn, n_stages=4, n_micro=m,
                                        mesh=mesh))
    y_pp = pp(W, x)
    y_seq = x
    for i in range(L):
        y_seq = layer_fn(W[i], y_seq)
    fwd_err = float(jnp.max(jnp.abs(y_pp - y_seq)))

    def loss_pp(w):
        return jnp.sum(pp(w, x) ** 2)

    def loss_seq(w):
        h = x
        for i in range(L):
            h = layer_fn(w[i], h)
        return jnp.sum(h ** 2)

    g_pp = jax.jit(jax.grad(loss_pp))(W)
    g_seq = jax.jit(jax.grad(loss_seq))(W)
    grad_err = float(jnp.max(jnp.abs(g_pp - g_seq)))
    return fwd_err, grad_err


def check_pp_train():
    """GPipe and 1F1B pipelined train steps (HFReduce grad sync over
    ("pod","data")) track the single-stage loss trajectory over 5 steps,
    for two microbatch counts."""
    from repro.configs.base import ParallelConfig
    from repro.parallel.plan import ParallelPlan, make_train_step
    from repro.data.synthetic import batch_for_model
    import repro.train_lib as tl

    cfg, model, opt, params = _small_dense()
    state0 = opt.init(params)
    mesh = make_mesh((2, 2, 2), ("pipe", "pod", "data"))

    def fetch(i):
        return {k: jnp.asarray(v)
                for k, v in batch_for_model(cfg, "train", i, 16, 32).items()}

    pcfg = ParallelConfig(tp=1, fsdp=False, batch_axes=())
    ref_step = jax.jit(tl.make_train_step(model, opt, pcfg, mesh))
    ref = jax.tree_util.tree_map(jnp.copy, state0)
    ref_losses = []
    for i in range(5):
        ref, mets = ref_step(ref, fetch(i))
        ref_losses.append(float(mets["loss"]))

    out = {"ref_losses": ref_losses}
    for schedule in ("gpipe", "1f1b"):
        for m in (2, 4):
            plan = ParallelPlan(mode="pp", pp_schedule=schedule,
                                pp_microbatches=m)
            step = make_train_step(plan, model, opt, mesh,
                                   params_template=params)
            st = jax.tree_util.tree_map(jnp.copy, state0)
            losses = []
            for i in range(5):
                st, mets = step(st, fetch(i))
                losses.append(float(mets["loss"]))
            loss_err = max(abs(a - b)
                           for a, b in zip(losses, ref_losses))
            master_err = max(float(jnp.max(jnp.abs(a - b)))
                             for a, b in zip(
                jax.tree_util.tree_leaves(st["master"]),
                jax.tree_util.tree_leaves(ref["master"])))
            out[f"{schedule}_m{m}"] = {"loss_err": loss_err,
                                       "master_err": master_err,
                                       "losses": losses}
    return out


def check_elastic_remesh():
    """Checkpoint saved on an 8-device mesh restores and continues on a
    4-device mesh (elastic shrink) with bit-identical training math."""
    import dataclasses as dc
    import tempfile
    from repro.configs.base import ParallelConfig
    from repro.configs.registry import smoke_config
    from repro.models import build_model
    from repro.optim import AdamW
    from repro.ckpt import CheckpointManager
    from repro.data.synthetic import batch_for_model
    from repro import train_lib

    cfg = dc.replace(smoke_config("phi4-mini-3.8b"), n_layers=2,
                     compute_dtype="float32")
    model = build_model(cfg)
    opt = AdamW(lr=1e-3, param_dtype="float32")

    def fetch(i):
        return {k: jnp.asarray(v) for k, v in
                batch_for_model(cfg, "train", i, 8, 32).items()}

    def run_steps(mesh, state, lo, hi):
        pcfg = ParallelConfig(tp=1, fsdp=True, zero1_pod=False,
                              batch_axes=("data",))
        # explicit placement: an elastic runner re-shards the restored
        # state onto the new (smaller) mesh before continuing
        sspec = train_lib.state_pspecs(model, pcfg, mesh)
        state = jax.device_put(state, train_lib.to_named(sspec, mesh))
        step = jax.jit(train_lib.make_train_step(model, opt, pcfg, mesh))
        for i in range(lo, hi):
            state, _ = step(state, fetch(i))
        return state

    state0 = opt.init(model.init(jax.random.PRNGKey(0)))
    mesh8 = make_mesh((8, 1), ("data", "model"))
    mesh4 = make_mesh((4, 1), ("data", "model"),
                        devices=jax.devices()[:4])

    # unbroken 6-step reference on the large mesh
    ref = run_steps(mesh8, jax.tree_util.tree_map(jnp.copy, state0), 0, 6)

    # elastic: 3 steps on 8 devices -> save -> restore -> 3 more on 4
    with tempfile.TemporaryDirectory() as d:
        mgr = CheckpointManager(d)
        st = run_steps(mesh8, jax.tree_util.tree_map(jnp.copy, state0), 0, 3)
        mgr.save(st, 3, blocking=True)
        st2, start = mgr.restore_latest(state0)
        st2 = run_steps(mesh4, st2, start, 6)

    # pull both to host: ref lives on the 8-dev mesh, st2 on the 4-dev one
    ref_h = jax.device_get(ref["master"])
    st2_h = jax.device_get(st2["master"])
    err = max(float(np.max(np.abs(a - b))) for a, b in zip(
        jax.tree_util.tree_leaves(ref_h),
        jax.tree_util.tree_leaves(st2_h)))
    return err


def _event_digest(report, event_log, tmpdir):
    """Persist the runner's JSONL stream and check exactly-once: the file
    holds the same records as ``report.events`` (one emit point), every
    record is unique (kinds counted, timestamps excluded from the key)."""
    import os as _os
    path = _os.path.join(tmpdir, "events.jsonl")
    event_log.write(path)
    with open(path) as f:
        lines = [json.loads(ln) for ln in f if ln.strip()]
    kinds = {}
    seen = set()
    unique = True
    for rec in lines:
        kinds[rec["kind"]] = kinds.get(rec["kind"], 0) + 1
        key = json.dumps({k: v for k, v in rec.items() if k != "t"},
                         sort_keys=True)
        unique = unique and key not in seen
        seen.add(key)
    return {
        "n_jsonl": len(lines),
        "n_report": len(report.events),
        "jsonl_matches_report": lines == [dict(r) for r in report.events],
        "unique": unique,
        "kinds": kinds,
    }


def check_elastic_kill_resume():
    """Same-plan kill/resume (ddp+zero1, Table-V-sampled non-fatal class)
    through FTRunner + ElasticCheckpointer is *bitwise*: replayed and
    post-restore losses and the final flat masters match the unbroken
    run exactly, and every platform event lands exactly once on the
    runner's event_log JSONL stream."""
    import tempfile
    from repro.data.synthetic import batch_for_model
    from repro.elastic import ElasticCheckpointer
    from repro.optim import AdamW
    from repro.parallel.plan import ParallelPlan, init_state, make_train_step
    from repro.platform.failures import FailureInjector, FailureModel
    from repro.platform.runner import FTRunner

    cfg, model, _, params = _small_dense()
    opt = AdamW(lr=1e-3, param_dtype="float32")
    mesh = _mesh()
    plan = ParallelPlan(mode="ddp", zero1=True, overlap=False)

    def fetch(i):
        return {k: jnp.asarray(v) for k, v in
                batch_for_model(cfg, "train", i, 16, 32).items()}

    base = make_train_step(plan, model, opt, mesh, params_template=params)

    def make_runner(tmp, injector, sink):
        def wrapped(state, batch):
            state, mets = base(state, batch)
            sink.append(float(mets["loss"]))
            return state, mets

        mgr = ElasticCheckpointer(tmp, plan, mesh)
        return FTRunner(lambda world: wrapped, fetch, mgr,
                        init_state(plan, opt, params, mesh),
                        world_size=2, ckpt_every=5, injector=injector)

    # failure class drawn from the paper's Table-V taxonomy; a non-fatal
    # class means the gang survives intact (no rescale) on this leg
    cls = next(e.cls for e in FailureModel(seed=0).sample(1250, 48.0)
               if not e.fatal)

    with tempfile.TemporaryDirectory() as d1, \
            tempfile.TemporaryDirectory() as d2:
        ref_losses = []
        runner_ref = make_runner(d1, None, ref_losses)
        runner_ref.run(10)
        ref_final = jax.device_get(runner_ref.state)

        losses = []
        runner = make_runner(d2, FailureInjector({7: cls}), losses)
        report = runner.run(10)
        final = jax.device_get(runner.state)
        digest = _event_digest(report, runner.event_log, d2)

    # kill at 7 -> restore ckpt 5 -> replay 5..6 -> continue 7..9
    want = ref_losses[:7] + ref_losses[5:]
    state_diff = max(
        float(np.max(np.abs(np.asarray(a, np.float32)
                            - np.asarray(b, np.float32))))
        for a, b in zip(jax.tree_util.tree_leaves(final),
                        jax.tree_util.tree_leaves(ref_final)))
    return {
        "cls": cls,
        "losses_bitwise": losses == want,
        "n_losses": [len(losses), len(want)],
        "state_diff": state_diff,
        "failures": report.failures,
        "restores": report.restores,
        "rescales": report.rescales,
        "lost_steps": report.lost_steps,
        "digest": digest,
    }


def check_elastic_cross_plan():
    """A checkpoint taken under pp (2 stages, 8 devices) resumes under
    ddp+zero1 on 4 devices mid-run: FTRunner hits a Table-V fatal class,
    the restore_fn reshards the plan-stamped checkpoint onto the shrunken
    mesh, and the post-restore loss trajectory tracks the unbroken pp
    run."""
    import tempfile
    from repro.data.synthetic import batch_for_model
    from repro.elastic import ElasticCheckpointer
    from repro.optim import AdamW
    from repro.parallel.plan import ParallelPlan, init_state, make_train_step
    from repro.platform.failures import FailureInjector, FailureModel
    from repro.platform.runner import FTRunner

    cfg, model, _, params = _small_dense()
    opt = AdamW(lr=1e-3, param_dtype="float32")
    mesh_pp = make_mesh((2, 2, 2), ("pipe", "pod", "data"))
    mesh_dp = make_mesh((1, 4), ("pod", "data"),
                        devices=jax.devices()[:4])
    plan_pp = ParallelPlan(mode="pp", pp_microbatches=2)
    plan_dp = ParallelPlan(mode="ddp", zero1=True, overlap=False)

    def fetch(i):
        return {k: jnp.asarray(v) for k, v in
                batch_for_model(cfg, "train", i, 16, 32).items()}

    def plan_for(world):
        return (plan_pp, mesh_pp) if world >= 2 else (plan_dp, mesh_dp)

    # unbroken pp reference trajectory
    step_pp = make_train_step(plan_pp, model, opt, mesh_pp,
                              params_template=params)
    st = init_state(plan_pp, opt, params, mesh_pp)
    ref_losses = []
    for i in range(10):
        st, mets = step_pp(st, fetch(i))
        ref_losses.append(float(mets["loss"]))

    cls = next(e.cls for e in FailureModel(seed=1).sample(1250, 48.0)
               if e.fatal)
    losses = []
    step_cache = {}

    def make_step(world):
        if world not in step_cache:
            p, m = plan_for(world)
            base = make_train_step(p, model, opt, m, params_template=params)

            def wrapped(state, batch, _base=base):
                state, mets = _base(state, batch)
                losses.append(float(mets["loss"]))
                return state, mets

            step_cache[world] = wrapped
        return step_cache[world]

    with tempfile.TemporaryDirectory() as d:
        mgr = ElasticCheckpointer(d, plan_pp, mesh_pp)

        def restore_fn(_template, new_world):
            p, m = plan_for(new_world)
            return mgr.restore_for(p, m, params)

        runner = FTRunner(make_step, fetch, mgr,
                          init_state(plan_pp, opt, params, mesh_pp),
                          world_size=2, min_world=1, ckpt_every=5,
                          injector=FailureInjector({7: cls}),
                          restore_fn=restore_fn)
        report = runner.run(10)
        digest = _event_digest(report, runner.event_log, d)

    # kill at 7 -> reshard ckpt 5 onto ddp/4dev -> 5 post-restore steps
    cont = losses[7:]
    post_err = max(abs(a - b) for a, b in zip(cont, ref_losses[5:]))
    return {
        "cls": cls,
        "post_err": post_err,
        "cont_losses": cont,
        "ref_losses": ref_losses,
        "world": runner.world,
        "failures": report.failures,
        "restores": report.restores,
        "rescales": report.rescales,
        "lost_steps": report.lost_steps,
        "digest": digest,
    }


def main():
    out = {}
    if sys.argv[1:] == ["elastic"]:
        out["elastic_same_plan"] = check_elastic_kill_resume()
        out["elastic_cross_plan"] = check_elastic_cross_plan()
        out["n_devices"] = len(jax.devices())
        print("MULTIDEV_JSON:" + json.dumps(out))
        return
    out["hfreduce_err"], out["flat_err"] = check_hfreduce()
    out["tree_err"], out["ring_err"] = check_tree_allreduce()
    out["bf16_psum_relerr"], out["int8_psum_relerr"] = check_compressed_psum()
    out["hfreduce_tree_err"] = check_hfreduce_tree_combo()
    (out["ddp_vs_ref_err"], out["ddp_loss"],
     out["ref_loss"]) = check_ddp_step()
    out["ddp_int8_losses"] = check_ddp_compressed()
    out["ddp_overlap"] = check_ddp_overlap()
    (out["zero1_err"], out["zero1_losses"],
     out["zero1_ref_losses"]) = check_ddp_zero1()
    out["fp8_fold_err"], out["fp8_after_err"] = check_fp8_prescale()
    out["pp_fwd_err"], out["pp_grad_err"] = check_pipeline()
    out["pp_train"] = check_pp_train()
    out["elastic_remesh_err"] = check_elastic_remesh()
    out["n_devices"] = len(jax.devices())
    print("MULTIDEV_JSON:" + json.dumps(out))


if __name__ == "__main__":
    main()
