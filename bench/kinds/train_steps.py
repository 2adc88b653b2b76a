"""Training steps through ``repro.parallel.plan.make_train_step``, the
entry ``repro.launch.train`` uses, under the traffic file's plan and mesh.

Set-up makes float32 weights from the seed on the device, builds the
optimizer state and the jitted step (state donated, as the launcher
runs it), places ``feed_batches`` batches of fresh rows on the devices
and drives the step through its first ``check["steps"]`` steps.  Those
steps compile it and give the readings the reference is compared with:
each step's loss, the per-leaf norms of the first gradient as the
optimizer got it (from its first moment after one step) and the
per-leaf norms of the change of the float32 masters after the last
check step.  The same state then goes on into the window.

The window calls the step back to back over the fed batches, with at
most two steps in flight, until ``--seconds`` have passed, then waits
for the last one: ``train_tok_s`` is the tokens of every step it ran
over that whole time.
"""
from __future__ import annotations

import math

import numpy as np

from bench import traffic_gen
from bench.harness import decoder_spec, model_config
from bench.reference import weights


def leaf_norms(tree) -> dict:
    import jax
    import jax.numpy as jnp
    return {jax.tree_util.keystr(k): jnp.sqrt(jnp.sum(jnp.square(
        v.astype(jnp.float32)))) for k, v in
        jax.tree_util.tree_leaves_with_path(tree)}


class Runner:
    def __init__(self, ctx):
        self.ctx = ctx
        self.config = ctx.cell.config
        self.traffic = ctx.cell.traffic
        self.spec = decoder_spec(self.config)
        self.rows = self.traffic["batch_per_chip"] * ctx.cell.chips

    def build_step(self):
        """The plan's jitted train step, as ``launch/train.py`` builds it
        (the fault tests wrap this method)."""
        from repro.parallel.plan import make_train_step
        return make_train_step(self.plan, self.model, self.opt, self.mesh,
                               params_template=self.template, donate=True)

    def setup(self) -> None:
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P

        from repro.launch.mesh import make_mesh
        from repro.models import build_model
        from repro.optim import AdamW
        from repro.parallel.plan import ParallelPlan, init_state
        from repro.telemetry import now

        t0 = now()
        cfg = model_config(self.config)
        self.model = build_model(cfg)
        weights.check_layout(self.spec, self.model, "float32")
        opt = self.config["optimizer"]
        self.opt = AdamW(lr=opt["lr"], b1=opt["b1"], b2=opt["b2"],
                         eps=opt["eps"], weight_decay=opt["weight_decay"],
                         clip_norm=opt["clip_norm"],
                         param_dtype=cfg.compute_dtype)
        plan = dict(self.traffic["plan"])
        plan["batch_axes"] = tuple(plan["batch_axes"])
        self.plan = ParallelPlan(**plan)
        mesh = self.traffic["mesh"]
        self.mesh = make_mesh(tuple(mesh["shape"]), tuple(mesh["axes"]),
                              devices=self.ctx.devices)
        params = weights.make_params(self.spec, self.ctx.seed, "float32")
        self.template = jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), params)
        state = init_state(self.plan, self.opt, params, self.mesh)
        del params
        self.step = self.build_step()
        axes = tuple(a for a in self.plan.batch_axes if a in self.mesh.shape)
        put = NamedSharding(self.mesh, P(axes))
        host = traffic_gen.train_batches(
            self.traffic, self.ctx.seed, self.traffic["feed_batches"],
            self.rows, self.spec.vocab)
        self.batches = [{k: jax.device_put(v, put) for k, v in b.items()}
                        for b in host]
        self.check_batches = host[:self.traffic["check"]["steps"]]
        t1 = now()
        c0 = len(self.ctx.compiles.events)

        norms = jax.jit(leaf_norms)
        key = weights.seed_key(self.ctx.seed, stream=1)
        spec = self.spec
        change = jax.jit(lambda master, key: leaf_norms(jax.tree_util.tree_map(
            jnp.subtract, master, weights.draw(spec, key, jnp.float32))))
        losses, g1 = [], None
        for i in range(self.traffic["check"]["steps"]):
            state, met = self.step(state, self.batches[i])
            losses.append(met["loss"])
            if i == 0:
                g1 = norms(state["m"])
        d3 = change(state["master"], key)
        self.readings = {
            "loss": [float(x) for x in losses],
            "grad_norm": {k: float(v) / (1.0 - opt["b1"])
                          for k, v in g1.items()},
            "update_norm": {k: float(v) for k, v in d3.items()},
        }
        self.state = state
        t2 = now()
        self.setup_record = {
            "build_s": t1 - t0, "check_steps_s": t2 - t1,
            "compiles": len(self.ctx.compiles.events) - c0,
            "compile_s": sum(d for _, d in self.ctx.compiles.events[c0:])}

    def window(self, seconds: float) -> dict:
        import jax
        from repro.telemetry import now
        ctx, step, batches = self.ctx, self.step, self.batches
        state, prev, n, ends = self.state, None, 0, []
        t0 = now()
        while True:
            ctx.poll()
            with ctx.span("train_step"):
                state, met = step(state, batches[n % len(batches)])
            n += 1
            if prev is not None:
                with ctx.span("wait"):
                    jax.block_until_ready(prev)
                ends.append(now())
            prev = met["loss"]
            if now() - t0 >= seconds:
                break
        with ctx.span("wait"):
            last = float(jax.block_until_ready(prev))
        t1 = now()
        ends.append(t1)
        self.state = state
        tokens = n * self.rows * self.traffic["seq"]
        return {"t0": t0, "t1": t1, "steps": n, "tokens": tokens,
                "step_ends": ends, "last_loss": last,
                "attempted": n, "failed": 0 if math.isfinite(last) else n,
                "setup": self.setup_record}

    def end_to_end(self, rec: dict) -> dict:
        return {"train_tok_s": rec["tokens"] / (rec["t1"] - rec["t0"])}

    def release(self) -> None:
        del self.state, self.batches, self.step

    def check(self, rec: dict) -> dict:
        ref = reference_readings(self.spec, self.config["optimizer"],
                                 self.ctx.seed, self.check_batches,
                                 self.traffic["check"]["rows_per_block"])
        return compare(self.readings, ref, self.limits())

    def limits(self) -> dict:
        return self.traffic["limits"][self.config["name"]]


def reference_readings(spec, opt: dict, seed: int, batches: list,
                       rows_per_block: int, precision="f32") -> dict:
    """The reference's readings over the same batches: losses, first
    (clipped) gradient and master change per leaf, in float32."""
    import jax
    import jax.numpy as jnp

    from bench.reference import decoder

    @jax.jit
    def one(params, m, v, step, tokens, labels):
        lv, g = decoder.grads(spec, params, tokens, labels, rows_per_block,
                              precision)
        p2, m2, v2, gc = decoder.adamw(opt, params, m, v, g, step)
        return lv, p2, m2, v2, leaf_norms(gc)

    p0 = weights.make_params(spec, seed, "float32")
    params = p0
    m = jax.tree_util.tree_map(jnp.zeros_like, p0)
    v = jax.tree_util.tree_map(jnp.zeros_like, p0)
    losses, g1 = [], None
    for i, b in enumerate(batches):
        lv, params, m, v, gn = one(params, m, v, jnp.int32(i + 1),
                                   jnp.asarray(b["tokens"]),
                                   jnp.asarray(b["labels"]))
        losses.append(float(lv))
        if i == 0:
            g1 = {k: float(x) for k, x in gn.items()}
    d = jax.jit(lambda a, b: leaf_norms(jax.tree_util.tree_map(
        jnp.subtract, a, b)))(params, p0)
    return {"loss": losses, "grad_norm": g1,
            "update_norm": {k: float(x) for k, x in d.items()}}


def leaf_gap(got: dict, want: dict, leaves=None) -> float:
    """Worst leaf's |‖got‖ - ‖want‖|, over the larger of that leaf's
    reference norm and the median leaf's."""
    keys = sorted(want) if leaves is None else leaves
    med = float(np.median([want[k] for k in keys]))
    return max(abs(got[k] - want[k]) / max(want[k], med) for k in keys)


def compare(prog: dict, ref: dict, limits: dict) -> dict:
    """The numbers that decide ``correct``.  Leaves whose reference
    gradient is under a thousandth of the median leaf's move by rounding
    alone under Adam and are left out of the change."""
    g = ref["grad_norm"]
    med = float(np.median(list(g.values())))
    moving = sorted(k for k in g if g[k] >= 1e-3 * med)
    loss_gap = max(abs(a - b) / abs(b) for a, b in zip(prog["loss"],
                                                      ref["loss"]))
    return {
        "loss_gap": {"value": loss_gap, "limit": limits["loss_gap"]},
        "grad_norm_gap": {"value": leaf_gap(prog["grad_norm"], g),
                          "limit": limits["grad_norm_gap"]},
        "update_norm_gap": {"value": leaf_gap(prog["update_norm"],
                                              ref["update_norm"], moving),
                            "limit": limits["update_norm_gap"]},
    }
