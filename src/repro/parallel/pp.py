"""Pipeline parallelism (HaiScale PP, paper §V-B2) as a shard_map schedule.

Two layers of machinery live here:

* ``pipeline_apply``/``make_pipelined_forward`` — the differentiable GPipe
  forward (microbatches flow stage-to-stage via ``collective_permute``,
  ``jax.grad`` transposes the ppermutes into the reverse pipeline).  Used
  by the numerics checks.
* ``make_pp_train_step`` — the first-class training path selected by
  ``ParallelPlan(mode="pp")``: a manual forward/backward schedule (GPipe
  or 1F1B) over a "pipe" mesh axis, composed with HFReduce gradient sync
  of the stage grads over ("pod","data") and microbatch accumulation, and
  sharing the replicated-optimizer state layout with the single-stage
  step (DESIGN.md §7).  The 1F1B schedule interleaves one microbatch
  forward and one backward per tick after a (P-1)-tick warmup, so each
  stage keeps at most ``2P-1`` activations live instead of GPipe's ``m``
  (``peak_live_activations``); the total tick count drops from
  ``2(m+P-1)`` to ``m+2P-1``.

The paper's PCIe-specific trick — staggering the PP ranks of the 8 GPUs on
a node across different DP ranks so they don't fight for the single NIC —
maps onto TPU as *placing the pipe axis on the intra-pod fabric and the DP
axis across pods*, which the mesh layout rules already enforce; the
explicit time-staggering knob has no analogue when every chip has its own
ICI links (documented in DESIGN.md §7).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax


def split_stages(stacked_params, n_stages: int):
    """(L, ...) stacked layer params -> (P, L/P, ...) for P("pipe") sharding."""
    def re(x):
        L = x.shape[0]
        assert L % n_stages == 0, (L, n_stages)
        return x.reshape(n_stages, L // n_stages, *x.shape[1:])
    return jax.tree_util.tree_map(re, stacked_params)


def pipeline_apply(stage_fn, stage_params, x_micro, *, axis: str = "pipe"):
    """Run the GPipe schedule.  Call INSIDE shard_map.

    stage_fn(stage_params, x) -> x      (applies this stage's layers)
    stage_params: this rank's (1, L/P, ...) slice (leading dim squeezed here)
    x_micro: (n_micro, mb, ...) microbatched input (stage 0 consumes it)

    Returns (n_micro, mb, ...) outputs, valid on every rank (psum-broadcast
    from the last stage).
    """
    P = lax.axis_size(axis)
    rank = lax.axis_index(axis)
    n_micro = x_micro.shape[0]
    sp = jax.tree_util.tree_map(lambda a: a[0], stage_params)
    perm = [(i, i + 1) for i in range(P - 1)]

    recv = jnp.zeros(x_micro.shape[1:], x_micro.dtype)
    outputs = jnp.zeros_like(x_micro)
    for t in range(n_micro + P - 1):
        mb_idx = t - rank
        mb_c = jnp.clip(mb_idx, 0, n_micro - 1)
        first_in = x_micro[mb_c]
        inp = jnp.where(rank == 0, first_in, recv)
        out = stage_fn(sp, inp)
        active = jnp.logical_and(mb_idx >= 0, mb_idx < n_micro)
        out = jnp.where(active, out, jnp.zeros_like(out))
        collect = jnp.logical_and(rank == P - 1, active)
        outputs = jnp.where(collect, outputs.at[mb_c].set(out), outputs)
        if perm:
            recv = lax.ppermute(out, axis, perm)
    # only the last stage holds real outputs -> broadcast to all ranks
    outputs = jnp.where(rank == P - 1, outputs, jnp.zeros_like(outputs))
    return lax.psum(outputs, axis)


def make_pipelined_forward(layer_fn, n_stages: int, n_micro: int, mesh,
                           *, axis="pipe"):
    """Build f(stacked_params, x) -> y running layers as a P-stage pipeline.

    layer_fn(layer_params, x) -> x;  stacked_params: (L, ...) trees;
    x: (batch, ...) with batch % n_micro == 0.
    """
    from jax.sharding import PartitionSpec as Pspec

    def stage_fn(sp, x):
        def body(carry, lp):
            return layer_fn(lp, carry), None
        x, _ = lax.scan(body, x, sp)
        return x

    def inner(staged_params, x_micro):
        return pipeline_apply(stage_fn, staged_params, x_micro, axis=axis)

    sharded = jax.shard_map(
        inner, mesh=mesh,
        in_specs=(Pspec(axis), Pspec()),
        out_specs=Pspec(),
        check_vma=False)

    def f(stacked_params, x):
        b = x.shape[0]
        assert b % n_micro == 0
        xm = x.reshape(n_micro, b // n_micro, *x.shape[1:])
        staged = split_stages(stacked_params, n_stages)
        ym = sharded(staged, xm)
        return ym.reshape(b, *x.shape[1:])

    return f


def bubble_fraction(n_stages: int, n_micro: int,
                    schedule: str = "gpipe") -> float:
    """Pipeline bubble: (P-1)/(m+P-1) — the Fig. 9 scaling term.

    GPipe and 1F1B share the same bubble fraction; 1F1B's win is
    activation memory (``peak_live_activations``), not bubble.
    """
    if schedule not in ("gpipe", "1f1b"):
        raise ValueError(schedule)
    return (n_stages - 1) / (n_micro + n_stages - 1)


def peak_live_activations(n_stages: int, n_micro: int,
                          schedule: str = "gpipe") -> int:
    """Max stage inputs held for the backward, per stage.

    GPipe holds every microbatch until the forward drains (m); the 1F1B
    interleave retires microbatch i's activation before microbatch
    i + 2P - 1 is stored, bounding liveness by the stage count alone.
    """
    if schedule == "gpipe":
        return n_micro
    if schedule == "1f1b":
        return min(n_micro, 2 * n_stages - 1)
    raise ValueError(schedule)


# ---------------------------------------------------------------------------
# First-class pipelined training (ParallelPlan mode="pp")
# ---------------------------------------------------------------------------


def _check_pp_model(model):
    from repro.models.model_api import DecoderLM
    if not isinstance(model, DecoderLM) or model.is_moe or model.is_vlm:
        raise ValueError(
            "mode='pp' currently pipelines dense decoder-only LMs "
            "(params['layers'] stacked, embed/head on the edge stages); "
            f"got {type(model).__name__}")


def make_pp_train_step(model, optimizer, mesh, plan, *,
                       params_template=None, donate=False):
    """Build the jitted pipelined train step ``step(state, batch)``.

    Layers are split into P contiguous stages over ``plan.pp_axis``; the
    embedding runs on stage 0 and the head (final norm + logits + CE) on
    stage P-1.  Each tick runs at most one microbatch forward and one
    backward per stage, exchanging activations/cotangents with one
    ppermute pair; ``plan.pp_schedule`` picks when backwards start
    ("gpipe": after the forward drains; "1f1b": as soon as the last stage
    finishes a microbatch).  Stage gradients are psum'd over the pipe
    axis into the replicated tree layout, synced with HFReduce over the
    plan's batch axes, and fed to the replicated optimizer — so ``state``
    is exactly ``optimizer.init(params)`` and the loss trajectory matches
    the single-stage step up to float reassociation.
    """
    from jax.sharding import PartitionSpec as Pspec
    from repro.core import bucketing
    from repro.core.ddp import make_ddp_grad_sync

    if plan.mode != "pp":
        raise ValueError(f"plan.mode={plan.mode!r}; want 'pp'")
    _check_pp_model(model)
    cfg = model.cfg
    pipe_axis = plan.pp_axis
    if pipe_axis not in mesh.shape:
        raise ValueError(f"mesh has no {pipe_axis!r} axis: "
                         f"{dict(mesh.shape)}")
    n_stages = mesh.shape[pipe_axis]
    if cfg.n_layers % n_stages == 0:
        layers_per_stage = cfg.n_layers // n_stages
    else:
        raise ValueError(f"n_layers={cfg.n_layers} not divisible by "
                         f"{n_stages} pipeline stages")
    m = plan.pp_microbatches
    schedule = plan.pp_schedule

    batch_axes = tuple(a for a in plan.batch_axes if a in mesh.shape)
    n_shards = 1
    for a in batch_axes:
        n_shards *= mesh.shape[a]
    weak = batch_axes[0] if len(batch_axes) > 1 else None
    strong = batch_axes[-1] if batch_axes else None

    if params_template is None:
        params_template = model.param_shapes(optimizer.param_dtype)
    bucket_plan = bucketing.plan_buckets(
        params_template,
        plan.bucket_bytes or bucketing.DEFAULT_BUCKET_BYTES,
        wire_dtype=plan.wire_dtype)
    sync = None
    if strong is not None:
        sync = make_ddp_grad_sync(
            bucket_plan, strong_axis=strong, weak_axis=weak or strong,
            compress=plan.compress,
            hierarchical=plan.grad_sync == "hfreduce" and weak is not None,
            bucketed=plan.bucketed, n_shards=n_shards)

    # schedule timing: forward for microbatch f at stage r fires at tick
    # f + r; backward for microbatch b at stage r fires at tick
    # b + off - r, with off chosen so the last stage's backward trails its
    # own forward by one tick (1f1b) or the whole forward phase (gpipe).
    off = 2 * n_stages - 1 if schedule == "1f1b" else m + 2 * n_stages - 2
    n_ticks = m + off
    n_slots = peak_live_activations(n_stages, m, schedule)

    # lazy: models.transformer imports parallel.axes — keep the package
    # import graph acyclic by resolving the layer fn at build time only
    from repro.models.transformer import dense_layer

    def emb_fn(nonlayer, tokens):
        return model._embed(nonlayer, tokens)

    def head_fn(nonlayer, y, labels):
        return model._ce(nonlayer, y, labels)

    def stage_fwd(sp, x):
        def body(h, lp):
            return dense_layer(cfg, lp, h, causal=True), None
        x, _ = lax.scan(body, x, sp)
        return x

    def local_step(state, batch):
        params = state["params"]
        tokens, labels = batch["tokens"], batch["labels"]
        b = tokens.shape[0]
        if b % m:
            raise ValueError(f"local batch {b} not divisible by "
                             f"pp_microbatches={m}")
        tok_m = tokens.reshape(m, b // m, *tokens.shape[1:])
        lab_m = labels.reshape(m, b // m, *labels.shape[1:])

        rank = lax.axis_index(pipe_axis)
        is_first = rank == 0
        is_last = rank == n_stages - 1
        nonlayer = {k: v for k, v in params.items() if k != "layers"}
        sp = jax.tree_util.tree_map(
            lambda a: lax.dynamic_slice_in_dim(
                a, rank * layers_per_stage, layers_per_stage, 0),
            params["layers"])

        x_shape = (b // m, tokens.shape[1], cfg.d_model)
        cdt = jnp.dtype(cfg.compute_dtype)
        acts = jnp.zeros((n_slots,) + x_shape, cdt)
        recv_f = jnp.zeros(x_shape, cdt)
        recv_b = jnp.zeros(x_shape, cdt)
        dsp = jax.tree_util.tree_map(jnp.zeros_like, sp)
        dnl = jax.tree_util.tree_map(jnp.zeros_like, nonlayer)
        loss_sum = jnp.zeros((), jnp.float32)

        def masked_add(acc, delta, gate):
            return jax.tree_util.tree_map(
                lambda a, d: a + jnp.where(gate, d, jnp.zeros_like(d))
                .astype(a.dtype), acc, delta)

        perm_down = [(i, i + 1) for i in range(n_stages - 1)]
        perm_up = [(i + 1, i) for i in range(n_stages - 1)]

        def tick(t, carry):
            acts, recv_f, recv_b, dsp, dnl, loss_sum = carry
            # ---- backward reads its saved activation BEFORE the forward
            # stores into the (possibly same) slot: at the liveness bound
            # the retiring microbatch and the arriving one share a tick.
            bmb = t + rank - off
            b_act = jnp.logical_and(bmb >= 0, bmb < m)
            bmb_c = jnp.clip(bmb, 0, m - 1)
            x_saved = acts[jnp.mod(bmb_c, n_slots)]

            # ---- forward op ----
            fmb = t - rank
            f_act = jnp.logical_and(fmb >= 0, fmb < m)
            fmb_c = jnp.clip(fmb, 0, m - 1)
            x_in = lax.cond(is_first,
                            lambda _: emb_fn(nonlayer, tok_m[fmb_c]),
                            lambda _: recv_f, None)
            y_out = stage_fwd(sp, x_in)
            acts = jnp.where(f_act, acts.at[jnp.mod(fmb_c, n_slots)]
                             .set(x_in), acts)
            send_f = jnp.where(f_act, y_out, jnp.zeros_like(y_out))

            # ---- backward op (forward recomputed from the saved input,
            # the remat the single-stage scan does too).  The head
            # (vocab-size logits + CE + grad) and the embedding vjp are
            # gated behind lax.cond so only the stage that owns them pays
            # for them — both are collective-free, so per-device branching
            # inside shard_map is safe.
            y2, stage_vjp = jax.vjp(stage_fwd, sp, x_saved)

            def run_head(args):
                y, labels = args
                return jax.value_and_grad(head_fn, argnums=(0, 1))(
                    nonlayer, y, labels)

            def skip_head(args):
                y, _ = args
                return (jnp.zeros((), jnp.float32),
                        (jax.tree_util.tree_map(jnp.zeros_like, nonlayer),
                         jnp.zeros_like(y)))

            loss_mb, (dnl_head, dy_head) = lax.cond(
                jnp.logical_and(b_act, is_last), run_head, skip_head,
                (y2, lab_m[bmb_c]))
            dy = jnp.where(is_last, dy_head, recv_b)
            dsp_mb, dx = stage_vjp(dy)

            def run_emb(args):
                dxi, tokens = args
                _, emb_vjp = jax.vjp(emb_fn, nonlayer, tokens)
                return emb_vjp(dxi)[0]

            def skip_emb(args):
                return jax.tree_util.tree_map(jnp.zeros_like, nonlayer)

            dnl_emb = lax.cond(jnp.logical_and(b_act, is_first),
                               run_emb, skip_emb, (dx, tok_m[bmb_c]))

            dsp = masked_add(dsp, dsp_mb, b_act)
            dnl = masked_add(dnl, dnl_head,
                             jnp.logical_and(b_act, is_last))
            dnl = masked_add(dnl, dnl_emb,
                             jnp.logical_and(b_act, is_first))
            loss_sum = loss_sum + jnp.where(
                jnp.logical_and(b_act, is_last), loss_mb, 0.0)
            send_b = jnp.where(b_act, dx, jnp.zeros_like(dx))

            if perm_down:
                recv_f = lax.ppermute(send_f, pipe_axis, perm_down)
                recv_b = lax.ppermute(send_b, pipe_axis, perm_up)
            return acts, recv_f, recv_b, dsp, dnl, loss_sum

        # one traced tick body, n_ticks iterations: program size stays
        # constant as pp_microbatches grows (the tick index math is all
        # traced-value arithmetic, so nothing needs unrolling)
        (acts, recv_f, recv_b, dsp, dnl, loss_sum) = lax.fori_loop(
            0, n_ticks, tick,
            (acts, recv_f, recv_b, dsp, dnl, loss_sum))

        # ---- assemble the replicated grad tree ----
        dlayers = jax.tree_util.tree_map(
            lambda full, g: lax.dynamic_update_slice_in_dim(
                jnp.zeros_like(full), g.astype(full.dtype),
                rank * layers_per_stage, 0),
            params["layers"], dsp)
        grads = {**dnl, "layers": dlayers}
        grads = jax.tree_util.tree_map(
            lambda g: lax.psum(g, pipe_axis) / m, grads)
        loss = lax.psum(loss_sum, pipe_axis) / m

        if sync is not None:
            grads = sync(grads)
            loss = lax.pmean(loss, batch_axes)
        new_state = optimizer.apply(state, grads)
        return new_state, {"loss": loss}

    batch_spec = Pspec(batch_axes if len(batch_axes) > 1 else
                       (batch_axes[0] if batch_axes else None))
    step = jax.shard_map(
        local_step, mesh=mesh,
        in_specs=(Pspec(), batch_spec),
        out_specs=(Pspec(), Pspec()),
        check_vma=False)
    return jax.jit(step, **(dict(donate_argnums=(0,)) if donate else {}))
