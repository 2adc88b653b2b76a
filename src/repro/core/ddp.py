"""HaiScale DDP: shard_map training step with explicit HFReduce grad sync.

This is the paper-faithful runtime for models that fit per-chip (paper
§V-A): parameters replicated, batch sharded over ("pod","data"), gradients
synced by the *explicit* hierarchical schedule (core/hfreduce.py) in
reverse-layer buckets, optionally with a compressed cross-pod wire format.

The paper's central claim is *overlap*: HaiScale launches each bucket's
allreduce asynchronously as backprop produces it, hiding the weak-link
transfer behind remaining backward compute.  ``plan.overlap=True`` (the
default) reproduces that structure here: every gradient bucket gets a
custom_vjp identity hook on its parameter leaves, whose backward runs the
bucket's HFReduce the moment the bucket's cotangents are all accumulated —
*inside* the backward pass.  Each collective then depends only on its own
bucket (not on a whole-tree flatten that finalizes after the last dgrad),
so XLA's latency-hiding scheduler can run cross-pod transfers concurrently
with the remaining reverse-layer compute.  ``plan.overlap=False`` keeps the
old post-hoc whole-tree sync for parity testing; both paths use identical
bucket slices and wire dtypes, so their gradients agree bitwise for an
uncompressed wire and to quantization error otherwise (DESIGN.md §3).

``plan.zero1=True`` extends ZeRO-1 to the explicit path, mirroring the
GSPMD ``zero1_pod`` semantics: gradients are reduce-scattered (intra-pod
first, then across pods — never gathered back), each rank updates its flat
fp32 master/moment shard, and the step ends with a bf16 param all-gather
(cross-pod on the 1/strong-size shard, then intra-pod).  Cross-pod bytes
per step drop from 2·|g|/strong to (|g| + |p|)/strong.

Big models use the GSPMD path instead (parallel/ + launch/train.py); both
paths share the optimizer and are selected by ``parallel/plan.py``.
"""
from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import bucketing, compression
from repro.core.hfreduce import flat_allreduce, hfreduce
from repro.parallel.plan import ParallelPlan


def _mesh_axes(plan: ParallelPlan, mesh):
    """(axes_in_mesh, strong, weak-or-None, n_shards) for the plan's batch."""
    axes = tuple(a for a in plan.batch_axes if a in mesh.shape)
    if not axes:
        raise ValueError(f"none of batch_axes={plan.batch_axes} in mesh "
                         f"{dict(mesh.shape)}")
    weak = axes[0] if len(axes) > 1 else None
    strong = axes[-1]
    n_shards = 1
    for a in axes:
        n_shards *= mesh.shape[a]
    return axes, strong, weak, n_shards


def make_ddp_grad_sync(plan: bucketing.BucketPlan, *,
                       strong_axis="data", weak_axis="pod",
                       compress: str = "", hierarchical=True,
                       bucketed=True, n_shards=1) -> Callable:
    """Returns grads -> synced grads (mean over all data shards).

    The 1/n_shards mean is folded into the sync itself — hierarchical
    schedules pre-scale the intra-pod shard *before* the (optionally
    compressed) cross-pod phase, so narrow wire formats quantize
    mean-magnitude values instead of pod sums.  Call inside shard_map with
    both axes in scope.  ``sync.sync_one`` is the per-bucket collective
    (flat array -> flat array), shared with the overlap hooks so both
    paths are numerically identical.
    """
    if compress and not hierarchical:
        # never a silent no-op: the compressed wire format only exists on
        # the hierarchical schedule's cross-pod phase
        raise ValueError(
            f"compress={compress!r} needs the hierarchical schedule "
            "(grad_sync='hfreduce' and a weak axis in the mesh); the "
            "flat allreduce would silently ignore it")
    weak_psum = compression.make_weak_psum(compress)
    inv = 1.0 / float(n_shards)

    def sync_one(g):
        if hierarchical:
            return hfreduce(g, strong_axis=strong_axis, weak_axis=weak_axis,
                            weak_psum=weak_psum,
                            prescale=inv if n_shards > 1 else None)
        if n_shards > 1:
            g = g * jnp.asarray(inv, g.dtype)
        return flat_allreduce(g, axes=(weak_axis, strong_axis))

    def sync(grads):
        if bucketed:
            return bucketing.bucketed_apply(plan, grads, sync_one)
        return jax.tree_util.tree_map(sync_one, grads)

    sync.sync_one = sync_one
    return sync


# ---------------------------------------------------------------------------
# Overlapped backward: per-bucket custom_vjp sync hooks
# ---------------------------------------------------------------------------


def _make_bucket_hook(shapes, dtypes, sizes, wire_dtype, sync_one):
    """Identity on a bucket's param leaves whose VJP syncs their grads.

    The forward is a no-op; the backward flattens the bucket's cotangents
    to the wire dtype, runs the bucket collective, and unflattens — the
    exact math ``bucketing.bucketed_apply`` does post-hoc on the same leaf
    range, but emitted at the point in the backward where this bucket's
    cotangents finalize.
    """

    @jax.custom_vjp
    def hook(*leaves):
        return leaves

    def fwd(*leaves):
        return leaves, None

    def bwd(_, cts):
        flat = bucketing.flatten_tree(cts, wire_dtype)
        flat = sync_one(flat)
        return tuple(bucketing.unflatten_leaves(flat, shapes, dtypes,
                                                sizes))

    hook.defvjp(fwd, bwd)
    return hook


def attach_sync_hooks(params, plan: bucketing.BucketPlan, sync_one):
    """Return ``params`` with each gradient bucket routed through a
    custom_vjp hook that issues its HFReduce inside the backward."""
    leaves, treedef = jax.tree_util.tree_flatten(params)
    tagged = list(leaves)
    for i0, i1 in bucketing.bucket_leaf_ranges(plan):
        if i0 == i1:
            continue
        hook = _make_bucket_hook(plan.shapes[i0:i1], plan.dtypes[i0:i1],
                                 plan.sizes[i0:i1], plan.wire_dtype,
                                 sync_one)
        tagged[i0:i1] = list(hook(*leaves[i0:i1]))
    return jax.tree_util.tree_unflatten(treedef, tagged)


# ---------------------------------------------------------------------------
# Train-step builder
# ---------------------------------------------------------------------------


def make_ddp_train_step(loss_fn: Callable, optimizer, mesh,
                        plan: ParallelPlan, *, params_template,
                        donate=False):
    """Build a jitted explicit-DDP train step from a ``ParallelPlan``.

    ``loss_fn(params, batch) -> (loss, metrics)``; params replicated,
    batch sharded on dim 0 over ``plan.batch_axes``.
    ``optimizer``: repro.optim AdamW-like with .init/.apply (replicated;
    ``plan.zero1`` switches to the flat-sharded state from
    ``init_zero1_state``).  ``donate=True`` donates the state argument —
    essential for ZeRO-1, whose point is not double-buffering the fp32
    masters — but leaves the caller's input state unusable afterwards.
    Returns ``(step, BucketPlan)``.
    """

    donate_kw = dict(donate_argnums=(0,)) if donate else {}

    if plan.mode != "ddp":
        raise ValueError(f"plan.mode={plan.mode!r}; want 'ddp'")
    bucket_plan = bucketing.plan_buckets(
        params_template,
        plan.bucket_bytes or bucketing.DEFAULT_BUCKET_BYTES,
        wire_dtype=plan.wire_dtype)
    axes_in_mesh, strong, weak, n_shards = _mesh_axes(plan, mesh)
    hierarchical = plan.grad_sync == "hfreduce" and weak is not None

    batch_spec = P(axes_in_mesh if len(axes_in_mesh) > 1 else axes_in_mesh[0])

    if plan.zero1:
        local_step, state_spec = _make_zero1_local_step(
            loss_fn, optimizer, mesh, plan, params_template,
            axes_in_mesh, strong, weak, n_shards)
        step = jax.shard_map(local_step, mesh=mesh,
                             in_specs=(state_spec, batch_spec),
                             out_specs=(state_spec, P()),
                             check_vma=False)
        return jax.jit(step, **donate_kw), bucket_plan

    sync = make_ddp_grad_sync(
        bucket_plan, strong_axis=strong, weak_axis=weak or strong,
        compress=plan.compress, hierarchical=hierarchical,
        bucketed=plan.bucketed, n_shards=n_shards)

    def local_step(state, batch):
        params = state["params"]
        if plan.overlap:
            def hooked_loss(p, b):
                return loss_fn(attach_sync_hooks(p, bucket_plan,
                                                 sync.sync_one), b)
            (loss, metrics), grads = jax.value_and_grad(
                hooked_loss, has_aux=True)(params, batch)
            # grads already synced + meaned, bucket by bucket, inside the
            # backward — nothing left to do here.
        else:
            (loss, metrics), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params, batch)
            grads = sync(grads)
        loss = jax.lax.pmean(loss, axes_in_mesh)
        new_state = optimizer.apply(state, grads)
        return new_state, {"loss": loss, **{k: jax.lax.pmean(v, axes_in_mesh)
                                            for k, v in metrics.items()}}

    step = jax.shard_map(
        local_step, mesh=mesh,
        in_specs=(P(), batch_spec),
        out_specs=(P(), P()),
        check_vma=False)
    return jax.jit(step, **donate_kw), bucket_plan


# ---------------------------------------------------------------------------
# Explicit ZeRO-1: reduce-scattered grads, flat-sharded fp32 masters,
# param all-gather (the split optimizer step, paper §V-B3)
# ---------------------------------------------------------------------------


def _zero1_layout(params_template, mesh, axes_in_mesh):
    """(total, padded_total, shard_spec) for the flat optimizer state.

    The flat vector is chunked strong-major: reduce-scatter over the
    strong axis first (chunk j), then over the weak axis (sub-chunk i), so
    rank (i, j) holds flat[j*S/strong + i*S/(strong*weak) : ...].  That is
    exactly ``PartitionSpec((strong, weak))`` on dim 0, which lets the
    state live as one global sharded array outside shard_map.
    """
    sizes = [int(functools.reduce(lambda a, b: a * b, l.shape, 1))
             for l in jax.tree_util.tree_leaves(params_template)]
    total = sum(sizes)
    n_parts = 1
    for a in axes_in_mesh:
        n_parts *= mesh.shape[a]
    padded = total + ((-total) % n_parts)
    strong = axes_in_mesh[-1]
    weak = axes_in_mesh[0] if len(axes_in_mesh) > 1 else None
    spec = P((strong, weak)) if weak is not None else P(strong)
    return total, padded, spec


def init_zero1_state(params, optimizer, mesh, plan: ParallelPlan):
    """Flat-sharded ZeRO-1 state for the explicit path.

    ``params`` stays a replicated working-copy tree in the optimizer's
    param dtype; ``master``/``m``/``v`` are flat fp32/moment vectors
    sharded over the plan's batch axes.
    """
    axes_in_mesh, _, _, _ = _mesh_axes(plan, mesh)
    total, padded, spec = _zero1_layout(params, mesh, axes_in_mesh)
    flat = jnp.concatenate(
        [l.reshape(-1).astype(jnp.float32)
         for l in jax.tree_util.tree_leaves(params)])
    if padded > total:
        flat = jnp.concatenate([flat, jnp.zeros((padded - total,),
                                                jnp.float32)])
    shard = NamedSharding(mesh, spec)
    rep = NamedSharding(mesh, P())
    mdt = jnp.dtype(optimizer.moments_dtype)
    return {
        "params": jax.device_put(
            jax.tree_util.tree_map(
                lambda x: x.astype(optimizer.param_dtype), params), rep),
        "master": jax.device_put(flat, shard),
        "m": jax.device_put(jnp.zeros((padded,), mdt), shard),
        "v": jax.device_put(jnp.zeros((padded,), mdt), shard),
        "step": jnp.zeros((), jnp.int32),
    }


def _make_zero1_local_step(loss_fn, optimizer, mesh, plan, params_template,
                           axes_in_mesh, strong, weak, n_shards):
    total, padded, spec = _zero1_layout(params_template, mesh, axes_in_mesh)
    # unflatten target: the *working copy* (param dtype), not the template
    param_plan = bucketing.plan_buckets(jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape,
                                       jnp.dtype(optimizer.param_dtype)),
        params_template))
    inv = 1.0 / float(n_shards)

    state_spec = {"params": P(), "master": spec, "m": spec, "v": spec,
                  "step": P()}

    def local_step(state, batch):
        params = state["params"]
        (loss, metrics), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params, batch)

        # --- reduce-scatter (never gather grads back) ---
        flat = jnp.concatenate(
            [g.reshape(-1).astype(jnp.float32)
             for g in jax.tree_util.tree_leaves(grads)])
        if padded > total:
            flat = jnp.concatenate([flat, jnp.zeros((padded - total,),
                                                    jnp.float32)])
        g = lax.psum_scatter(flat, strong, scatter_dimension=0, tiled=True)
        g = g * inv                     # mean fold, before the weak link
        if weak is not None:
            g = lax.psum_scatter(g, weak, scatter_dimension=0, tiled=True)

        # --- AdamW on the local flat shard: the clip norm needs a psum
        # over the sharded axes; the update itself is the optimizer's own
        # per-leaf rule, so the two paths cannot drift ---
        step_no = state["step"] + 1
        gnorm = jnp.sqrt(lax.psum(jnp.sum(g * g), axes_in_mesh))
        g = g * jnp.minimum(1.0, optimizer.clip_norm /
                            jnp.maximum(gnorm, 1e-12))
        m, v, master = optimizer.update_fn(step_no)(
            g, state["m"], state["v"], state["master"])

        # --- all-gather the updated params in the working dtype ---
        pshard = master.astype(jnp.dtype(optimizer.param_dtype))
        if weak is not None:
            pshard = lax.all_gather(pshard, weak, axis=0, tiled=True)
        pflat = lax.all_gather(pshard, strong, axis=0, tiled=True)
        if padded > total:
            pflat = pflat[:total]
        new_params = bucketing.unflatten_tree(param_plan, pflat)

        loss = lax.pmean(loss, axes_in_mesh)
        new_state = {"params": new_params, "master": master,
                     "m": m, "v": v, "step": step_no}
        return new_state, {"loss": loss,
                           **{k: lax.pmean(v_, axes_in_mesh)
                              for k, v_ in metrics.items()}}

    return local_step, state_spec
