"""ParallelPlan: one description of how a training run maps onto the mesh.

Before this module, the three training paths each threaded their own ad-hoc
kwargs: ``core/ddp.py`` took (batch_axes, compress, hierarchical,
bucket_bytes, wire_dtype), ``train_lib.py`` took a ``ParallelConfig``, and
``parallel/pp.py`` was reachable only from ``testing/multidev.py``.  A
``ParallelPlan`` is the single source of truth (DESIGN.md §3):

  * ``mode`` picks the executor — ``"gspmd"`` (sharding-rule path,
    ``train_lib.make_train_step``), ``"ddp"`` (explicit shard_map HFReduce
    path, ``core/ddp.py``), or ``"pp"`` (pipelined path,
    ``parallel/pp.py``).
  * grad-sync strategy (``grad_sync``/``compress``/``bucket_bytes``/
    ``overlap``) describes *when and how* gradients cross the weak link:
    ``overlap=True`` issues each bucket's HFReduce inside the backward via
    a custom_vjp hook as the bucket closes; ``overlap=False`` keeps the
    post-hoc whole-tree sync for parity testing.
  * ``zero1`` shards fp32 masters/moments over the mesh (GSPMD:
    ``zero1_pod``; explicit: flat reduce-scatter + param all-gather).
  * pipeline knobs (``pp_schedule``/``pp_microbatches``) select GPipe or
    1F1B and the microbatch count.

``make_train_step(plan, model, optimizer, mesh)`` is the single entry point
used by ``launch/train.py`` and the examples; ``init_state`` builds the
matching optimizer state (ZeRO-1 needs flat sharded masters).
"""
from __future__ import annotations

import dataclasses
from typing import Optional

MODES = ("gspmd", "ddp", "pp")
GRAD_SYNCS = ("hfreduce", "flat")
COMPRESSIONS = ("", "bf16", "fp8", "int8")
PP_SCHEDULES = ("gpipe", "1f1b")


@dataclasses.dataclass(frozen=True)
class ParallelPlan:
    """How a training step is parallelized, across all three executors."""

    mode: str = "gspmd"                # gspmd | ddp | pp
    batch_axes: tuple = ("pod", "data")  # mesh axes carrying the batch dim
    # --- gradient sync (ddp + pp modes) ---
    grad_sync: str = "hfreduce"        # hfreduce | flat
    compress: str = ""                 # "" | bf16 | fp8 | int8 (weak axis)
    bucket_bytes: Optional[int] = None  # None -> bucketing.DEFAULT_BUCKET_BYTES
    bucketed: bool = True              # False -> one collective per leaf
    overlap: bool = True               # sync inside the backward per bucket
    wire_dtype: Optional[str] = None   # grad wire dtype (None: promoted leaf)
    zero1: bool = False                # shard fp32 masters/moments
    microbatch: int = 1                # grad accumulation (gspmd mode)
    # --- pipeline (pp mode) ---
    pp_axis: str = "pipe"
    pp_schedule: str = "1f1b"          # gpipe | 1f1b
    pp_microbatches: int = 4
    # --- gspmd passthrough (parallel/axes.py rules) ---
    tp: int = 1
    fsdp: bool = True
    opt_shard_model: bool = False
    seq_shard: bool = False
    remat: str = "full"
    ep: int = 1

    def __post_init__(self):
        if self.mode not in MODES:
            raise ValueError(f"mode={self.mode!r}; want one of {MODES}")
        if self.grad_sync not in GRAD_SYNCS:
            raise ValueError(
                f"grad_sync={self.grad_sync!r}; want one of {GRAD_SYNCS}")
        if self.compress not in COMPRESSIONS:
            raise ValueError(
                f"compress={self.compress!r}; want one of {COMPRESSIONS}")
        if self.pp_schedule not in PP_SCHEDULES:
            raise ValueError(
                f"pp_schedule={self.pp_schedule!r}; want one of "
                f"{PP_SCHEDULES}")
        if self.mode == "ddp" and self.zero1 and self.compress:
            raise ValueError(
                "explicit ZeRO-1 reduce-scatters grads (no allreduce to "
                "compress); use compress with zero1=False")
        if self.compress and self.grad_sync == "flat" and \
                self.mode in ("ddp", "pp"):
            raise ValueError(
                "compress is the wire format of the *hierarchical* "
                "cross-pod phase; grad_sync='flat' has no weak phase to "
                "compress")
        if self.mode == "ddp" and self.zero1 and self.overlap:
            raise ValueError(
                "explicit ZeRO-1 already splits the sync around the "
                "optimizer (scatter before, gather after); overlap hooks "
                "apply to the replicated-optimizer path — set overlap=False")
        if self.mode == "ddp" and self.overlap and not self.bucketed:
            raise ValueError(
                "overlap hooks are per-bucket by construction; the "
                "monolithic per-leaf sync (bucketed=False) is a post-hoc "
                "baseline — set overlap=False")
        if self.mode == "ddp" and self.microbatch != 1:
            raise ValueError(
                "the explicit DDP path does not accumulate microbatches "
                "(each accumulation step would re-sync every bucket); use "
                "mode='gspmd' or mode='pp' for microbatching")
        if self.pp_microbatches < 1:
            raise ValueError("pp_microbatches must be >= 1")

    # ------------------------------------------------------------------
    # derived views
    # ------------------------------------------------------------------

    def mesh_batch_axes(self, mesh) -> tuple:
        """The plan's batch axes that actually exist in ``mesh``."""
        return tuple(a for a in self.batch_axes if a in mesh.shape)

    def gspmd_config(self):
        """Lower to the ``ParallelConfig`` the GSPMD sharding rules read."""
        from repro.configs.base import ParallelConfig
        return ParallelConfig(
            tp=self.tp, fsdp=self.fsdp, zero1_pod=self.zero1,
            opt_shard_model=self.opt_shard_model,
            batch_axes=self.batch_axes, seq_shard=self.seq_shard,
            microbatch=self.microbatch, remat=self.remat, ep=self.ep,
            grad_compression=self.compress,
            hier_allreduce=self.grad_sync == "hfreduce")


# ----------------------------------------------------------------------
# single entry point
# ----------------------------------------------------------------------


def make_train_step(plan: ParallelPlan, model, optimizer, mesh, *,
                    loss_fn=None, params_template=None, donate=False):
    """Build the jitted train step ``step(state, batch)`` for ``plan``.

    ``loss_fn(params, batch) -> (loss, metrics)`` defaults to
    ``model.loss``.  ``params_template`` (a params pytree or matching
    ShapeDtypeStructs) is required for the explicit paths, which plan
    gradient buckets from it.  ``donate=True`` donates the state argument
    on every executor (drivers should pass it; test harnesses that reuse
    a state across steps must not).

    The returned callable is wrapped in a host-side ``train.step``
    telemetry span *outside* the jit boundary: a profiler annotation
    (``mode`` attr) around the step's dispatch, on the same clock as the
    device ops it launches — every executor gets the same trace shape
    for free.  The
    jitted step itself is its ``jitted`` attribute (AOT ``lower`` /
    ``compile``, e.g. to inspect the compiled program).
    """
    import jax

    from repro.telemetry import span

    if plan.mode == "gspmd":
        from repro import train_lib
        step = train_lib.make_train_step(model, optimizer,
                                         plan.gspmd_config(), mesh)
        step = jax.jit(step, donate_argnums=(0,) if donate else ())
    elif plan.mode == "ddp":
        from repro.core import ddp
        if loss_fn is None:
            loss_fn = lambda p, b: model.loss(p, b)  # noqa: E731
        if params_template is None:
            raise ValueError("mode='ddp' needs params_template to plan "
                             "gradient buckets")
        step, _ = ddp.make_ddp_train_step(loss_fn, optimizer, mesh, plan,
                                          params_template=params_template,
                                          donate=donate)
    else:
        from repro.parallel import pp
        step = pp.make_pp_train_step(model, optimizer, mesh, plan,
                                     params_template=params_template,
                                     donate=donate)

    mode = plan.mode

    def traced_step(state, batch):
        with span("train.step", mode=mode):
            return step(state, batch)

    traced_step.jitted = step
    return traced_step


def init_state(plan: ParallelPlan, optimizer, params, mesh):
    """Optimizer state matching the plan's executor.

    Replicated-optimizer paths use ``optimizer.init``; explicit ZeRO-1
    needs flat masters/moments sharded over the mesh instead.
    """
    if plan.mode == "ddp" and plan.zero1:
        from repro.core import ddp
        return ddp.init_zero1_state(params, optimizer, mesh, plan)
    return optimizer.init(params)
