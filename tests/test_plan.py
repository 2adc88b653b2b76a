"""ParallelPlan validation + pipeline schedule math (single device).

The multi-device numerics for each executor live in test_collectives.py
(via testing/multidev.py); these are the cheap structural checks.
"""
import pathlib
import re

import jax.numpy as jnp
import pytest

from repro.core.bucketing import bucket_leaf_ranges, plan_buckets
from repro.parallel.plan import ParallelPlan
from repro.parallel.pp import bubble_fraction, peak_live_activations


# ------------------------------- plan ---------------------------------


def test_plan_defaults_valid():
    plan = ParallelPlan()
    assert plan.mode == "gspmd"
    assert plan.overlap


@pytest.mark.parametrize("kw", [
    {"mode": "nope"},
    {"grad_sync": "ring"},
    {"compress": "fp4"},
    {"pp_schedule": "interleaved"},
    {"pp_microbatches": 0},
    {"mode": "ddp", "zero1": True, "compress": "fp8"},
    {"mode": "ddp", "zero1": True, "overlap": True},
    {"mode": "ddp", "overlap": True, "bucketed": False},
    {"mode": "ddp", "microbatch": 4},
    {"mode": "ddp", "grad_sync": "flat", "compress": "int8"},
    {"mode": "pp", "grad_sync": "flat", "compress": "bf16"},
])
def test_plan_rejects_bad_combos(kw):
    with pytest.raises(ValueError):
        ParallelPlan(**kw)


def test_plan_zero1_needs_posthoc_but_gspmd_does_not():
    # the gspmd path has no overlap hooks — zero1+overlap is fine there
    assert ParallelPlan(mode="gspmd", zero1=True).zero1
    assert ParallelPlan(mode="ddp", zero1=True, overlap=False).zero1


def test_plan_lowers_to_parallel_config():
    plan = ParallelPlan(mode="gspmd", tp=2, zero1=True, microbatch=4,
                        compress="bf16", grad_sync="flat",
                        batch_axes=("data",))
    pcfg = plan.gspmd_config()
    assert pcfg.tp == 2
    assert pcfg.zero1_pod
    assert pcfg.microbatch == 4
    assert pcfg.grad_compression == "bf16"
    assert not pcfg.hier_allreduce
    assert pcfg.batch_axes == ("data",)


def test_plan_ddp_requires_params_template():
    import jax
    from repro.parallel.plan import make_train_step
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((1, 1), ("pod", "data"))
    with pytest.raises(ValueError, match="params_template"):
        make_train_step(ParallelPlan(mode="ddp"), None, None, mesh)


# --------------------------- bucket ranges ----------------------------


def test_bucket_leaf_ranges_cover_all_leaves():
    tree = {"a": jnp.zeros((100,)), "b": jnp.zeros((3, 7)),
            "c": jnp.zeros((50,)), "d": jnp.zeros((2, 2))}
    plan = plan_buckets(tree, bucket_bytes=256)
    ranges = bucket_leaf_ranges(plan)
    assert len(ranges) == len(plan.bucket_slices)
    covered = sorted(i for i0, i1 in ranges for i in range(i0, i1))
    assert covered == list(range(len(plan.shapes)))
    # each range's element count equals its flat slice length
    for (i0, i1), (s, e) in zip(ranges, plan.bucket_slices):
        assert sum(plan.sizes[i0:i1]) == e - s


def test_bucket_leaf_ranges_single_bucket():
    tree = {"a": jnp.zeros((4,)), "b": jnp.zeros((4,))}
    plan = plan_buckets(tree, bucket_bytes=1 << 20)
    assert bucket_leaf_ranges(plan) == ((0, 2),)


# ------------------------- schedule math ------------------------------


def test_bubble_fraction_both_schedules():
    # Fig. 9 term: (P-1)/(m+P-1); shared by GPipe and 1F1B
    for schedule in ("gpipe", "1f1b"):
        assert bubble_fraction(1, 8, schedule) == 0.0
        assert bubble_fraction(4, 4, schedule) == pytest.approx(3 / 7)
        assert bubble_fraction(10, 40, schedule) == pytest.approx(9 / 49)
        # more microbatches -> smaller bubble, monotonically
        fracs = [bubble_fraction(4, m, schedule) for m in (1, 2, 4, 8, 16)]
        assert fracs == sorted(fracs, reverse=True)
    with pytest.raises(ValueError):
        bubble_fraction(4, 4, "zb-h1")


def test_design_doc_sections_exist():
    """Every `DESIGN.md §N` citation in the codebase resolves to a real
    `## §N` section — modules must not cite documentation that does not
    exist."""
    root = pathlib.Path(__file__).resolve().parent.parent
    design = (root / "DESIGN.md").read_text()
    sections = set(re.findall(r"^## §(\d+)", design, flags=re.M))
    assert sections, "DESIGN.md has no numbered sections"
    cited = set()
    for sub in ("src", "tests", "benchmarks", "examples"):
        for path in (root / sub).rglob("*.py"):
            for ref in re.findall(r"DESIGN\.md §(\d+)", path.read_text()):
                cited.add((str(path.relative_to(root)), ref))
    assert cited, "expected at least one DESIGN.md citation"
    missing = [(p, ref) for p, ref in cited if ref not in sections]
    assert not missing, f"stale DESIGN.md citations: {missing}"


def test_peak_live_activations():
    # GPipe holds every microbatch; 1F1B is bounded by the stage count
    assert peak_live_activations(4, 16, "gpipe") == 16
    assert peak_live_activations(4, 16, "1f1b") == 7
    assert peak_live_activations(4, 3, "1f1b") == 3   # m < bound
    for m in (1, 4, 64):
        assert peak_live_activations(8, m, "1f1b") == min(m, 15)
        assert (peak_live_activations(8, m, "1f1b")
                <= peak_live_activations(8, m, "gpipe"))
    with pytest.raises(ValueError):
        peak_live_activations(4, 4, "zb-h1")
