"""Elastic fault-tolerant training (DESIGN.md §13): plan-stamped sharded
checkpoints, cross-plan resharding, and the kill/resume failure-injection
harness (8 fake devices in a subprocess, like test_collectives)."""
import json
import os
import subprocess
import sys
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.ckpt import CheckpointManager
from repro.elastic import (ElasticCheckpointer, PlanMismatchError,
                           canonical_state, master_layout, plan_from_dict,
                           plan_to_dict, plans_equal, reshard, save_sharded)
from repro.launch.mesh import make_mesh
from repro.optim import AdamW
from repro.parallel.plan import ParallelPlan, init_state

_RESULT = {}


def _run_elastic_harness():
    global _RESULT
    if _RESULT:
        return _RESULT
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    out = subprocess.run(
        [sys.executable, "-m", "repro.testing.multidev", "elastic"],
        capture_output=True, text=True, env=env, timeout=1800)
    assert out.returncode == 0, out.stderr[-3000:]
    for line in out.stdout.splitlines():
        if line.startswith("MULTIDEV_JSON:"):
            _RESULT = json.loads(line[len("MULTIDEV_JSON:"):])
            return _RESULT
    raise AssertionError("no MULTIDEV_JSON in output:\n" + out.stdout)


# ---------------------- manifest (single device) ----------------------


def _params():
    return {"emb": jnp.arange(12, dtype=jnp.float32).reshape(3, 4) / 7.0,
            "blk": {"w": jnp.ones((5,), jnp.float32) * 0.3,
                    "b": jnp.arange(7, dtype=jnp.float32) - 3.0}}


def test_plan_manifest_roundtrip():
    for plan in (ParallelPlan(),
                 ParallelPlan(mode="ddp", zero1=True, overlap=False),
                 ParallelPlan(mode="pp", pp_schedule="gpipe",
                              pp_microbatches=8, compress="int8")):
        d = plan_to_dict(plan)
        json.loads(json.dumps(d))          # JSON-serializable
        assert plan_from_dict(d) == plan
        assert plans_equal(plan, d)
    assert not plans_equal(ParallelPlan(), plan_to_dict(
        ParallelPlan(mode="ddp", zero1=True, overlap=False)))


def test_master_layout_offsets_cover_flat():
    params = _params()
    lay = master_layout(params)
    sizes = {p: e - s for p, (s, e) in lay["offsets"].items()}
    leaves = jax.tree_util.tree_flatten_with_path(params)[0]
    assert sum(sizes.values()) == lay["total"] == sum(
        int(np.prod(l.shape)) for _, l in leaves)
    # offsets are contiguous in tree-flatten order
    ends = sorted(e for _, e in lay["offsets"].values())
    starts = sorted(s for s, _ in lay["offsets"].values())
    assert starts[0] == 0 and ends[-1] == lay["total"]
    assert starts[1:] == ends[:-1]
    # bucket slices land on leaf boundaries and cover [0, total)
    assert lay["bucket_slices"][-1][0] == 0 or lay["bucket_slices"]
    covered = sorted(tuple(s) for s in lay["bucket_slices"])
    assert covered[0][0] == 0 and covered[-1][1] == lay["total"]


def test_sharded_roundtrip_and_plan_stamp(tmp_path):
    params = _params()
    opt = AdamW(lr=1e-2, param_dtype="float32")
    plan = ParallelPlan(mode="gspmd")
    mesh = make_mesh((1, 1), ("pod", "data"))
    state = opt.init(params)

    mgr = save_sharded(state, plan, mesh, step=4,
                       root_or_backend=str(tmp_path))
    man = mgr.load_manifest(4)
    assert man["layout"] == "tree" and man["step"] == 4
    assert plans_equal(plan, man["plan"])
    assert man["mesh"]["axes"] == ["pod", "data"]

    restored, step = mgr.restore_latest(state)
    assert step == 4
    for a, b in zip(jax.tree_util.tree_leaves(restored),
                    jax.tree_util.tree_leaves(state)):
        assert np.array_equal(np.asarray(a), np.asarray(b))


def test_cross_plan_restore_requires_opt_in(tmp_path):
    params = _params()
    opt = AdamW(lr=1e-2, param_dtype="float32")
    mesh = make_mesh((1, 1), ("pod", "data"))
    state = opt.init(params)
    mgr = save_sharded(state, ParallelPlan(mode="gspmd"), mesh, step=1,
                       root_or_backend=str(tmp_path))

    other = ElasticCheckpointer(
        str(tmp_path), ParallelPlan(mode="ddp", zero1=True, overlap=False),
        mesh)
    with pytest.raises(PlanMismatchError):
        other.restore_latest(state)
    # the explicit cross-plan door still opens
    restored, step = other.restore_for(other.plan, mesh, params)
    assert step == 1
    total = sum(int(np.prod(l.shape))
                for l in jax.tree_util.tree_leaves(params))
    flat_ref = np.concatenate(
        [np.asarray(l, np.float32).ravel()
         for l in jax.tree_util.tree_leaves(state["master"])])
    assert np.array_equal(np.asarray(restored["master"])[:total], flat_ref)


def test_reshard_tree_to_zero1_and_back(tmp_path):
    params = _params()
    opt = AdamW(lr=1e-2, param_dtype="float32")
    mesh = make_mesh((1, 1), ("pod", "data"))
    plan_t = ParallelPlan(mode="gspmd")
    plan_z = ParallelPlan(mode="ddp", zero1=True, overlap=False)
    state = opt.init(params)
    # give the moments non-trivial values so the remap is visible
    state = dict(state)
    state["m"] = jax.tree_util.tree_map(
        lambda x: x * 0.5 + 1.0, state["master"])

    mgr = save_sharded(state, plan_t, mesh, step=2,
                       root_or_backend=str(tmp_path))
    z, _ = reshard(mgr, plan_z, mesh, params, step=2)
    assert z["master"].ndim == 1

    # write the zero1 state back out and reshard to a tree again
    mgr2 = ElasticCheckpointer(str(tmp_path / "z"), plan_z, mesh)
    mgr2.save(z, 3, blocking=True)
    assert mgr2.load_manifest(3)["layout"] == "zero1_flat"
    t, _ = reshard(mgr2, plan_t, mesh, params, step=3)
    for k in ("master", "m", "v", "params"):
        for a, b in zip(jax.tree_util.tree_leaves(t[k]),
                        jax.tree_util.tree_leaves(state[k])):
            assert np.array_equal(np.asarray(a), np.asarray(b)), k
    assert int(t["step"]) == int(state["step"])


def test_canonical_state_async_save(tmp_path):
    """Async sharded save lands the same canonical bytes as blocking."""
    params = _params()
    opt = AdamW(lr=1e-2, param_dtype="float32")
    mesh = make_mesh((1, 1), ("pod", "data"))
    plan = ParallelPlan(mode="ddp", zero1=True, overlap=False)
    state = init_state(plan, opt, params, mesh)

    mgr_a = ElasticCheckpointer(str(tmp_path / "a"), plan, mesh)
    mgr_a.save(state, 7, blocking=False)
    mgr_a.wait()
    mgr_b = ElasticCheckpointer(str(tmp_path / "b"), plan, mesh)
    mgr_b.save(state, 7, blocking=True)

    ca, cb = canonical_state(mgr_a, 7), canonical_state(mgr_b, 7)
    for k in ("master", "m", "v"):
        assert np.array_equal(ca["flats"][k], cb["flats"][k])
    # "step" is the *optimizer* counter saved in the state (fresh -> 0);
    # the checkpoint step lives in the manifest
    assert ca["step"] == cb["step"] == 0
    assert ca["manifest"]["step"] == cb["manifest"]["step"] == 7


def test_elastic_keeps_manager_gc(tmp_path):
    """Plan-stamped steps respect ``keep=`` like plain checkpoints."""
    params = _params()
    opt = AdamW(lr=1e-2, param_dtype="float32")
    mesh = make_mesh((1, 1), ("pod", "data"))
    plan = ParallelPlan(mode="gspmd")
    state = opt.init(params)
    mgr = ElasticCheckpointer(str(tmp_path), plan, mesh, keep=2)
    for s in (1, 2, 3):
        mgr.save(state, s, blocking=True)
    assert sorted(mgr.backend.list_steps()) == [2, 3]
    assert mgr.backend.exists("step_3/plan.json")
    assert not mgr.backend.exists("step_1/index.json")


# ------------------- kill/resume harness (8 devices) -------------------


def test_same_plan_kill_resume_bitwise():
    r = _run_elastic_harness()["elastic_same_plan"]
    assert r["losses_bitwise"], r
    assert r["state_diff"] == 0.0
    assert r["failures"] == 1 and r["restores"] == 1
    assert r["rescales"] == 0          # sampled class was non-fatal
    assert r["lost_steps"] == 2        # killed at 7, checkpoint at 5


def test_cross_plan_reshard_resume_continuity():
    r = _run_elastic_harness()["elastic_cross_plan"]
    # pp(2 stages, 8 dev) -> ddp+zero1(4 dev): 5 post-restore steps
    assert len(r["cont_losses"]) == 5
    assert r["post_err"] <= 1e-5, r
    assert r["failures"] == 1 and r["restores"] == 1
    assert r["rescales"] == 1 and r["world"] == 1
    assert r["lost_steps"] == 2


@pytest.mark.parametrize("leg", ["elastic_same_plan", "elastic_cross_plan"])
def test_harness_events_exactly_once(leg):
    d = _run_elastic_harness()[leg]["digest"]
    # one emit point: the JSONL stream is exactly the report's events
    assert d["jsonl_matches_report"]
    assert d["n_jsonl"] == d["n_report"]
    assert d["unique"], "duplicate platform event on the JSONL stream"
    assert d["kinds"]["failure"] == 1
    assert d["kinds"]["restore"] == 1
    # start save + step-5 periodic + step-10 periodic + final blocking
    assert d["kinds"]["ckpt"] == 4
    if leg == "elastic_cross_plan":
        assert d["kinds"]["rescale"] == 1
    else:
        assert "rescale" not in d["kinds"]
