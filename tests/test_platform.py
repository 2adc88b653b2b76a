"""HAI platform: scheduler invariants, failure model, validator, FT runner."""
import dataclasses as dc
import tempfile

import jax
import jax.numpy as jnp
import pytest

from repro.platform import (Cluster, FailureInjector, FailureModel, FTRunner,
                            Scheduler, Task, Validator)


# ------------------------------ scheduler ----------------------------------


def test_single_zone_placement_preferred():
    s = Scheduler(Cluster(n_nodes=8, zones=2))
    s.submit(Task(1, n_nodes=4, priority=1, runtime_hours=1))
    s.schedule()
    t = s.running[1]
    zones = {s.cluster.nodes[n]["zone"] for n in t.nodes}
    assert len(zones) == 1 and not t.cross_zone


def test_at_most_one_cross_zone_task():
    s = Scheduler(Cluster(n_nodes=8, zones=2))
    s.submit(Task(1, n_nodes=6, priority=1, runtime_hours=2))  # cross
    s.submit(Task(2, n_nodes=2, priority=1, runtime_hours=2))
    s.schedule()
    assert s.running[1].cross_zone
    # a second cross-zone task must wait even though nodes are free
    s.submit(Task(3, n_nodes=2, priority=1, runtime_hours=1, zone_pref=None))
    s.schedule()
    cross = [t for t in s.running.values() if t.cross_zone]
    assert len(cross) == 1


def test_preemption_interrupts_lower_priority():
    s = Scheduler(Cluster(n_nodes=4, zones=2))
    s.submit(Task(1, n_nodes=4, priority=0, runtime_hours=10))
    s.schedule()
    s.submit(Task(2, n_nodes=4, priority=9, runtime_hours=1))
    s.schedule()
    assert 2 in s.running
    assert 1 not in s.running
    victim = next(t for _, _, t in s._queue if t.task_id == 1)
    assert victim.interruptions == 1


def test_node_failure_interrupts_and_reschedules():
    s = Scheduler(Cluster(n_nodes=6, zones=2))
    s.submit(Task(1, n_nodes=2, priority=1, runtime_hours=4))
    s.schedule()
    victim_node = s.running[1].nodes[0]
    s.node_failure(victim_node)
    assert 1 not in s.running
    s.schedule()
    assert 1 in s.running, "task rescheduled on healthy nodes"
    assert victim_node not in s.running[1].nodes


def test_utilization_accounting():
    s = Scheduler(Cluster(n_nodes=4, zones=2))
    s.submit(Task(1, n_nodes=4, priority=1, runtime_hours=2))
    s.advance(1.0)
    s.advance(1.0)
    assert s.utilization() == pytest.approx(1.0)


# ----------------------------- failure model -------------------------------


def test_failure_rates_match_paper_tables():
    fm = FailureModel(0)
    r = fm.rates_per_node_hour()
    # 12,970 xids / 1,250 nodes / 8,760 h
    assert r["xid"] == pytest.approx(12970 / 1250 / 8760, rel=1e-6)
    ev = fm.sample(1250, 24 * 30)
    assert 900 <= len(ev) <= 1300   # ~1,100 expected per month
    assert all(e.t_hours <= 24 * 30 for e in ev)
    kinds = {e.cls for e in ev}
    assert "nvlink_xid74" in kinds  # dominant class (42.57 %)


def test_cluster_mtbf_motivates_5min_checkpoints():
    fm = FailureModel(0)
    mtbf = fm.cluster_mtbf_hours(1250)
    assert mtbf < 2.0, "at paper scale, failures are sub-2-hourly"


# ------------------------------ validator ----------------------------------


def test_validator_suite_passes_on_healthy_node():
    v = Validator(gemm_n=96, mem_mb=4, storage_mb=2)
    results = v.run_all()
    failed = [c.name for c in results if not c.ok]
    assert not failed, failed


# ------------------------------ FT runner ----------------------------------


def _tiny_setup():
    from repro.configs.base import ParallelConfig
    from repro.configs.registry import smoke_config
    from repro.data.synthetic import batch_for_model
    from repro.models import build_model
    from repro.optim import AdamW
    from repro import train_lib
    from repro.launch.mesh import make_mesh

    cfg = dc.replace(smoke_config("phi4-mini-3.8b"), n_layers=2,
                     compute_dtype="float32")
    model = build_model(cfg)
    opt = AdamW(lr=1e-3, param_dtype="float32")
    state = opt.init(model.init(jax.random.PRNGKey(0)))
    mesh = make_mesh((1, 1), ("data", "model"))
    pcfg = ParallelConfig(tp=1, fsdp=False, batch_axes=("data",))

    def make_step(world):
        return jax.jit(train_lib.make_train_step(model, opt, pcfg, mesh))

    def fetch(step):
        return {k: jnp.asarray(v) for k, v in
                batch_for_model(cfg, "train", step, 2, 32).items()}

    return make_step, fetch, state


def test_ft_runner_recovers_and_rescales(tmp_path):
    from repro.ckpt import CheckpointManager
    make_step, fetch, state = _tiny_setup()
    inj = FailureInjector({6: "uncorrectable", 11: "nvlink_xid74"})
    r = FTRunner(make_step, fetch, CheckpointManager(str(tmp_path)), state,
                 world_size=4, min_world=2, ckpt_every=5,
                 injector=inj).run(15)
    assert r.failures == 2
    assert r.restores == 2
    assert r.rescales == 2          # both classes are fatal -> shrink twice
    assert r.steps_done >= 15
    assert r.lost_steps <= 2 * 5    # bounded by ckpt_every


def test_ft_runner_resume_determinism(tmp_path):
    """Interrupted+restored run reaches the same state as an unbroken one."""
    from repro.ckpt import CheckpointManager
    make_step, fetch, state0 = _tiny_setup()

    mgr1 = CheckpointManager(str(tmp_path / "a"))
    r1 = FTRunner(make_step, fetch, mgr1,
                  jax.tree_util.tree_map(jnp.copy, state0),
                  world_size=2, ckpt_every=5).run(10)
    mgr2 = CheckpointManager(str(tmp_path / "b"))
    inj = FailureInjector({7: "cpu_ecc"})
    r2 = FTRunner(make_step, fetch, mgr2,
                  jax.tree_util.tree_map(jnp.copy, state0),
                  world_size=2, ckpt_every=5, injector=inj,
                  min_world=2).run(10)
    s1, _ = mgr1.restore_latest(state0)
    s2, _ = mgr2.restore_latest(state0)
    for a, b in zip(jax.tree_util.tree_leaves(s1["master"]),
                    jax.tree_util.tree_leaves(s2["master"])):
        assert bool(jnp.allclose(a, b, atol=1e-6)), \
            "resume after failure diverged from unbroken run"


def test_validator_gates_restore_and_rescale(tmp_path):
    """A node failing its validation suite after a *non-fatal* failure is
    still excluded from the restored gang: the runner emits a
    ``validator`` event (healthy=False, excluded=True) and rescales."""
    from repro.ckpt import CheckpointManager
    from repro.platform.failures import EVENT_KINDS
    from repro.platform.validator import CheckResult

    assert "validator" in EVENT_KINDS
    make_step, fetch, state = _tiny_setup()

    sick = Validator(gemm_n=64, mem_mb=2, storage_mb=1)
    # silent-corruption detector trips: run_all() reports the failure
    sick.check_gemm = lambda: CheckResult("gemm_oracle", False, 0.0, "")
    assert not sick.node_healthy()

    inj = FailureInjector({6: "sw_xid31"})      # non-fatal class
    r = FTRunner(make_step, fetch, CheckpointManager(str(tmp_path)), state,
                 world_size=4, min_world=2, ckpt_every=5, injector=inj,
                 validator=sick).run(10)
    assert r.failures == 1 and r.restores == 1
    assert r.rescales == 1, "unhealthy node must leave the rescale mesh"
    vevents = [e for e in r.events if e["kind"] == "validator"]
    assert len(vevents) == 1
    assert vevents[0]["healthy"] is False and vevents[0]["excluded"] is True
    # ordering: the health verdict lands before restore/rescale
    kinds = [e["kind"] for e in r.events]
    assert kinds.index("validator") < kinds.index("restore") < \
        kinds.index("rescale")


def test_validator_healthy_node_keeps_world(tmp_path):
    """Same non-fatal class with a passing validator: restore only, no
    rescale, and the validator event records healthy=True."""
    from repro.ckpt import CheckpointManager

    make_step, fetch, state = _tiny_setup()
    ok = Validator(gemm_n=64, mem_mb=2, storage_mb=1)
    inj = FailureInjector({6: "sw_xid31"})
    r = FTRunner(make_step, fetch, CheckpointManager(str(tmp_path)), state,
                 world_size=4, min_world=2, ckpt_every=5, injector=inj,
                 validator=ok).run(10)
    assert r.failures == 1 and r.restores == 1 and r.rescales == 0
    vevents = [e for e in r.events if e["kind"] == "validator"]
    assert len(vevents) == 1 and vevents[0]["healthy"] is True
