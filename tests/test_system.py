"""System-level behaviour: hardware/cost models, package wiring."""
import pytest

from repro import hw


def test_fire_flyer_network_totals():
    net = hw.fire_flyer_network()
    assert net["total_switches"] == 122          # paper Table III
    assert net["zones"] == 2
    assert net["per_zone"] == {"leaf": 40, "spine": 20}


def test_two_layer_fat_tree_800_ports():
    t = hw.FatTree(ports_per_switch=40, layers=2, endpoints=800)
    counts = t.switch_counts()
    assert counts["leaf"] == 40
    assert counts["spine"] == 20
    assert t.max_endpoints == 800


def test_cost_performance_ratio_table2():
    ours, dgx = hw.FIRE_FLYER_NODE, hw.DGX_A100_NODE
    rel_perf = ours.fp16_tflops_per_gpu / dgx.fp16_tflops_per_gpu
    assert rel_perf == pytest.approx(0.8365, abs=0.01)   # ~83%
    cost_perf = rel_perf / ours.node_relative_price
    assert cost_perf == pytest.approx(1.38, abs=0.03)    # paper: 1.38
    assert ours.power_watts / dgx.power_watts == pytest.approx(0.60, abs=0.01)


def test_tpu_roofline_constants():
    assert hw.V5E.peak_bf16_flops == 197e12
    assert hw.V5E.hbm_bw == 819e9
    assert hw.V5E.ici_bw_per_link == 50e9


def test_peak_table_keyed_by_device_kind():
    """Peaks are looked up by jax's device_kind; an unknown kind raises
    instead of defaulting to v5e."""
    assert hw.chip_spec("TPU v5 lite") is hw.V5E
    with pytest.raises(KeyError, match="no peak table"):
        hw.chip_spec("cpu")
    with pytest.raises(KeyError):
        hw.chip_spec(None)      # a dry-run record without device_kind


def test_public_api_imports():
    import repro.core.hfreduce
    import repro.core.tree_allreduce
    import repro.core.compression
    import repro.kernels
    import repro.fs3
    import repro.ckpt
    import repro.platform
    import repro.models
    import repro.launch.mesh


_IMPORT_ALL = """
import importlib, pkgutil
import jax

def boom(*args, **kwargs):
    raise RuntimeError("JAX backend queried at import")

jax.default_backend = jax.devices = boom
import repro
for mod in pkgutil.walk_packages(repro.__path__, "repro."):
    importlib.import_module(mod.name)
"""


def test_imports_do_not_query_the_backend():
    """Importing any module must not ask JAX for its backend or devices:
    on a chip host that would claim the chip for whatever process merely
    imports the package (one process per chip)."""
    import os
    import subprocess
    import sys
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    out = subprocess.run([sys.executable, "-c", _IMPORT_ALL], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-3000:]


def test_dryrun_input_specs():
    # dryrun.py sets XLA_FLAGS at import (by design, for 512 fake devices);
    # pin the backend first and restore the env so other tests (and their
    # subprocesses) keep a 1-device world.
    import os
    import jax
    jax.devices()
    prev = os.environ.get("XLA_FLAGS")
    try:
        from repro.launch.dryrun import input_specs
        specs = input_specs("whisper-base", "decode_32k")
        assert "seq_state" in specs and "params" in specs
        assert specs["tokens"].shape == specs["positions"].shape
        specs = input_specs("codeqwen1.5-7b", "chunk_2k")
        assert specs["tokens"].shape[1] == 2048      # a prefill chunk
        specs = input_specs("qwen3-moe-235b-a22b", "train_4k")
        assert "state" in specs and "batch" in specs
    finally:
        if prev is None:
            os.environ.pop("XLA_FLAGS", None)
        else:
            os.environ["XLA_FLAGS"] = prev
