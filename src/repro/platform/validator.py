"""Node validator (paper §VII-B): the weekly health suite that removes
faulty nodes from scheduling before they corrupt a run.

Checks mirror the paper's list, adapted to what is actually measurable in
this process: device inventory & dtype support (link/frequency analogue),
CPU stress + memory bandwidth, accelerator-memory pattern test (every byte
of a large buffer), full-occupancy GEMM with a numerical oracle (catches
silent-data-corruption-style wrong math), intra-node allreduce (psum over
local devices), and storage read/write bandwidth.
"""
from __future__ import annotations

import dataclasses
import os
import tempfile

import jax
import jax.numpy as jnp
import numpy as np

from repro.launch.mesh import make_mesh
from repro.telemetry import now, span


@dataclasses.dataclass
class CheckResult:
    name: str
    ok: bool
    value: float
    unit: str
    detail: str = ""


class Validator:
    def __init__(self, gemm_n: int = 512, mem_mb: int = 64,
                 storage_mb: int = 32):
        self.gemm_n = gemm_n
        self.mem_mb = mem_mb
        self.storage_mb = storage_mb

    # -- individual checks --

    def check_devices(self) -> CheckResult:
        devs = jax.devices()
        ok = len(devs) >= 1
        try:
            jnp.zeros((2,), jnp.bfloat16) + 1  # dtype support (FP16-era gate)
        except Exception:
            ok = False
        return CheckResult("devices_and_dtypes", ok, len(devs), "devices")

    def check_cpu_memory_bandwidth(self) -> CheckResult:
        n = self.mem_mb * 1024 * 1024 // 8
        a = np.ones(n, np.float64)
        t0 = now()
        for _ in range(3):
            b = a * 1.0000001
        dt = now() - t0
        gbps = 3 * 2 * n * 8 / dt / 1e9
        return CheckResult("cpu_mem_bandwidth", gbps > 0.5, gbps, "GB/s")

    def check_device_memory(self) -> CheckResult:
        """Write/read-back pattern over a large buffer (paper: every byte)."""
        n = self.mem_mb * 1024 * 1024 // 4
        pat = jnp.arange(n, dtype=jnp.uint32) * np.uint32(2654435761)
        back = jax.device_get(pat)
        expect = (np.arange(n, dtype=np.uint64) * 2654435761) % (1 << 32)
        ok = bool(np.array_equal(back, expect.astype(np.uint32)))
        return CheckResult("device_memory_pattern", ok, n * 4 / 1e6, "MB")

    def check_gemm(self) -> CheckResult:
        """Full GEMM vs float64 oracle — silent-corruption detector."""
        n = self.gemm_n
        rng = np.random.default_rng(0)
        a = rng.standard_normal((n, n)).astype(np.float32)
        b = rng.standard_normal((n, n)).astype(np.float32)
        t0 = now()
        c = np.asarray(jnp.dot(a, b))
        dt = now() - t0
        ref = a.astype(np.float64) @ b.astype(np.float64)
        err = float(np.max(np.abs(c - ref)) / (np.abs(ref).max() + 1e-9))
        gflops = 2 * n ** 3 / dt / 1e9
        return CheckResult("gemm_oracle", err < 1e-4, gflops, "GFLOP/s",
                           f"rel_err={err:.2e}")

    def check_allreduce(self) -> CheckResult:
        """Intra-node allreduce over all local devices (paper: NVLink test)."""
        devs = jax.devices()
        x = jnp.ones((len(devs), 1024), jnp.float32)
        try:
            from jax.sharding import PartitionSpec as P
            mesh = make_mesh((len(devs),), ("d",))
            out = jax.shard_map(lambda v: jax.lax.psum(v, "d"), mesh=mesh,
                                in_specs=P("d"), out_specs=P("d"))(x)
            ok = bool(jnp.all(out == float(len(devs))))
        except Exception as e:  # pragma: no cover
            return CheckResult("intra_node_allreduce", False, 0, "",
                               detail=str(e))
        return CheckResult("intra_node_allreduce", ok, len(devs), "devices")

    def check_storage(self, root: str | None = None) -> CheckResult:
        data = os.urandom(self.storage_mb * 1024 * 1024)
        with tempfile.NamedTemporaryFile(dir=root, delete=True) as f:
            t0 = now()
            f.write(data)
            f.flush()
            os.fsync(f.fileno())
            t_w = now() - t0
            f.seek(0)
            t0 = now()
            back = f.read()
            t_r = now() - t0
        ok = back == data and t_w > 0
        mbps = self.storage_mb / max(t_w, 1e-9)
        return CheckResult("storage_bandwidth", ok, mbps, "MB/s write",
                           f"read={self.storage_mb / max(t_r, 1e-9):.0f}MB/s")

    # -- suite --

    def run_all(self, storage_root: str | None = None) -> list[CheckResult]:
        checks = [
            (self.check_devices, ()),
            (self.check_cpu_memory_bandwidth, ()),
            (self.check_device_memory, ()),
            (self.check_gemm, ()),
            (self.check_allreduce, ()),
            (self.check_storage, (storage_root,)),
        ]
        out = []
        for fn, args in checks:
            with span(f"validator.{fn.__name__}"):
                out.append(fn(*args))
        return out

    def node_healthy(self, storage_root: str | None = None) -> bool:
        return all(c.ok for c in self.run_all(storage_root))
