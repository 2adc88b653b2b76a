"""Hardware constants and cost models.

Two hardware universes live here:

1. The TPU v5e target for the JAX/Pallas system (roofline constants used by
   ``benchmarks/roofline.py`` and the perf loop).
2. The Fire-Flyer 2 / DGX-A100 universe from the paper, used by the
   benchmark harnesses that reproduce the paper's tables and figures
   (Table II/III, Fig. 7/8/9).
"""
from __future__ import annotations

import dataclasses
import math

# ---------------------------------------------------------------------------
# 1. TPU v5e target (per chip) — roofline constants from the brief.
# ---------------------------------------------------------------------------

TPU_PEAK_BF16_FLOPS = 197e12       # FLOP/s per chip
TPU_HBM_BW = 819e9                 # bytes/s per chip
TPU_ICI_BW_PER_LINK = 50e9         # bytes/s per ICI link
TPU_ICI_LINKS_PER_CHIP = 4         # 2-D torus: ±x, ±y
TPU_HBM_BYTES = 16 * 1024**3       # 16 GiB HBM per v5e chip
TPU_VMEM_BYTES = 128 * 1024**2     # ~128 MiB VMEM (v5e ~ 128MB)
# Cross-pod (DCI) effective per-chip bandwidth. Scarce by construction —
# this is the "one IB NIC per node" analogue. We model 1/16 of ICI.
TPU_DCI_BW_PER_CHIP = TPU_ICI_BW_PER_LINK / 16.0


@dataclasses.dataclass(frozen=True)
class ChipSpec:
    name: str
    peak_bf16_flops: float
    hbm_bw: float
    hbm_bytes: int
    ici_bw_per_link: float
    ici_links: int
    dci_bw_per_chip: float

    @property
    def ici_bw(self) -> float:
        return self.ici_bw_per_link * self.ici_links


V5E = ChipSpec(
    name="tpu-v5e",
    peak_bf16_flops=TPU_PEAK_BF16_FLOPS,
    hbm_bw=TPU_HBM_BW,
    hbm_bytes=TPU_HBM_BYTES,
    ici_bw_per_link=TPU_ICI_BW_PER_LINK,
    ici_links=TPU_ICI_LINKS_PER_CHIP,
    dci_bw_per_chip=TPU_DCI_BW_PER_CHIP,
)

# Peak tables keyed by ``jax.Device.device_kind``.  Source of the v5e
# figures: Google Cloud documentation, "TPU v5e" (197 TFLOP/s bf16,
# 16 GB HBM at 819 GB/s per chip).
CHIPS = {"TPU v5 lite": V5E}

# The chip the dry-run models when it lowers on fake CPU devices.
DRYRUN_DEVICE_KIND = "TPU v5 lite"


def chip_spec(device_kind: str) -> ChipSpec:
    """Peaks of ``device_kind``; a kind without a table is an error, never
    a silent default."""
    try:
        return CHIPS[device_kind]
    except KeyError:
        raise KeyError(f"no peak table for device kind {device_kind!r}; "
                       f"known: {sorted(CHIPS)}") from None


# ---------------------------------------------------------------------------
# 2. Fire-Flyer 2 universe (paper constants, used to reproduce tables/figs)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class GPUNodeSpec:
    """One Fire-Flyer 2 or DGX-A100 node (paper Table I/II)."""

    name: str
    gpus: int
    tf32_tflops_per_gpu: float      # measured GEMM, paper Table II
    fp16_tflops_per_gpu: float
    node_relative_price: float      # DGX == 1.0
    power_watts: float
    nics: int
    nic_gbps: float
    pcie_gbps_per_gpu: float        # unidirectional usable PCIe 4.0 x16
    nvlink_gbps_pair: float         # NVLink bridge pair bandwidth (0 = none)
    host_mem_bw_gbps: float         # practical DDR4 bandwidth (paper: 320 GB/s)
    pcie_host_bridge_gbps: float    # EPYC Rome root-complex limit (paper: 37.5)


FIRE_FLYER_NODE = GPUNodeSpec(
    name="fire-flyer2-pcie-a100",
    gpus=8,
    tf32_tflops_per_gpu=107.0,
    fp16_tflops_per_gpu=220.0,
    node_relative_price=0.60,
    power_watts=2500.0,
    nics=1,
    nic_gbps=200.0,
    pcie_gbps_per_gpu=27.0 * 8,     # ~27 GB/s -> Gbps
    nvlink_gbps_pair=600.0 * 8,
    host_mem_bw_gbps=320.0 * 8,
    pcie_host_bridge_gbps=37.5 * 8,
)

DGX_A100_NODE = GPUNodeSpec(
    name="dgx-a100",
    gpus=8,
    tf32_tflops_per_gpu=131.0,
    fp16_tflops_per_gpu=263.0,
    node_relative_price=1.0,
    power_watts=4200.0,
    nics=9,
    nic_gbps=200.0,
    pcie_gbps_per_gpu=27.0 * 8,
    nvlink_gbps_pair=600.0 * 8,
    host_mem_bw_gbps=320.0 * 8 * 4,
    pcie_host_bridge_gbps=37.5 * 8 * 4,
)


# ---------------------------------------------------------------------------
# Fat-tree topology cost model (paper Table III, Section III-B/C).
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class FatTree:
    """A k-port two- or three-layer fat-tree built from fixed-radix switches."""

    ports_per_switch: int
    layers: int           # 2 or 3
    endpoints: int

    def switch_counts(self) -> dict[str, int]:
        p = self.ports_per_switch
        if self.layers == 2:
            # leaf: p/2 down, p/2 up; spine: p down.
            leaves = math.ceil(self.endpoints / (p // 2))
            spines = math.ceil(leaves * (p // 2) / p)
            return {"leaf": leaves, "spine": spines, "core": 0}
        if self.layers == 3:
            # classic 3-tier folded clos with full bisection
            leaves = math.ceil(self.endpoints / (p // 2))
            spines = math.ceil(leaves / 2) * 2
            cores = math.ceil(spines * (p // 2) / p)
            return {"leaf": leaves, "spine": spines, "core": cores}
        raise ValueError(f"unsupported layers={self.layers}")

    @property
    def total_switches(self) -> int:
        return sum(self.switch_counts().values())

    @property
    def max_endpoints(self) -> int:
        p = self.ports_per_switch
        if self.layers == 2:
            return (p // 2) * p  # p spines of p ports
        return (p // 2) ** 2 * p // 2


def fire_flyer_network() -> dict[str, object]:
    """The paper's actual deployment: two 800-port 2-layer fat-tree zones.

    Paper Sec III-B: each zone is an 800-port fat-tree (40 leaf x 40 ports
    down/up... configured with 20 spine + 40 leaf = 60 switches per zone),
    plus a small number of inter-zone links and a storage dual-homing layout.
    Total 122 switches (paper Table III).
    """
    per_zone = {"leaf": 40, "spine": 20}
    zones = 2
    interzone_and_mgmt = 122 - zones * (per_zone["leaf"] + per_zone["spine"])
    return {
        "zones": zones,
        "per_zone": per_zone,
        "interzone_and_mgmt_switches": interzone_and_mgmt,
        "total_switches": 122,
    }


# ---------------------------------------------------------------------------
# Dtype sizes
# ---------------------------------------------------------------------------

DTYPE_BYTES = {
    "float32": 4, "bfloat16": 2, "float16": 2, "int8": 1, "uint8": 1,
    "int32": 4, "int64": 8, "float64": 8, "bool": 1, "int16": 2, "uint32": 4,
    "float8_e4m3fn": 1, "float8_e5m2": 1,
}


def dtype_bytes(dtype) -> int:
    return DTYPE_BYTES[str(getattr(dtype, "name", dtype))]
