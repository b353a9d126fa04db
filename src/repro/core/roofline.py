"""Three-term roofline model over compiled dry-run artifacts (§Roofline).

    compute term    = HLO_FLOPs_per_device   / peak_FLOP/s_per_chip
    memory term     = HLO_bytes_per_device   / HBM_bw_per_chip
    collective term = coll_bytes_per_device  / (links_per_chip * link_bw)

``compiled.cost_analysis()`` reports **per-device** FLOPs and bytes (verified
numerically in this environment), and the device tree's ``coll_bytes`` counts
per-device operand bytes of every collective instruction, so no further
division by chip count is applied. The step-time estimate is the max of the
three terms (perfect-overlap bound); the dominant term is the §Perf target.
"""

from __future__ import annotations

from dataclasses import dataclass, field

from .calltree import CallTree
from .hlo_tree import COLLECTIVE_OPS


@dataclass(frozen=True)
class HardwareSpec:
    """Published peaks of one chip."""

    name: str
    peak_flops: float  # bf16 FLOP/s per chip
    hbm_bw: float  # bytes/s per chip
    ici_link_bw: float  # bytes/s per link
    ici_links: int  # links used by a chip in a 2D torus (2 axes x 2 dirs)
    hbm_bytes: float  # capacity, for fit checks


V5E_KIND = "TPU v5 lite"

#: Peaks keyed by ``jax.Device.device_kind``.  A kind with no entry has no
#: roofline: it is never costed with another chip's peaks.
PEAKS: dict[str, HardwareSpec] = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 16 GB of HBM
    # at 819 GB/s, 1,600 Gbit/s of inter-chip interconnect (4 x 50 GB/s).
    V5E_KIND: HardwareSpec(
        name="tpu-v5e", peak_flops=197e12, hbm_bw=819e9, ici_link_bw=50e9, ici_links=4, hbm_bytes=16e9
    ),
}


def peaks_for(device_kind: str | None) -> HardwareSpec | None:
    """The peaks table entry for a ``device_kind``, or None when it has none."""
    return PEAKS.get(device_kind) if device_kind else None


@dataclass
class RooflineReport:
    arch: str
    shape: str
    mesh: str
    chips: int
    flops_per_device: float
    bytes_per_device: float
    coll_bytes_per_device: float
    hw: HardwareSpec
    coll_by_kind: dict[str, float] = field(default_factory=dict)
    model_flops_global: float = 0.0  # 6*N*D (dense) or 6*N_active*D (MoE)
    per_device_hbm_peak: float = 0.0  # from memory_analysis

    @property
    def t_compute(self) -> float:
        return self.flops_per_device / self.hw.peak_flops

    @property
    def t_memory(self) -> float:
        return self.bytes_per_device / self.hw.hbm_bw

    @property
    def t_collective(self) -> float:
        return self.coll_bytes_per_device / (self.hw.ici_links * self.hw.ici_link_bw)

    @property
    def t_step(self) -> float:
        """Perfect-overlap lower bound on step time."""
        return max(self.t_compute, self.t_memory, self.t_collective)

    @property
    def dominant(self) -> str:
        terms = {"compute": self.t_compute, "memory": self.t_memory, "collective": self.t_collective}
        return max(terms, key=terms.get)

    @property
    def useful_flops_ratio(self) -> float:
        """MODEL_FLOPS / (HLO_FLOPs * chips): how much compiled compute is useful.

        < 1 means remat/redundancy waste; > 1 means the HLO count missed
        something (e.g. attention FLOPs not in the 6ND napkin model).
        """
        total_hlo = self.flops_per_device * self.chips
        return self.model_flops_global / total_hlo if total_hlo else 0.0

    @property
    def mfu(self) -> float:
        """Model FLOPs utilization at the roofline bound."""
        if self.t_step <= 0 or self.chips == 0:
            return 0.0
        return self.model_flops_global / (self.t_step * self.chips * self.hw.peak_flops)

    @property
    def hw_util(self) -> float:
        """Fraction of roofline the dominant resource reaches if the other two
        overlap perfectly: compute-term / step-time when compute-bound, etc."""
        if self.t_step <= 0:
            return 0.0
        return self.t_compute / self.t_step

    def fits_hbm(self) -> bool:
        return self.per_device_hbm_peak <= self.hw.hbm_bytes

    def row(self) -> dict:
        return {
            "arch": self.arch,
            "shape": self.shape,
            "mesh": self.mesh,
            "chips": self.chips,
            "t_compute_s": self.t_compute,
            "t_memory_s": self.t_memory,
            "t_collective_s": self.t_collective,
            "t_step_s": self.t_step,
            "dominant": self.dominant,
            "model_flops": self.model_flops_global,
            "hlo_flops_per_dev": self.flops_per_device,
            "useful_flops_ratio": self.useful_flops_ratio,
            "mfu_bound": self.mfu,
            "hbm_peak_bytes": self.per_device_hbm_peak,
            "fits_hbm": self.fits_hbm(),
            **{f"coll_{k}": v for k, v in self.coll_by_kind.items()},
        }


def report_from_artifacts(
    *,
    arch: str,
    shape: str,
    mesh: str,
    chips: int,
    cost_analysis: dict,
    device_tree: CallTree,
    memory_analysis=None,
    hw: HardwareSpec,
    model_flops_global: float = 0.0,
) -> RooflineReport:
    # XLA's cost_analysis() counts while-loop bodies ONCE (verified: its FLOPs
    # fall short of 6ND by ~the layer count for scanned stacks). The device
    # tree multiplies by known_trip_count, so take the max of both estimates
    # per term (the tree counts dot/conv FLOPs only; cost_analysis adds
    # elementwise FLOPs but misses loop trips).
    flops = max(float(cost_analysis.get("flops", 0.0)), device_tree.total("flops"))
    byts = max(float(cost_analysis.get("bytes accessed", 0.0)), device_tree.total("bytes"))
    coll = device_tree.total("coll_bytes")
    by_kind = {}
    for k in COLLECTIVE_OPS:
        v = device_tree.root.metrics.get(f"coll_bytes::{k}", 0.0)
        if v:
            by_kind[k] = v
    hbm_peak = 0.0
    if memory_analysis is not None:
        hbm_peak = float(
            getattr(memory_analysis, "argument_size_in_bytes", 0.0)
            + getattr(memory_analysis, "output_size_in_bytes", 0.0)
            + getattr(memory_analysis, "temp_size_in_bytes", 0.0)
            - getattr(memory_analysis, "alias_size_in_bytes", 0.0)
        )
    return RooflineReport(
        arch=arch,
        shape=shape,
        mesh=mesh,
        chips=chips,
        flops_per_device=flops,
        bytes_per_device=byts,
        coll_bytes_per_device=coll,
        coll_by_kind=by_kind,
        model_flops_global=model_flops_global,
        per_device_hbm_peak=hbm_peak,
        hw=hw,
    )
