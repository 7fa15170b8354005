"""Roofline math for the TPU v5e target.

The dry-run gives us compiled HLO FLOPs / bytes / collective traffic, and
this module turns those into the three roofline terms per chip:

    compute    = HLO_FLOPs      / (chips * PEAK_FLOPS)
    memory     = HLO_bytes      / (chips * HBM_BW)
    collective = collective_B   / (chips * ICI_BW)

The peaks are the dry-run target's, a TPU v5e (``V5E``).
"""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class DevicePeaks:
    flops: float        # bf16 FLOP/s per chip
    hbm_bw: float       # bytes/s per chip
    ici_bw: float       # bytes/s per link
    hbm_bytes: int      # HBM per chip


# Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 16 GB HBM at
# 819 GB/s, 1,600 Gbit/s of chip-to-chip interconnect over 4 links.
V5E = DevicePeaks(flops=197e12, hbm_bw=819e9, ici_bw=50e9,
                  hbm_bytes=16 * 1024**3)

# the dry-run's target (production v5e meshes)
PEAK_FLOPS = V5E.flops
HBM_BW = V5E.hbm_bw
ICI_BW = V5E.ici_bw
HBM_PER_CHIP = V5E.hbm_bytes


@dataclasses.dataclass(frozen=True)
class Roofline:
    arch: str
    shape: str
    mesh: str
    chips: int
    hlo_flops: float
    hlo_bytes: float
    collective_bytes: float
    model_flops: float
    bytes_per_device: float = 0.0
    hlo_bytes_fused: float = 0.0     # HBM bytes with Pallas-fused attention

    @property
    def t_compute(self) -> float:
        # cost_analysis flops are whole-program (already per-device under SPMD)
        return self.hlo_flops / PEAK_FLOPS

    @property
    def t_memory(self) -> float:
        return self.hlo_bytes / HBM_BW

    @property
    def t_memory_fused(self) -> float:
        return (self.hlo_bytes_fused or self.hlo_bytes) / HBM_BW

    @property
    def t_collective(self) -> float:
        return self.collective_bytes / ICI_BW

    @property
    def dominant(self) -> str:
        terms = {
            "compute": self.t_compute,
            "memory": self.t_memory,
            "collective": self.t_collective,
        }
        return max(terms, key=terms.get)

    @property
    def useful_flops_ratio(self) -> float:
        """MODEL_FLOPS / HLO_FLOPs on a per-chip basis; catches remat and
        redundant-compute waste.  >1 means HLO under-counts (fusion),
        <1 means recompute/padding overhead."""
        if self.hlo_flops <= 0:
            return float("nan")
        return (self.model_flops / self.chips) / self.hlo_flops

    @property
    def bound_time(self) -> float:
        return max(self.t_compute, self.t_memory, self.t_collective)

    def row(self) -> dict:
        return {
            "arch": self.arch,
            "shape": self.shape,
            "mesh": self.mesh,
            "chips": self.chips,
            "t_compute_s": self.t_compute,
            "t_memory_s": self.t_memory,
            "t_memory_fused_s": self.t_memory_fused,
            "t_collective_s": self.t_collective,
            "dominant": self.dominant,
            "model_flops": self.model_flops,
            "hlo_flops": self.hlo_flops,
            "useful_ratio": self.useful_flops_ratio,
            "bytes_per_device": self.bytes_per_device,
            "fits_hbm": self.bytes_per_device <= HBM_PER_CHIP,
        }


def dense_model_flops(num_params: int, tokens: int) -> float:
    """MODEL_FLOPS = 6*N*D for a training step over D tokens."""
    return 6.0 * num_params * tokens


def moe_model_flops(active_params: int, tokens: int) -> float:
    """MoE uses activated parameters only: 6*N_active*D."""
    return 6.0 * active_params * tokens


def decode_model_flops(num_params_active: int, batch: int) -> float:
    """One decode step = forward only over `batch` new tokens: 2*N*B."""
    return 2.0 * num_params_active * batch
