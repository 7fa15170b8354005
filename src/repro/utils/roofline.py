"""Roofline math for the TPU v5e target.

The dry-run gives us compiled HLO FLOPs / bytes / collective traffic, and
this module turns those into the three roofline terms per chip:

    compute    = HLO_FLOPs      / (chips * PEAK_FLOPS)
    memory     = HLO_bytes      / (chips * HBM_BW)
    collective = collective_B   / (chips * ICI_BW)

Peaks live in ``PEAKS``, keyed by ``jax.Device.device_kind``.  A device
that is not in the table has no peak: nothing divides by one for it.
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
PEAKS: dict[str, DevicePeaks] = {"TPU v5 lite": V5E, "TPU v5e": V5E}

# the dry-run's target (production v5e meshes)
PEAK_FLOPS = V5E.flops
HBM_BW = V5E.hbm_bw
ICI_BW = V5E.ici_bw
HBM_PER_CHIP = V5E.hbm_bytes


def device_peaks(device_kind: str) -> DevicePeaks | None:
    """Published peaks of ``device_kind``; None for a device not in the
    table (the CPU among them)."""
    return PEAKS.get(device_kind)


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


def drift_scan_bytes(rows: int, num_classes: int,
                     dtype_bytes: int = 4) -> float:
    """HBM traffic of one drift-scan pass: stream the stored and fresh
    ``[rows, C]`` label-dist arenas in, one ``[rows]`` drift column out."""
    return float(rows) * (2.0 * num_classes + 1.0) * dtype_bytes


def record_bandwidth(metrics, name: str, nbytes: float, seconds: float,
                     peak_bw: float | None = HBM_BW) -> float:
    """Record achieved vs roofline-predicted bandwidth for one measured
    pass as gauges (``<name>/achieved_gbs``, ``<name>/predicted_gbs``,
    ``<name>/efficiency``) on a metric registry; returns the achieved
    bytes/s.  ``peak_bw=None`` (a device with no published peak) records
    the achieved figure only.
    """
    achieved = nbytes / seconds if seconds > 0 else float("nan")
    metrics.gauge(f"{name}/achieved_gbs").set(achieved / 1e9)
    if peak_bw is not None:
        metrics.gauge(f"{name}/predicted_gbs").set(peak_bw / 1e9)
        metrics.gauge(f"{name}/efficiency").set(achieved / peak_bw)
    return achieved


def dense_model_flops(num_params: int, tokens: int) -> float:
    """MODEL_FLOPS = 6*N*D for a training step over D tokens."""
    return 6.0 * num_params * tokens


def moe_model_flops(active_params: int, tokens: int) -> float:
    """MoE uses activated parameters only: 6*N_active*D."""
    return 6.0 * active_params * tokens


def decode_model_flops(num_params_active: int, batch: int) -> float:
    """One decode step = forward only over `batch` new tokens: 2*N*B."""
    return 2.0 * num_params_active * batch
