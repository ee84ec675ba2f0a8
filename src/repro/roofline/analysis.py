"""Three-term roofline model from the compiled dry-run artifact.

  compute term    = HLO_FLOPs_per_device / peak_FLOP/s_per_chip
  memory term     = HLO_bytes_per_device / HBM_BW_per_chip
  collective term = wire_bytes_per_device / ICI_BW_per_chip

``cost_analysis()`` on the SPMD-partitioned executable reports *per-device*
flops/bytes (verified empirically in tests), so dividing by per-chip peaks
directly matches the spec's "HLO_FLOPs / (chips x peak)" formula.
"""
from __future__ import annotations

from dataclasses import asdict, dataclass
from typing import Optional

from repro.configs.base import ModelConfig, ShapeConfig
from repro.core.device import chip

#: the device kind the dry run models (its meshes stand for v5e pods)
DRYRUN_KIND = "TPU v5 lite"


@dataclass
class Roofline:
    arch: str
    shape: str
    mesh: str
    n_devices: int
    flops_per_device: float
    hbm_bytes_per_device: float
    wire_bytes_per_device: float
    compute_s: float
    memory_s: float
    collective_s: float
    bottleneck: str
    model_flops: float           # 6*N*D (dense) or 6*N_active*D
    useful_flops_ratio: float    # MODEL_FLOPS/chips / HLO_FLOPs
    step_time_s: float           # max of the three terms
    roofline_fraction: float     # compute_s / step_time_s (MFU-like bound)

    def to_dict(self):
        return asdict(self)


def model_flops(c: ModelConfig, shape: ShapeConfig) -> float:
    """6*N*D for training; 2*N*D for inference (fwd only)."""
    n = c.active_param_count()
    d = shape.tokens_per_step
    mult = 6.0 if shape.kind == "train" else 2.0
    return mult * n * d


def analyze(c: ModelConfig, shape: ShapeConfig, *, mesh_name: str,
            n_devices: int, flops_per_device: float,
            hbm_bytes_per_device: float,
            wire_bytes_per_device: float) -> Roofline:
    spec = chip(DRYRUN_KIND)
    compute_s = flops_per_device / spec.peak_bf16_flops
    memory_s = hbm_bytes_per_device / spec.hbm_bw
    coll_s = wire_bytes_per_device / spec.ici_bw
    terms = {"compute": compute_s, "memory": memory_s, "collective": coll_s}
    bottleneck = max(terms, key=terms.get)
    mf = model_flops(c, shape)
    useful = (mf / n_devices) / max(flops_per_device, 1.0)
    step = max(terms.values())
    return Roofline(
        arch=c.name, shape=shape.name, mesh=mesh_name, n_devices=n_devices,
        flops_per_device=flops_per_device,
        hbm_bytes_per_device=hbm_bytes_per_device,
        wire_bytes_per_device=wire_bytes_per_device,
        compute_s=compute_s, memory_s=memory_s, collective_s=coll_s,
        bottleneck=bottleneck, model_flops=mf, useful_flops_ratio=useful,
        step_time_s=step,
        roofline_fraction=compute_s / step if step > 0 else 0.0)
