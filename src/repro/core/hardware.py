"""Hardware specifications and the balance point rho = phi / beta.

TPU v5e is the deployment target.  The paper's three GPUs are kept as
presets so the reproduction can be cross-checked against the paper's own
numbers (Table 2 / Table 24).

``get_hardware`` is the spec a program runs against: on a TPU it is the
attached chip's, looked up by the ``device_kind`` JAX reports (a kind
missing from ``DEVICE_KINDS`` is an error, never a default); elsewhere
the named preset is the modelled target.  Analytic tables over other
targets index ``PRESETS`` by name.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Optional

import jax


@dataclass(frozen=True)
class HardwareSpec:
    name: str
    phi: float           # peak bf16/fp16 compute, FLOP/s
    beta: float          # peak HBM bandwidth, bytes/s
    ici: float = 0.0     # per-link interconnect bandwidth, bytes/s
    n_ici_links: int = 0
    hbm_bytes: float = 0.0
    vmem_bytes: float = 0.0
    mxu_dim: int = 128   # systolic array side (TPU); tensor-core tile (GPU)

    @property
    def rho(self) -> float:
        """Hardware balance point (FLOP per byte)."""
        return self.phi / self.beta


# --- deployment target -----------------------------------------------------
# Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 16 GB HBM at
# 819 GB/s, 1,600 Gbit/s of chip-to-chip interconnect (4 links).
TPU_V5E = HardwareSpec(
    name="tpu_v5e",
    phi=197e12,          # bf16 TFLOP/s per chip
    beta=819e9,          # HBM GB/s
    ici=50e9,            # ~GB/s per ICI link
    n_ici_links=4,
    hbm_bytes=16 * 2**30,
    vmem_bytes=128 * 2**20,
    mxu_dim=128,
)

# --- paper's GPUs (Table 2) — used to validate the reproduction -----------
H20 = HardwareSpec("h20", phi=148e12, beta=4.0e12)
A800 = HardwareSpec("a800", phi=312e12, beta=2.039e12)
H800 = HardwareSpec("h800", phi=989e12, beta=3.35e12)

PRESETS = {h.name: h for h in (TPU_V5E, H20, A800, H800)}

BYTES_BF16 = 2
BYTES_F32 = 4


# ``device_kind`` as JAX reports it -> the chip's published peaks
DEVICE_KINDS = {
    "TPU v5 lite": TPU_V5E,          # what JAX 0.9 reports for a v5e chip
}


def spec_for_device_kind(kind: str) -> HardwareSpec:
    try:
        return DEVICE_KINDS[kind]
    except KeyError:
        raise ValueError(
            f"no hardware spec for device_kind {kind!r} (known: "
            f"{sorted(DEVICE_KINDS)}); add its published peaks to "
            "repro.core.hardware.DEVICE_KINDS") from None


def get_hardware(name: Optional[str] = None) -> HardwareSpec:
    """The spec of the hardware this process runs against.

    On a TPU: the attached chip's spec; ``name``, when given, must name
    that same spec.  Elsewhere: the preset ``name`` (default the TPU v5e
    deployment target), as a modelled target."""
    if jax.default_backend() == "tpu":
        spec = spec_for_device_kind(jax.devices()[0].device_kind)
        if name is not None and name != spec.name:
            raise ValueError(
                f"hardware {name!r} requested, but the attached device is "
                f"{jax.devices()[0].device_kind!r} ({spec.name})")
        return spec
    return PRESETS[name or TPU_V5E.name]
