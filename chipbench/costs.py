"""Peaks of the chip, and the work the window's requests needed.

A family's ``request_work`` (``families/<family>.py``) fills ``Work``.
Operations and bytes are what the algorithm needs for the call, counted
from shapes and positions: padding, parked rows and recomputation are
not counted, so a roofline share also shows the work a kernel wastes.
"""
from __future__ import annotations

from dataclasses import dataclass, field

# Published peaks of one chip, keyed by ``jax.Device.device_kind``.
# Source: Google Cloud documentation, "TPU v5e" (system architecture):
# 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s per chip.
PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "int8_ops": 393e12,
                    "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9},
}


def peaks(device_kind: str) -> dict:
    """The peaks of ``device_kind``; an unknown device is an error."""
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; known: {sorted(PEAKS)}")
    return PEAKS[device_kind]


@dataclass
class Work:
    """What the window's requests needed, summed."""
    prefill_flops: float = 0.0
    decode_flops: float = 0.0
    # least seconds of each kernel, keyed like the family's ``KERNELS``
    kernel: dict = field(default_factory=dict)
