"""Published peaks of the chips the benchmark runs on, keyed by
``jax.Device.device_kind``.  A device that is not in the table has no
roofline: ``peaks_for`` raises rather than borrowing another chip's."""

from __future__ import annotations

from dataclasses import dataclass

__all__ = ["Peaks", "PEAKS", "peaks_for"]


@dataclass(frozen=True)
class Peaks:
    flops: float          # FLOP/s per chip
    hbm_bytes: float      # HBM bytes/s per chip
    hbm_capacity: float   # HBM bytes per chip
    source: str


# The v5e's only published compute peak is its bf16 MXU rate; no float32
# (VPU) rate is published.  For the float32 elementwise work of these HVPs
# the real compute ceiling is lower, so the compute side of a roofline
# taken from this table is optimistic: the share it gives is a lower bound
# of the share against a float32 ceiling, and a "memory" label may hide
# a compute bound.
PEAKS = {
    "TPU v5 lite": Peaks(
        flops=197e12, hbm_bytes=819e9, hbm_capacity=16e9,
        source='Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, '
               '16 GB HBM at 819 GB/s per chip'),
}


def peaks_for(device_kind: str) -> Peaks:
    """The published peaks of ``device_kind``; an unknown kind is an error."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; known: {sorted(PEAKS)}") from None
