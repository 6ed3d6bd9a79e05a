"""Post-SPMD HLO analysis: collective-byte accounting + roofline terms.

cost_analysis() gives per-device HLO FLOPs/bytes but NOT collective traffic;
we parse the compiled (post-partitioning) HLO text and sum, per collective
op, the bytes each device puts on the wire under a ring model:

  all-reduce       2 (g-1)/g * buffer      (reduce-scatter + all-gather ring)
  all-gather         (g-1)/g * output
  reduce-scatter     (g-1)/g * input
  all-to-all         (g-1)/g * buffer
  collective-permute          buffer

g = replica-group size parsed from the op's replica_groups / device list.

Roofline terms, with the peaks of the device the program runs on:
  compute   = FLOPs_per_device / peak FLOP/s             [s]
  memory    = bytes_per_device / peak HBM bytes/s        [s]
  collective= wire_bytes_per_device / per-link ICI bytes/s [s]
"""

from __future__ import annotations

import re
from dataclasses import dataclass, field

__all__ = ["CollectiveStats", "parse_collectives", "roofline_terms",
           "DevicePeaks", "PEAKS", "peaks_for"]


@dataclass(frozen=True)
class DevicePeaks:
    flops: float          # FLOP/s per chip (bf16 MXU)
    hbm_bw: float         # HBM bytes/s per chip
    ici_bw: float         # bytes/s per ICI link
    source: str


# Keyed by ``jax.Device.device_kind``.  A device missing here has no
# roofline: ``peaks_for`` raises rather than borrowing another chip's.
PEAKS = {
    "TPU v5 lite": DevicePeaks(
        flops=197e12, hbm_bw=819e9,
        ici_bw=1600e9 / 8 / 4,      # 1,600 Gbit/s over the chip's 4 links
        source='Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, '
               '819 GB/s HBM, 1,600 Gbit/s ICI per chip'),
}


def peaks_for(device_kind: str) -> DevicePeaks:
    """The published peaks of ``device_kind``; unknown kinds are an error."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; known: {sorted(PEAKS)}") from None

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16, "token": 0, "s4": 1, "u4": 1, "f8e4m3fn": 1, "f8e5m2": 1,
}

_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute")

_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([\d,]*)\]")


def _shape_bytes(shape_str: str) -> int:
    """Total bytes of a possibly-tuple HLO shape string."""
    total = 0
    for dt, dims in _SHAPE_RE.findall(shape_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                if d:
                    n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def _group_size(line: str, default: int) -> int:
    # replica_groups={{0,1,2,...},{...}} or [g,k]<=[...] iota form
    m = re.search(r"replica_groups=\{\{([^}]*)\}", line)
    if m:
        return len([x for x in m.group(1).split(",") if x.strip() != ""])
    m = re.search(r"replica_groups=\[(\d+),(\d+)\]", line)
    if m:
        return int(m.group(2))
    m = re.search(r"source_target_pairs=", line)
    if m:
        return 2
    return default


@dataclass
class CollectiveStats:
    ops: dict = field(default_factory=dict)        # kind -> count
    wire_bytes: float = 0.0                        # per-device bytes sent
    by_kind: dict = field(default_factory=dict)    # kind -> bytes
    details: list = field(default_factory=list)


def parse_collectives(hlo_text: str, n_devices: int) -> CollectiveStats:
    stats = CollectiveStats()
    for line in hlo_text.splitlines():
        s = line.strip()
        m = re.match(r"%?[\w.\-]+\s*=\s*(\([^)]*\)|[a-z0-9]+\[[\d,]*\][^ ]*)\s+([a-z\-]+)", s)
        if not m:
            continue
        kind = m.group(2)
        if kind.endswith("-done"):
            continue                      # async done: shape already counted
        if kind.endswith("-start"):
            kind = kind[: -len("-start")]
        if kind not in _COLLECTIVES:
            continue
        out_bytes = _shape_bytes(m.group(1))
        g = _group_size(s, n_devices)
        if g <= 1:
            continue
        frac = (g - 1) / g
        if kind == "all-reduce":
            wire = 2.0 * frac * out_bytes
        elif kind == "all-gather":
            wire = frac * out_bytes
        elif kind == "reduce-scatter":
            wire = frac * out_bytes * g   # input = output * g
        elif kind == "all-to-all":
            wire = frac * out_bytes
        else:                              # collective-permute
            wire = float(out_bytes)
        stats.ops[kind] = stats.ops.get(kind, 0) + 1
        stats.by_kind[kind] = stats.by_kind.get(kind, 0.0) + wire
        stats.wire_bytes += wire
        stats.details.append({"kind": kind, "bytes": out_bytes, "group": g,
                              "wire": wire})
    return stats


def roofline_terms(flops_per_dev: float, bytes_per_dev: float,
                   wire_bytes_per_dev: float, peaks: DevicePeaks) -> dict:
    t_c = flops_per_dev / peaks.flops
    t_m = bytes_per_dev / peaks.hbm_bw
    t_n = wire_bytes_per_dev / peaks.ici_bw
    dom = max(("compute", t_c), ("memory", t_m), ("collective", t_n),
              key=lambda kv: kv[1])
    total = max(t_c, t_m, t_n)
    return {
        "compute_s": t_c, "memory_s": t_m, "collective_s": t_n,
        "bound": dom[0],
        "step_time_lower_bound_s": total,
    }
