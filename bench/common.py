"""What the drivers share: seeds, the checked numbers, device memory."""

from __future__ import annotations

from dataclasses import dataclass, field

import jax
import numpy as np

__all__ = ["device_key", "host_rng", "Checked", "memory_peak_bytes"]


def _words(seed: int) -> tuple:
    s = int(seed) % 2 ** 64
    return s % 2 ** 32, s // 2 ** 32


def device_key(seed: int):
    """A JAX PRNG key from any whole number (wider than 32 bits too)."""
    lo, hi = _words(seed)
    return jax.random.fold_in(jax.random.key(lo), hi)


def host_rng(seed: int, stream: int) -> np.random.Generator:
    """A NumPy generator for one named use of the seed."""
    return np.random.default_rng([*_words(seed), stream])


@dataclass
class Checked:
    """The numbers compared, each beside its limit, and the counts."""
    attempted: int = 0
    failed: int = 0
    numbers: dict = field(default_factory=dict)   # name -> (value, limit)

    def add(self, name: str, value: float, limit: float) -> None:
        self.numbers[name] = (float(value), float(limit))

    @property
    def correct(self) -> bool:
        return (self.attempted > 0 and self.failed == 0 and all(
            v <= lim for v, lim in self.numbers.values()))


def memory_peak_bytes(devices) -> int:
    """The peak bytes in use on the fullest of ``devices``."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks)) if peaks else 0
