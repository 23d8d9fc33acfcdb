"""Peaks of the chips the benchmark may run on, keyed by JAX's
``device_kind``. A copy of ``paddle_tpu/analysis/chips.py``'s row for the
installed chip, kept here so that no later PR can move the yardstick.

Source: Google Cloud documentation, "TPU v5e" system architecture page:
197 TFLOP/s bf16, 819 GB/s HBM2e, 16 GB HBM per chip. A device that is
not in the table is an error, never a default.
"""
from __future__ import annotations

from dataclasses import dataclass


@dataclass(frozen=True)
class Chip:
    name: str
    peak_flops: float        # bf16 FLOP/s
    hbm_bytes_per_s: float
    hbm_bytes: int
    source: str


_CHIPS = {
    ("v5 lite", "v5e"): Chip(
        "TPU v5e", 197e12, 819e9, 16 * 2**30,
        "Google Cloud documentation, TPU v5e"),
}


def chip_for(device_kind: str) -> Chip:
    kind = device_kind.lower()
    for needles, chip in _CHIPS.items():
        if any(n in kind for n in needles):
            return chip
    raise KeyError(
        f"device_kind {device_kind!r} is not in benchmark/chips.py: add its "
        "published peaks, with their source, before measuring on it")
