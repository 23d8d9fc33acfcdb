"""Accelerator roofline constants — the ONE table.

Deliberately dependency-free (stdlib dataclasses only) so a tool that
needs three numbers on a machine without jax can load this file
standalone via importlib without paying (or requiring) the full
paddle_tpu/jax import. Everything else imports it through
`paddle_tpu.analysis.hlo_cost`, which re-exports the table for the
tpucost roofline.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, Tuple

__all__ = ["ChipSpec", "CHIP_SPECS", "DEFAULT_CHIP",
           "chip_for_device_kind"]


@dataclass(frozen=True)
class ChipSpec:
    """Roofline constants for one accelerator generation (public specs).
    `peak_flops` is bf16; `hbm_bandwidth` is bytes/s."""
    name: str
    peak_flops: float
    hbm_bandwidth: float
    hbm_capacity: float
    ici_gbps: float = 0.0    # aggregate inter-chip Gbit/s (0 = n/a)
    # lower-cased substrings of jax's ``device.device_kind`` naming it
    device_kinds: Tuple[str, ...] = ()


CHIP_SPECS: Dict[str, ChipSpec] = {
    # v5-lite (v5e): the installed chip (Google Cloud "TPU v5e" docs)
    "v5lite": ChipSpec("v5lite", peak_flops=197e12, hbm_bandwidth=819e9,
                       hbm_capacity=16 * 2**30, ici_gbps=1600,
                       device_kinds=("v5 lite", "v5e")),
    # v5p: the pod chip (no cell runs on it)
    "v5p": ChipSpec("v5p", peak_flops=459e12, hbm_bandwidth=2765e9,
                    hbm_capacity=95 * 2**30, ici_gbps=4800,
                    device_kinds=("v5p",)),
}
# the target the STATIC cost model (tpucost, northstar) prices when no
# device exists; a measurement on a live device never defaults — it
# resolves its device through chip_for_device_kind, which raises
DEFAULT_CHIP = "v5lite"


def chip_for_device_kind(kind: str) -> ChipSpec:
    """The table row for a live device's ``device_kind``. A device that
    is not in the table is an error, never a default: an MFU computed
    against an assumed peak is a wrong number with a right name."""
    k = kind.lower()
    for spec in CHIP_SPECS.values():
        if any(sub in k for sub in spec.device_kinds):
            return spec
    raise KeyError(
        f"device_kind {kind!r} is not in analysis/chips.py CHIP_SPECS — "
        "add its published peaks (with source) before measuring on it")
