"""THE efficiency formula: model FLOPs / modeled bytes over measured
wall time, as a fraction of one chip's peak.

This is the ONE implementation the live gauges share. The benchmark
counts its cells' operations itself, from their shapes
(``benchmark/work.py``): ``step_mfu`` there is not this gauge.

* training: ``mfu(train_step_flops(params, tokens), seconds)`` — the
  standard nominal-MFU accounting (6 * params * tokens; remat recompute
  excluded, attention's O(L*H*S) term excluded when layer geometry is
  unknown). hapi's fit loop exports it per dispatch as the
  ``ptpu_train_mfu`` gauge (plus ``ptpu_train_step_seconds``).
* serving: the decode tick is bandwidth-bound (tpucost's anchor), so
  its efficiency is modeled HBM bytes moved per measured second as a
  fraction of the chip's bandwidth — ``model_bandwidth_eff(
  modeled_tick_bytes(kind, geometry), seconds)``. The engine exports
  it per tick as ``ptpu_engine_tick_model_eff`` (surfaced in
  ``stats()`` / ``/healthz``).

Numbers are chip-RELATIVE: the default chip is analysis/chips.py's
``DEFAULT_CHIP`` (v5lite, the chip the benchmark's cells run on),
overridable via ``PADDLE_TPU_EFF_CHIP``. On a CPU backend the gauges
still move (the arithmetic is honest) but read as tiny fractions of a
TPU's peak — they become meaningful when the TPU suite runs.

Module import is stdlib-only (the obs package contract);
analysis/chips.py is itself dependency-free, and the pytree helpers
import jax lazily at call time (callers are jax-land by definition).
"""
from __future__ import annotations

import os

__all__ = [
    "MFU_GAUGE", "STEP_SECONDS_GAUGE", "TICK_EFF_GAUGE",
    "chip_spec", "train_step_flops", "mfu", "model_bandwidth_eff",
    "modeled_tick_bytes", "tree_nbytes", "tree_nelems",
]

# the gauge names, importable so benches/docs/northstar cross-reference
# the exact exported series instead of retyping strings
MFU_GAUGE = "ptpu_train_mfu"
STEP_SECONDS_GAUGE = "ptpu_train_step_seconds"
TICK_EFF_GAUGE = "ptpu_engine_tick_model_eff"


def chip_spec(chip=None):
    """Resolve a chip for the efficiency denominator: a ChipSpec passes
    through untouched (the per-tick hot path — the engine resolves once
    at init and hands the spec back in), a name looks up
    analysis/chips.py's table, None reads ``PADDLE_TPU_EFF_CHIP``
    (default: the table's DEFAULT_CHIP)."""
    if chip is not None and not isinstance(chip, str):
        return chip
    from ..analysis.chips import CHIP_SPECS, DEFAULT_CHIP
    if chip is None:
        chip = os.environ.get("PADDLE_TPU_EFF_CHIP") or DEFAULT_CHIP
    return CHIP_SPECS[chip]


def train_step_flops(param_count: int, tokens: int) -> float:
    """Nominal model FLOPs for training ``tokens`` tokens: the standard
    6 * N * T (fwd 2NT + bwd 4NT) MFU accounting. Remat recompute is
    deliberately EXCLUDED (standard MFU counts useful math, not
    re-execution) and so is the attention O(L*H*S^2) term — a caller
    that knows its layer geometry adds it itself (the benchmark's
    count, benchmark/work.py, does); the live gauge stays the
    comparable lower bound."""
    return 6.0 * float(param_count) * float(tokens)


def mfu(model_flops: float, seconds: float, chip=None) -> float:
    """Model-FLOPs-utilization: useful FLOPs over what the chip could
    have done in the measured wall time."""
    if seconds <= 0:
        return 0.0
    return float(model_flops) / (float(seconds)
                                 * chip_spec(chip).peak_flops)


def model_bandwidth_eff(modeled_bytes: float, seconds: float,
                        chip=None) -> float:
    """Modeled HBM bytes moved per measured second, as a fraction of
    the chip's bandwidth — the efficiency notion for bandwidth-bound
    programs (the decode tick)."""
    if seconds <= 0:
        return 0.0
    return float(modeled_bytes) / (float(seconds)
                                   * chip_spec(chip).hbm_bandwidth)


def modeled_tick_bytes(kind: str, geometry: dict) -> int:
    """Analytic HBM bytes for one engine dispatch, by program kind —
    delegating to the ONE set of formulas in analysis/hlo_cost.py (the
    same bounds the tpucost anchors price):

      "decode"        dense slot tick   (tick_tokens, param, kv bytes)
      "decode_paged"  paged tick        (+ kv_view_bytes)
      "verify"        speculative verify-k dispatch (single pass)
    """
    from ..analysis import hlo_cost
    fn = {"decode": hlo_cost.analytic_decode_hbm_bytes,
          "decode_paged": hlo_cost.analytic_paged_decode_hbm_bytes,
          "verify": hlo_cost.analytic_verify_hbm_bytes}.get(kind)
    if fn is None:
        raise ValueError(f"unknown tick kind {kind!r} "
                         "(valid: decode, decode_paged, verify)")
    return fn(geometry)


def tree_nbytes(tree) -> int:
    """Total leaf bytes of a pytree (params/caches) — the geometry
    input every analytic bound consumes. Lazy jax import: callers
    (engine init, registry builders, benches) are jax-land already."""
    import jax
    import numpy as np
    total = 0
    for leaf in jax.tree_util.tree_leaves(tree):
        shape = tuple(getattr(leaf, "shape", ()) or ())
        n = 1
        for d in shape:
            n *= int(d)
        dt = getattr(leaf, "dtype", None)
        total += n * (np.dtype(dt).itemsize if dt is not None else 4)
    return total


def tree_nelems(tree) -> int:
    """Total leaf element count of a pytree (the parameter count the
    train MFU formula takes)."""
    import jax
    total = 0
    for leaf in jax.tree_util.tree_leaves(tree):
        n = 1
        for d in tuple(getattr(leaf, "shape", ()) or ()):
            n *= int(d)
        total += n
    return total
