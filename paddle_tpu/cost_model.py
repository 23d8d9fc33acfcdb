"""paddle.cost_model parity — now a thin face over the tpucost pass.

DEPRECATED surface: the real cost machinery lives in
`paddle_tpu.analysis.hlo_cost` (PR 6) — a static fusion & HBM-traffic
inventory over compiled HLO with a roofline model and a ratcheted CI
gate (`tools/tpucost.py`). MIGRATING.md's cost-model mapping points
there; this module re-exports the new API so `paddle.cost_model.*`
keeps resolving, and keeps `CostModel.profile_measure` for reference
compatibility (the reference profiles a static Program per op; here
the unit of cost is the compiled PROGRAM).
"""
from __future__ import annotations

import time

import jax

__all__ = ["CostModel", "ChipSpec", "CHIP_SPECS", "DEFAULT_CHIP",
           "program_cost"]

# the new API, re-exported LAZILY (PEP 562): paddle_tpu/__init__.py
# imports this module eagerly, and pulling the whole analysis package
# in at `import paddle_tpu` time would couple every process to every
# analysis submodule importing cleanly
_REEXPORTS = ("ChipSpec", "CHIP_SPECS", "DEFAULT_CHIP", "program_cost")


def __getattr__(name):
    if name in _REEXPORTS:
        from .analysis import hlo_cost
        return getattr(hlo_cost, name)
    raise AttributeError(
        f"module {__name__!r} has no attribute {name!r}")


class CostModel:
    def profile_measure(self, fn, example_args=(), startup_program=None,
                        device="tpu", fetch_cost_list=("time",)):
        """Compile `fn(*example_args)` and return its cost dict: XLA's
        own analytical flops/bytes plus a measured wall time, extended
        with the tpucost static model's view of the same compiled HLO
        (hbm_bytes, arithmetic intensity, roofline seconds under the
        default chip spec — see analysis/hlo_cost.program_cost)."""
        if not callable(fn):
            raise TypeError(
                "CostModel.profile_measure expects a callable (the static "
                "Program path has no op-level IR here); pass a jittable "
                "function or a to_static Layer")
        raw = [a.value if hasattr(a, "value") else a for a in example_args]
        compiled = jax.jit(lambda *xs: fn(*xs)).lower(*raw).compile()
        cost = compiled.cost_analysis() or {}
        t0 = time.perf_counter()
        out = compiled(*raw)
        jax.block_until_ready(out)
        wall = time.perf_counter() - t0
        from .analysis.hlo_cost import program_cost
        inv = program_cost(compiled.as_text())
        return {
            "flops": float(cost.get("flops", 0.0)),
            "bytes accessed": float(cost.get("bytes accessed", 0.0)),
            "estimated_seconds": float(
                cost.get("optimal_seconds", 0.0) or 0.0),
            "measured_seconds": wall,
            "modeled_flops": inv["flops"],
            "modeled_hbm_bytes": inv["hbm_bytes"],
            "arithmetic_intensity": inv["arithmetic_intensity"],
            "roofline_seconds": inv["roofline_seconds"],
        }

    def static_cost_data(self):
        # reference-parity stub kept so callers get guidance, not a
        # bare AttributeError
        raise NotImplementedError(
            "static per-op cost tables describe the reference's "
            "op-level executor; program-level costs come from "
            "profile_measure, paddle_tpu.analysis.program_cost, or "
            "tools/tpucost.py (MIGRATING.md 'cost_model -> the "
            "tpucost inventory')")
