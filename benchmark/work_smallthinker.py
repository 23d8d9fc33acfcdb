"""Operations and bytes that the SmallThinker family's algorithm needs, from
shapes and from the routing that really happened (``benchmark/work.py`` for
the GPT family, ``benchmark/work_afmoe.py`` for afmoe, whose counts of a
band, a triangle and their bytes at grouped heads these are). Needed work
only: 6 operations a weight and token for what every token passes (2
forward, 4 backward); the held experts by the assignments that landed on
them, never the padded rows; attention as the band a window layer needs and
the triangle a full layer needs, k and v bytes at the key/value heads; a
recomputed forward pass is not counted. No share built on these counts can
pass 100%.
"""
from __future__ import annotations

from benchmark.reference.smallthinker import layer_layouts, sizes
from benchmark.work import roofline_seconds
from benchmark.work_afmoe import attention_bytes, attention_flops

__all__ = ["attention_seconds", "expert_flops", "expert_bytes",
           "expert_seconds", "matmul_params", "train_flops"]


def _windows(arch: dict) -> list:
    """The keys a query sees, a layer that is run: the window, or None."""
    return [int(arch["sliding_window_size"]) if sliding else None
            for _, sliding in layer_layouts(arch)]


def attention_seconds(arch: dict, batch: int, seq: int, chip) -> tuple:
    """Least time for one step's attention (every layer, forward and
    backward once), and which peak binds each kind and pass."""
    z = sizes(arch)
    total, bound = 0.0, {}
    for window in _windows(arch):
        for backward in (False, True):
            t, by = roofline_seconds(
                attention_flops(seq, z["nh"], z["hd"], window, backward),
                attention_bytes(seq, z["nh"], z["nkv"], z["hd"], backward),
                chip)
            total += batch * t
            bound[("full" if window is None else "window") + "."
                  + ("backward" if backward else "forward")] = by
    return total, bound


def expert_flops(arch: dict, landed: float, backward: bool) -> float:
    """The held experts' three products for ``landed`` assignments:
    forward 2 operations a weight and assignment, backward 4."""
    z = sizes(arch)
    return (4 if backward else 2) * 3 * z["H"] * z["Fe"] * landed


def expert_bytes(arch: dict, landed: float, backward: bool,
                 itemsize: int = 2) -> float:
    """Forward: the held weights read once, the rows in, the two hidden
    rows out and in again, the rows out. Backward: the weights read once
    and their gradients written once, and twice the forward's rows."""
    z = sizes(arch)
    weights = 3 * z["held"] * z["H"] * z["Fe"]
    rows = landed * (2 * z["H"] + 4 * z["Fe"])
    return itemsize * ((2 * weights + 2 * rows) if backward
                       else (weights + rows))


def expert_seconds(arch: dict, landed_by_layer, chip) -> tuple:
    """Least time for one step's grouped products (every layer, forward
    and backward once) at the assignments that landed."""
    total, bound = 0.0, {}
    for landed in landed_by_layer:
        for backward in (False, True):
            t, by = roofline_seconds(expert_flops(arch, landed, backward),
                                     expert_bytes(arch, landed, backward),
                                     chip)
            total += t
            bound["backward" if backward else "forward"] = by
    return total, bound


def matmul_params(arch: dict) -> dict:
    """Weights that multiply every token, by group, and one expert's (no
    embedding look-up, no norms)."""
    z = sizes(arch)
    return {"projections": z["L"] * (2 * z["H"] * z["Q"]
                                     + 2 * z["H"] * z["KV"]),
            "router": z["L"] * z["H"] * z["E"],
            "head": z["H"] * z["V"],
            "one_expert": 3 * z["H"] * z["Fe"]}


def train_flops(arch: dict, batch: int, seq: int,
                landed_by_layer=None) -> dict:
    """Matmul operations one training step needs, by group. The experts
    count the assignments that landed on held experts
    (``landed_by_layer``, one number a layer; an even routing where it is
    not given). The head sees seq - 1 positions a row."""
    z = sizes(arch)
    p = matmul_params(arch)
    tokens = batch * seq
    if landed_by_layer is None:
        even = tokens * int(arch["moe_num_active_primary_experts"]) \
            * z["held"] / z["E"]
        landed_by_layer = [even] * z["L"]
    out = {g: 6 * p[g] * tokens for g in ("projections", "router")}
    out["head"] = 6 * p["head"] * batch * (seq - 1)
    out["routed_experts"] = 6 * p["one_expert"] * float(sum(landed_by_layer))
    out["window_attention"] = out["full_attention"] = 0
    for window in _windows(arch):
        out["full_attention" if window is None else "window_attention"] += \
            batch * sum(attention_flops(seq, z["nh"], z["hd"], window, bwd)
                        for bwd in (False, True))
    out["total"] = sum(out.values())
    return out
