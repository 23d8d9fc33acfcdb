"""Operations and bytes that the afmoe family's algorithm needs, from
shapes and from the routing that really happened (``benchmark/work.py``
for the GPT family). Needed work only: 6 operations a weight and token
for what every token passes (2 forward, 4 backward); the routed experts
by the assignments that landed on experts held here, never the padded
rows; attention as the band a window layer needs and the triangle a full
layer needs; a recomputed forward pass is not counted. No share built on
these counts can pass 100%.
"""
from __future__ import annotations

from benchmark.reference.afmoe import layer_kinds, sizes
from benchmark.work import roofline_seconds

__all__ = ["attended_pairs", "attention_flops", "attention_bytes",
           "attention_seconds", "expert_flops", "expert_bytes",
           "expert_seconds", "afmoe_train_flops", "roofline_seconds"]


def attended_pairs(seq: int, window) -> int:
    """(query, key) pairs one head's row needs: the causal triangle, or
    the band 0 <= i - j < window of it."""
    full = seq * (seq + 1) // 2
    if window is None or window >= seq:
        return full
    short = seq - window            # queries that see a whole window
    return full - short * (short + 1) // 2


def attention_flops(seq: int, heads: int, head_dim: int, window,
                    backward: bool) -> int:
    """One row, all query heads: forward QK^T and PV; backward dV, dP,
    dQ, dK; each 2 * head_dim operations a pair."""
    products = 4 if backward else 2
    return products * 2 * head_dim * heads * attended_pairs(seq, window)


def attention_bytes(seq: int, heads: int, kv_heads: int, head_dim: int,
                    backward: bool, itemsize: int = 2) -> int:
    """One row: forward reads q, k, v and writes o; backward reads q, k,
    v, o, do and writes dq, dk, dv; k, v, dk, dv at the key/value heads."""
    q_like, kv_like = (5, 4) if backward else (2, 2)
    return (q_like * heads + kv_like * kv_heads) * seq * head_dim * itemsize


def attention_seconds(arch: dict, batch: int, seq: int, chip) -> tuple:
    """Least time for one step's attention (every layer, forward and
    backward once), and which peak binds each kind and pass."""
    z = sizes(arch)
    total, bound = 0.0, {}
    for kind in layer_kinds(arch):
        window = int(arch["sliding_window"]) \
            if kind == "sliding_attention" else None
        for backward in (False, True):
            t, by = roofline_seconds(
                attention_flops(seq, z["nh"], z["hd"], window, backward),
                attention_bytes(seq, z["nh"], z["nkv"], z["hd"], backward),
                chip)
            total += batch * t
            bound[f"{kind}.{'backward' if backward else 'forward'}"] = by
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
    """Least time for one step's grouped products (every expert layer,
    forward and backward once) at the assignments that landed."""
    total, bound = 0.0, {}
    for landed in landed_by_layer:
        for backward in (False, True):
            t, by = roofline_seconds(expert_flops(arch, landed, backward),
                                     expert_bytes(arch, landed, backward),
                                     chip)
            total += t
            bound["backward" if backward else "forward"] = by
    return total, bound


def afmoe_matmul_params(arch: dict) -> dict:
    """Weights that multiply every token, by group, and one routed
    expert's (no embedding look-up, no norms)."""
    z = sizes(arch)
    attn = z["H"] * (2 * z["Q"] + 2 * z["KV"]) + z["Q"] * z["H"]
    return {"projections": z["L"] * attn,
            "dense_mlp": z["Ld"] * 3 * z["H"] * z["F"],
            "shared_expert": z["Lm"] * 3 * z["H"] * z["Fs"],
            "router": z["Lm"] * z["H"] * z["E"],
            "head": z["H"] * z["V"],
            "one_expert": 3 * z["H"] * z["Fe"]}


def afmoe_train_flops(arch: dict, batch: int, seq: int,
                      landed_by_layer=None) -> dict:
    """Matmul operations one training step needs, by group. The routed
    experts count the assignments that landed on held experts
    (``landed_by_layer``, one number an expert layer; an even routing
    where it is not given). The head sees seq - 1 positions a row."""
    z = sizes(arch)
    p = afmoe_matmul_params(arch)
    tokens = batch * seq
    if landed_by_layer is None:
        even = tokens * int(arch["num_experts_per_tok"]) * z["held"] / z["E"]
        landed_by_layer = [even] * z["Lm"]
    out = {g: 6 * p[g] * tokens for g in
           ("projections", "dense_mlp", "shared_expert", "router")}
    out["head"] = 6 * p["head"] * batch * (seq - 1)
    out["routed_experts"] = 6 * p["one_expert"] * float(sum(landed_by_layer))
    out["window_attention"] = out["full_attention"] = 0
    for kind in layer_kinds(arch):
        sliding = kind == "sliding_attention"
        window = int(arch["sliding_window"]) if sliding else None
        out["window_attention" if sliding else "full_attention"] += batch * (
            attention_flops(seq, z["nh"], z["hd"], window, False)
            + attention_flops(seq, z["nh"], z["hd"], window, True))
    out["total"] = sum(out.values())
    return out
