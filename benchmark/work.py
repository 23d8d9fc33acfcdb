"""Operations and bytes that the algorithm needs, from shapes alone.

Every count here is of *needed* work: causal attention is the lower
triangle (half of the square), a recomputed forward pass is not counted,
and neither is the attention backward's second look at the scores. A
share of a peak built on these counts can therefore not pass 100%.
"""
from __future__ import annotations


def gpt_matmul_params(arch: dict) -> dict:
    """Weights that multiply every token, by group (no embeddings'
    look-ups, no biases, no norms)."""
    h, layers = int(arch["hidden_size"]), int(arch["num_layers"])
    f = int(arch["ffn_mult"]) * h
    return {"blocks": layers * (3 * h * h + h * h + 2 * h * f),
            "head": int(arch["vocab_size"]) * h}


def gpt_train_flops(arch: dict, batch: int, seq: int) -> dict:
    """Matmul operations of one training step (forward and backward) on
    ``batch`` rows of ``seq`` tokens: 6 per weight and token (2 forward,
    4 backward); the head sees seq-1 positions of each row; attention is
    2 products forward and 4 backward of S x S x H a layer and row,
    halved for the causal mask."""
    p = gpt_matmul_params(arch)
    h, layers = int(arch["hidden_size"]), int(arch["num_layers"])
    blocks = 6 * p["blocks"] * batch * seq
    head = 6 * p["head"] * batch * (seq - 1)
    attention = layers * batch * attention_flops(seq, h, backward=True)
    attention += layers * batch * attention_flops(seq, h, backward=False)
    return {"blocks": blocks, "head": head, "attention": attention,
            "total": blocks + head + attention}


def attention_flops(seq: int, hidden: int, backward: bool) -> int:
    """One row, all heads, causal: forward QK^T and PV; backward dV, dP,
    dQ, dK. Each product is 2*S*S*H operations over the full square."""
    products = 4 if backward else 2
    return products * 2 * seq * seq * hidden // 2


def attention_bytes(seq: int, hidden: int, backward: bool,
                    itemsize: int = 2) -> int:
    """One row, all heads: forward reads q, k, v and writes o; backward
    reads q, k, v, o, do and writes dq, dk, dv. The per-row softmax
    statistics are left out (a 1/head_dim part)."""
    tensors = 8 if backward else 4
    return tensors * seq * hidden * itemsize


def roofline_seconds(flops: float, bytes_: float, chip) -> tuple:
    """Least time the chip could take, and which peak sets it."""
    t_flops = flops / chip.peak_flops
    t_bytes = bytes_ / chip.hbm_bytes_per_s
    return ((t_flops, "compute") if t_flops >= t_bytes
            else (t_bytes, "memory"))
