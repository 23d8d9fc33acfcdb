"""The one generator of inputs: it reads a traffic file's parameters and
draws from ``--seed``. The program receives only what this makes."""
from __future__ import annotations

import numpy as np


def token_batches(traffic: dict, vocab_size: int, seed: int):
    """Endless [batch, seq] int64 batches of uniform token ids, rows all
    different, the same for the same seed. Labels are the inputs (the
    model's own loss shifts them)."""
    rng = np.random.default_rng([int(seed), 0x70C5])
    shape = (int(traffic["batch"]), int(traffic["seq"]))
    while True:
        yield rng.integers(0, vocab_size, shape, dtype=np.int64)
