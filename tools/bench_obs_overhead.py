#!/usr/bin/env python
"""Decode-tick observability overhead gate (ISSUE 8).

The obs instrumentation on the engine's hot path is a handful of
perf_counter reads, histogram observes, and one bounded ring append
per tick — microseconds against a decode program that takes
milliseconds. This bench MEASURES that claim and gates on it: two
engines over the same weights, one built with obs enabled and one
disabled, serve the identical full-occupancy decode workload; the
per-tick wall time is compared.

Jitter control on this 1-core host (the bench_train_loop.py recipe,
tightened): host noise here is CORRELATED over seconds (frequency /
contention phases), so per-side min-of-N still compares one side's
lucky second against the other's unlucky one. Instead each on-round is
PAIRED with the off-round measured back-to-back inside the same
~0.3 s window — slow drift hits both halves of a pair equally — and
the reported overhead is the MEDIAN of the per-pair ratios (robust to
a descheduled outlier pair).

GATE: enabled/disabled per-tick ratio <= 1.02 (2%). Exit 1 past it.
Prints ONE terminal JSON record (one terminal JSON record).

CPU run: JAX_PLATFORMS=cpu python tools/bench_obs_overhead.py
"""
from __future__ import annotations

import argparse
import json
import sys
import time

import numpy as np

sys.path.insert(0, __file__.rsplit("/", 2)[0])

GATE_RATIO = 1.02


def _run_round(engine, prompts, max_new: int) -> float:
    """Fill every slot, decode to completion; per-tick wall ms."""
    ticks0 = engine.ticks
    futs = [engine.submit(p, max_new_tokens=max_new, seed=0)
            for p in prompts]
    t0 = time.perf_counter()
    for f in futs:
        f.result(timeout=600)
    wall = time.perf_counter() - t0
    ticks = engine.ticks - ticks0
    return wall * 1e3 / max(ticks, 1)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--rounds", type=int, default=7,
                    help="back-to-back on/off pairs (median ratio)")
    ap.add_argument("--max-new", type=int, default=384,
                    help="decode length per request (rounds must be "
                         "long enough — ~250ms — to sit above this "
                         "host's per-measurement noise floor)")
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--tick-tokens", type=int, default=4,
                    help="micro-steps per tick (production default is "
                         "8; obs cost is per TICK, so a 1-token tick "
                         "would gate the constant ~10us against an "
                         "artificially light program)")
    args = ap.parse_args()

    import paddle_tpu as paddle
    from paddle_tpu import obs
    from paddle_tpu.inference.engine import ContinuousBatchingEngine
    from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM

    paddle.seed(0)
    # serving-representative geometry, not an adversarial micro-model:
    # the gate bounds obs's FIXED per-tick cost relative to a tick that
    # actually runs a few transformer layers (a sub-ms toy tick would
    # report the constant ~10us as if it were model-relative)
    model = GPTForCausalLM(GPTConfig(
        vocab_size=256, hidden_size=64, num_layers=2, num_heads=4,
        max_seq_len=args.max_new + 32))
    model.eval()
    rng = np.random.RandomState(0)
    prompts = [rng.randint(0, 100, (6,)).astype("int64")
               for _ in range(args.slots)]
    kw = dict(slots=args.slots, max_len=args.max_new + 16,
              cache_dtype="float32", prefill_buckets=(8,),
              tick_tokens=args.tick_tokens, max_queue=args.slots * 2)

    # the obs flag is snapshotted at engine construction — build one
    # engine per side, restore the env-driven default after
    obs.set_enabled(True)
    eng_on = ContinuousBatchingEngine(model, **kw)
    obs.set_enabled(False)
    eng_off = ContinuousBatchingEngine(model, **kw)
    obs.set_enabled(None)

    try:
        # warm both sides (compile + first-touch) before measuring
        _run_round(eng_on, prompts, args.max_new)
        _run_round(eng_off, prompts, args.max_new)
        on_ms, off_ms, ratios = [], [], []
        for i in range(args.rounds):
            # alternate which side leads inside the pair so any
            # cache/freq asymmetry of "going first" cancels too
            if i % 2 == 0:
                on = _run_round(eng_on, prompts, args.max_new)
                off = _run_round(eng_off, prompts, args.max_new)
            else:
                off = _run_round(eng_off, prompts, args.max_new)
                on = _run_round(eng_on, prompts, args.max_new)
            on_ms.append(on)
            off_ms.append(off)
            ratios.append(on / off)
        ratio = float(np.median(ratios))
        rec = {
            "metric": "obs_tick_overhead",
            "value": round(ratio, 4),
            "unit": "enabled_over_disabled_tick_time",
            "pair_ratios": [round(r, 4) for r in ratios],
            "tick_ms_obs_on": round(min(on_ms), 4),
            "tick_ms_obs_off": round(min(off_ms), 4),
            "rounds": args.rounds,
            "tick_tokens": args.tick_tokens,
            "slots": args.slots,
            "gate_ratio": GATE_RATIO,
            "gate": "pass" if ratio <= GATE_RATIO else "FAIL",
        }
        print(json.dumps(rec))
        return 0 if ratio <= GATE_RATIO else 1
    finally:
        eng_on.stop()
        eng_off.stop()


if __name__ == "__main__":
    sys.exit(main())
