"""Profile one bench training step and print the op-time breakdown.

VERDICT r2 item 2 infrastructure: run the GPT bench TrainStep under the
XLA profiler, parse the xplane trace, and report where the step time
goes (matmul vs attention vs collectives vs elementwise) — the input to
"attack the largest non-matmul slice".

The trace parsing lives in paddle_tpu/analysis/runtime_profile.py (the
tpuprof pass — ISSUE 14 folded the parser that used to be private here
into the ONE implementation tools/tpuprof.py gates CI with); this tool
keeps its CLI face, the category table, and the terminal JSON contract
as a thin wrapper over it.

Run on TPU:  python tools/profile_step.py
CPU smoke:   JAX_PLATFORMS=cpu python tools/profile_step.py --smoke
Prints a category table + top ops, and one JSON summary line last.
"""
import argparse
import collections
import json
import sys
import tempfile
import time

import numpy as np

sys.path.insert(0, __file__.rsplit("/", 2)[0])


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true", help="tiny model, CPU")
    ap.add_argument("--steps", type=int, default=5)
    ap.add_argument("--top", type=int, default=15)
    args = ap.parse_args()

    import jax

    import paddle_tpu as paddle
    from paddle_tpu.analysis.runtime_profile import (category_of,
                                                     device_op_times,
                                                     load_trace_events)
    from paddle_tpu.jit import TrainStep
    from paddle_tpu.models import GPTConfig, GPTForCausalLM

    if args.smoke:
        seq, batch = 128, 2
        cfg = GPTConfig(vocab_size=1024, hidden_size=128, num_layers=2,
                        num_heads=4, max_seq_len=seq)
    else:
        seq, batch = 1024, 8
        cfg = GPTConfig(vocab_size=50304, hidden_size=768, num_layers=12,
                        num_heads=12, max_seq_len=seq)

    paddle.seed(0)
    model = GPTForCausalLM(cfg)
    if not args.smoke:
        model.bfloat16()
    opt = paddle.optimizer.AdamW(learning_rate=1e-4,
                                 multi_precision=not args.smoke,
                                 parameters=model.parameters())
    step = TrainStep(model, GPTForCausalLM.loss_fn, opt)
    ids = paddle.to_tensor(np.random.RandomState(0).randint(
        0, cfg.vocab_size, (batch, seq)).astype("int64"))

    for _ in range(3):           # compile + warm
        loss = step(ids, ids)
    float(loss)

    logdir = tempfile.mkdtemp(prefix="paddle_tpu_profile_")
    jax.profiler.start_trace(logdir)
    t0 = time.perf_counter()          # bare steps only: trace start/stop
    for _ in range(args.steps):       # serialization must not pollute
        loss = step(ids, ids)         # the wall number vs bench.py
    float(loss)
    wall = (time.perf_counter() - t0) / args.steps
    jax.profiler.stop_trace()

    prof = device_op_times(load_trace_events(logdir))
    per_op = collections.Counter(prof.per_op)
    op_cat, had_device = prof.op_category, prof.had_device
    total_us = sum(per_op.values())
    cats = collections.Counter()
    for name, us in per_op.items():
        cats[category_of(name, op_cat)] += us

    if had_device:
        print(f"\n== category breakdown ({args.steps} steps, device "
              f"planes, total {total_us/1e3:.2f} ms) ==")
        for cat, us in cats.most_common():
            print(f"  {cat:<28} {us/1e3:9.2f} ms  "
                  f"{100*us/max(total_us, 1e-9):5.1f}%")
        print(f"\n== top {args.top} ops ==")
        for name, us in per_op.most_common(args.top):
            print(f"  {name[:64]:<64} {us/1e3:9.2f} ms "
                  f"[{category_of(name, op_cat)}]")
    else:
        print("\n(no device plane in trace — CPU backend records host "
              "events only; run on TPU for the op breakdown)")

    biggest_non_matmul = next(
        (c for c, _ in cats.most_common()
         if not any(k in c.lower()
                    for k in ("matmul", "conv", "fusion", "dot"))), "n/a")
    print()
    print(json.dumps({
        "metric": "gpt_step_profile",
        "ms_per_step_wall": round(wall * 1e3, 2),
        "device_total_ms": round(total_us / 1e3, 2),
        "had_device_plane": had_device,
        "categories_ms": {c: round(us / 1e3, 2)
                          for c, us in cats.most_common()},
        "biggest_non_matmul_category": biggest_non_matmul,
        "logdir": logdir,
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
