"""``benchmark/readings.py`` for a family whose reference plants more
faults than half a batch (``reference.FAULTS``): the readings that the
limits of `correct` are set from, in one process on the chip at the cell's
own size (the benchmark's runs never call it):

    python3 benchmark/readings_afmoe.py --workload <cell> --seeds 11,12,... \
        --controls 2 --faults 1 [--only norm_sum_no_grad,...] \
        --out chiprun_out/readings.jsonl

For every seed: the program's first steps against the reference (the
lower readings). For the first ``--controls`` seeds also the reference in
the program's place in the nearest lower precision (``fp8``); for the
first ``--faults`` seeds the reference with half of the batch left out
and with each of the family's faults planted (``--only``: just the ones
named, and no half batch unless ``half_batch`` is among them); each
against the reference (the upper readings). One JSON line a seed.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
import types

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

from benchmark import run as run_mod            # noqa: E402
from benchmark import traffic as traffic_mod    # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--controls", type=int, default=2)
    ap.add_argument("--faults", type=int, default=1)
    ap.add_argument("--only", default="")
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)

    bench = run_mod.load_json("BENCHMARK.json")
    cell = run_mod.find_cell(bench, args.workload)
    devices = run_mod.require_devices(cell["chips"])
    import jax
    run_mod.keep_compile_cache(jax)
    driver = run_mod.load_module("drivers", cell["traffic"]["driver"])
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)

    for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
        ctx = types.SimpleNamespace(
            cell=cell, seed=seed, seconds=0.0, trace=False,
            t0=time.perf_counter(), trace_dir=None, say=run_mod.say,
            devices=devices, chip=None)
        session = driver.Session(ctx).warm_up()
        got, arch, job = session.readings, session.arch, session.job
        ref_mod = session.reference
        session.release()
        fresh = traffic_mod.token_batches(cell["traffic"],
                                          arch["vocab_size"], seed)
        batches = [next(fresh)
                   for _ in range(int(cell["traffic"]["compare_steps"]))]

        def follow(**kw):
            t0 = time.perf_counter()
            out = ref_mod.train_readings(arch, job, seed, batches, **kw)
            run_mod.say(f"reference {kw or ''}: "
                        f"{time.perf_counter() - t0:.1f} s")
            return out
        ref = follow()
        line = {"workload": args.workload, "seed": seed,
                "losses": got["losses"], "reference_losses": ref["losses"],
                "program": driver.compare(got, ref, run_mod.say)}
        if i < args.controls:
            line["control_fp8"] = driver.compare(follow(precision="fp8"),
                                                 ref)
        if i < args.faults:
            only = [f for f in args.only.split(",") if f]
            if not only or "half_batch" in only:
                line["fault_half_batch"] = driver.compare(
                    follow(half_batch=True), ref)
            for fault in ref_mod.FAULTS:
                if not only or fault in only:
                    line["fault_" + fault] = driver.compare(
                        follow(fault=fault), ref)
        with open(args.out, "a") as f:
            f.write(json.dumps(line) + "\n")
        run_mod.say(json.dumps(line))
    return 0


if __name__ == "__main__":
    sys.exit(main())
