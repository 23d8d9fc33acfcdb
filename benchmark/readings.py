"""The readings that the limits of `correct` are set from, in one process
on the chip at the cell's own size (the benchmark's runs never call it):

    python3 benchmark/readings.py --workload <cell> --seeds 11,12,... \
        --controls 3 --out chiprun_out/readings.jsonl

For every seed: the program's first steps against the reference (the
lower readings). For the first ``--controls`` seeds also the reference in
the program's place in the nearest lower precision (``fp8``: the
control), and with half of the batch left out (a fault the cell can
have), each against the reference (the upper readings). One JSON line a
seed. Only drivers whose session has ``warm_up``/``readings`` and whose
reference has ``train_readings`` are read.
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
    ap.add_argument("--controls", type=int, default=3)
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
        t0 = time.perf_counter()
        ref = ref_mod.train_readings(arch, job, seed, batches)
        line = {"workload": args.workload, "seed": seed,
                "reference_s": time.perf_counter() - t0,
                "losses": got["losses"], "reference_losses": ref["losses"],
                "program": driver.compare(got, ref, run_mod.say)}
        if i < args.controls:
            line["control_fp8"] = driver.compare(
                ref_mod.train_readings(arch, job, seed, batches,
                                       precision="fp8"), ref)
            line["fault_half_batch"] = driver.compare(
                ref_mod.train_readings(arch, job, seed, batches,
                                       half_batch=True), ref)
        with open(args.out, "a") as f:
            f.write(json.dumps(line) + "\n")
        run_mod.say(json.dumps(line))
    return 0


if __name__ == "__main__":
    sys.exit(main())
