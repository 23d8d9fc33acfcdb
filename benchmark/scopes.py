"""Where a cell's device time goes, by the program's own scopes: the table
of ``PERF.md`` section 5, from one traced window of the cell's own driver
(the benchmark's runs never call this; it compares nothing and runs no
reference):

    python3 benchmark/scopes.py --workload <cell> --seed <n> \
        --out chiprun_out/scopes_<cell>_<n>.json

Written: the window's busy seconds and steps; ``by_scope``
(``analysis.runtime_profile.by_scope``: seconds and share of busy time a
region and pass, per-instruction self time joined by name with
``TrainStep.op_scopes()`` as the driver hands it over in
``obs["op_scopes"]``; a driver that hands none over reads ``unscoped``
throughout: its region is the innermost name on a path, which may be an
operation's own, ``linear`` or ``moe_experts``); ``under`` (seconds under
every name on the paths, by pass, ``"experts|recompute"``: a layer's row
of section 5 is its name's seconds less its children's); ``top_ops`` (the
80 longest instructions with the tail of their paths) and ``kernels``
(the custom calls' operands).
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import time
import types

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

from benchmark import run as run_mod            # noqa: E402


def table(trace, op_scopes: dict) -> dict:
    from benchmark import trace as trace_mod
    from paddle_tpu.analysis import runtime_profile as rp
    lo, hi = trace.window()
    events = [(n, max(s, lo), min(e, hi)) for n, s, e in trace.chips[0].ops
              if min(e, hi) > max(s, lo)]
    self_s = {n: ns / 1e9 for n, ns in trace_mod.self_times(events).items()}
    ops, under = [], {}
    for n, s in self_s.items():
        path = op_scopes.get(rp.normalize_kernel_name(n), "")
        ops.append((s, n, path[-160:]))
        if path:
            got = rp.read_scope(path, n)
            for name in set(got["scope"].split("/")):
                key = f"{name}|{got['pass']}"
                under[key] = under.get(key, 0.0) + s
    ops.sort(reverse=True)
    return {"busy_s": trace.busy_s(), "window_s": trace.window_s(),
            "by_scope": rp.by_scope(self_s, op_scopes),
            "under": dict(sorted(under.items())), "top_ops": ops[:80],
            "kernels": {n: h[:400] for n, h in trace.kernels().items()}}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)

    bench = run_mod.load_json("BENCHMARK.json")
    cell = run_mod.find_cell(bench, args.workload)
    devices = run_mod.require_devices(cell["chips"])
    import jax
    from benchmark import chips, trace as trace_mod
    run_mod.keep_compile_cache(jax)
    trace_dir = os.path.join(run_mod.ROOT, ".cache", "benchmark_trace",
                             args.workload + ".scopes")
    shutil.rmtree(trace_dir, ignore_errors=True)
    ctx = types.SimpleNamespace(
        cell=cell, seed=args.seed, seconds=args.seconds, trace=True,
        t0=time.perf_counter(), trace_dir=trace_dir, say=run_mod.say,
        devices=devices, chip=chips.chip_for(devices[0].device_kind))
    driver = run_mod.load_module("drivers", cell["traffic"]["driver"])
    obs = driver.start(ctx).obs
    out = table(trace_mod.load(trace_dir), obs.get("op_scopes") or {})
    shutil.rmtree(trace_dir, ignore_errors=True)
    out.update(workload=args.workload, seed=args.seed, steps=obs.get("steps"),
               landed_by_layer=obs.get("landed_by_layer"))
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(out, f, indent=1)
    run_mod.say(f"wrote {args.out}: busy {out['busy_s']:.3f} s of "
                f"{out['window_s']:.3f}, {out['steps']} steps")
    return 0


if __name__ == "__main__":
    sys.exit(main())
