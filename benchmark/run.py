"""The benchmark's one command.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

It holds no cell's, configuration's or metric's name: the cell is looked
up in ``BENCHMARK.json``; its configuration is the file that entry names;
its traffic is ``benchmark/traffic/<traffic>.json``, which names its
driver (``benchmark/drivers/<driver>.py``); the limits of `correct` are
``benchmark/limits/<cell>.json``; every per-layer metric is read by
``benchmark/layer_metrics/<metric>.py``. A later PR adds files and
entries, and edits none.

The last line of standard output is the result object. Everything else
(losses, cache hits, which roofline binds) goes to standard error.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()        # set-up is counted from here

import argparse                 # noqa: E402
import importlib.util           # noqa: E402
import json                     # noqa: E402
import os                       # noqa: E402
import shutil                   # noqa: E402
import sys                      # noqa: E402
import types                    # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def say(msg: str) -> None:
    sys.stderr.write(f"[benchmark] {msg}\n")
    sys.stderr.flush()


def load_json(*parts: str):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


def load_module(kind: str, name: str):
    """``benchmark/<kind>/<name>.py`` by file, since a metric's name may
    hold a dot."""
    path = os.path.join(HERE, kind, name + ".py")
    if not os.path.isfile(path):
        raise FileNotFoundError(f"{kind} {name!r}: no file {path}")
    spec = importlib.util.spec_from_file_location(
        f"benchmark.{kind}.{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def find_cell(bench: dict, name: str) -> dict:
    entry = next((w for w in bench["workloads"] if w["name"] == name), None)
    if entry is None:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json")
    conf = next(c for c in bench["configs"] if c["name"] == entry["config"])
    return {"name": name, "chips": int(entry["chips"]),
            "config": load_json(conf["file"]),
            "traffic": load_json("benchmark", "traffic",
                                 entry["traffic"] + ".json"),
            "limits": load_json("benchmark", "limits", name + ".json")}


def metrics_of(bench: dict, group: str, cell: str) -> list:
    return [m for m in bench[group]
            if "workloads" not in m or cell in m["workloads"]]


def require_devices(chips: int):
    """The accelerators JAX found, or no run at all."""
    import jax
    say(f"set-up: {time.perf_counter() - T0:.2f} s to jax imported")
    devices = jax.devices()
    if devices[0].platform == "cpu" or len(devices) < chips:
        raise SystemExit(
            f"the cell needs {chips} accelerator chip(s); JAX found "
            f"{len(devices)} x {devices[0].platform}: no result")
    return devices[:chips]


def keep_compile_cache(jax) -> None:
    """JAX's persistent compilation cache, on, at the one place the
    program's own rule names (``paddle_tpu/_paths.py``): where
    ``JAX_COMPILATION_CACHE_DIR`` says, else ``<checkout>/.cache/jax``.
    The benchmark sets it itself because the program leaves the cache
    off whenever ``JAX_PLATFORMS`` names ``cpu`` anywhere, as the chip
    machine's ``tpu,cpu`` does: every run then compiled every program
    (PERF.md, PR 27)."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        path = os.path.join(ROOT, ".cache", "jax")
        os.makedirs(path, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", path)
    # one checkout's cache holds this benchmark's programs and nothing
    # else; an eviction limit set for the machine (192 MiB on the chip
    # tool's) would turn one cell's run into the other's cache miss
    jax.config.update("jax_compilation_cache_max_size", -1)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path.insert(0, ROOT)
    bench = load_json("BENCHMARK.json")
    cell = find_cell(bench, args.workload)
    devices = require_devices(cell["chips"])
    say(f"set-up: {time.perf_counter() - T0:.2f} s to the devices")

    import jax
    from benchmark import chips, trace as trace_mod
    chip = chips.chip_for(devices[0].device_kind)   # unknown kind raises
    trace_dir = os.path.join(ROOT, ".cache", "benchmark_trace",
                             args.workload)
    if args.trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
    ctx = types.SimpleNamespace(
        cell=cell, seed=args.seed, seconds=args.seconds,
        trace=bool(args.trace), t0=T0, trace_dir=trace_dir, say=say,
        devices=devices, chip=chip)

    keep_compile_cache(jax)

    driver = load_module("drivers", cell["traffic"]["driver"])
    session = driver.start(ctx)
    say(f"compile cache at {jax.config.jax_compilation_cache_dir}")

    # the allocator counts a program's temporaries as reserved and not
    # as in use, and both come out of the same memory (a program whose
    # compiler counts 8 GiB of temporaries reads 8 GiB reserved and none
    # of it in use; PERF.md, PR 27): the peak held is their sum
    stats = [d.memory_stats() or {} for d in devices]
    peak = max(int(s.get("peak_bytes_in_use", 0))
               + int(s.get("peak_bytes_reserved", 0)) for s in stats)
    say("device memory, GiB: " + ", ".join(
        f"{k} {v / 2**30:.3f}" for k, v in sorted(stats[0].items())
        if "bytes" in k))
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": peak}
    session.obs["memory_peak_bytes"] = peak

    checks = session.check()        # frees the program, runs the reference

    result = {"correct": all(v <= lim for _, v, lim in checks),
              "attempted": session.attempted, "failed": session.failed}
    if args.trace:
        tr = trace_mod.load(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)
        device["busy_s"] = tr.busy_s()
        device["window_s"] = tr.window_s()
        values = {}
        for m in metrics_of(bench, "per_layer", args.workload):
            v = load_module("layer_metrics", m["name"]).read(
                tr, session.obs, cell, chip, say)
            if v is not None:           # nothing to read: left out
                values[m["name"]] = {"value": v, "unit": m["unit"]}
        result["metrics"] = values
        result["breakdown"] = {
            "device_ops": tr.top_ops(10),
            "idle_gaps": sorted(
                ([n, ns / 1e9] for n, ns in tr.idle_by_cause().items()),
                key=lambda kv: -kv[1])[:10]}
    else:
        result["metrics"] = {
            m["name"]: {"value": session.end_to_end[m["name"]],
                        "unit": m["unit"]}
            for m in metrics_of(bench, "end_to_end", args.workload)}
    result["device"] = device
    result["compared"] = {n: {"value": v, "limit": lim}
                          for n, v, lim in checks}
    for n, v, lim in checks:
        say(f"compared {n}: {v:.6g} (limit {lim:.6g})"
            f"{'' if v <= lim else '  <-- over'}")
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
