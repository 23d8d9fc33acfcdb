#!/usr/bin/env python
"""tpucost CLI: static fusion & HBM-traffic inventory over every
ProgramRegistry site, gated against a ratcheted roofline baseline.

The measurement half of the MFU campaign (ROADMAP item 3): every
registered program is built exactly as its owner builds it (PR 5's
registry), lowered + compiled (through the warm persistent caches), and
its optimized HLO parsed into a per-program inventory — FLOPs, HBM
bytes read/written, arithmetic intensity, roofline time under a
configurable chip spec (v5-lite default), fusion-kind histogram, and
the ranked top unfused elementwise chains. The JSON report is the A/B
instrument every later Pallas-kernel / mega-kernelization PR diffs
against.

Usage:
    python tools/tpucost.py                      # full run + gate
    python tools/tpucost.py --update-baseline    # re-pin the budgets
    python tools/tpucost.py --programs gpt_decode,train_step
    python tools/tpucost.py --json report.json   # full report artifact
    python tools/tpucost.py --chip v5p           # roofline chip spec
    python tools/tpucost.py --detail             # per-kernel lists in
                                                 # the --json report

Exit codes: 0 = gate passes, 1 = budget/anchor violation vs
tools/tpucost_baseline.json, 2 = analyzer error. The last stdout line
is always one JSON record (one terminal JSON record) — a failing
gate is a GOOD record with "gate": "fail".

Baseline semantics (analysis/hlo_cost.py): per-program budgets ratchet
— hbm_bytes and kernel_count may only stay or shrink, matmul-FLOP
share may only stay or grow; `--update-baseline` re-pins them from the
current run (and locks wins in). `anchors` are hand-set invariants
that SURVIVE updates: the decode tick's modeled HBM bytes must stay
within 1.15x of the analytic KV-cache + weight bound, train-step
matmul share must never drop below its floor — regressing one requires
editing the baseline by hand, which is the review point. A baseline
entry naming a program the registry no longer has fails as
stale-cost-program (registry-rename rot, the stale-quarantine
analogue).
"""
from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DEFAULT_BASELINE = os.path.join(ROOT, "tools", "tpucost_baseline.json")

_WANT_FLAG = "--xla_force_host_platform_device_count=8"
_REEXEC_MARK = "_PADDLE_TPU_TPUCOST_REEXEC"


def _env_ok() -> bool:
    return (os.environ.get(_REEXEC_MARK) == "1"
            or (os.environ.get("JAX_PLATFORMS") == "cpu"
                and _WANT_FLAG in os.environ.get("XLA_FLAGS", "")))


def _reexec():
    """The platform/device-count env must be in place before jax is
    first imported — re-exec with it (and the warm compile cache, so
    the per-program compiles load instead of compiling)."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") + " " + _WANT_FLAG).strip()
    env.setdefault("JAX_COMPILATION_CACHE_DIR",
                   os.path.join(ROOT, ".cache", "jax_ci_cpu"))
    env.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "1")
    env[_REEXEC_MARK] = "1"
    import subprocess
    rc = subprocess.call([sys.executable] + sys.argv, env=env)
    sys.exit(rc)


def collect_inventories(programs=None, chip="v5lite", detail=False):
    """Build + compile + cost every registry manifest site. Returns
    (inventories, geometries, skipped) — a site needing more devices
    than the process has is skipped with a reason (the CLI re-exec
    provides 8, so this only triggers for ad-hoc imports)."""
    import jax
    from paddle_tpu.analysis import program_cost
    from paddle_tpu.compilation import registry
    invs, geoms, skipped = {}, {}, {}
    n_dev = len(jax.devices())
    for name in (programs or registry.names(tag="manifest")):
        prog = registry.get(name)
        if prog.min_devices > n_dev:
            skipped[name] = (f"needs >= {prog.min_devices} devices, "
                             f"have {n_dev}")
            continue
        r = prog.builder()
        try:
            hlo = r.fn.lower(*r.args).compile().as_text()
        finally:
            if r.cleanup is not None:
                r.cleanup()
        invs[name] = program_cost(hlo, name=name, chip=chip,
                                  detail=detail)
        geoms[name] = dict(r.geometry)
        tokens = r.geometry.get("tokens_per_exec")
        if tokens:
            invs[name]["tokens_per_exec"] = tokens
            invs[name]["flops_per_token"] = invs[name]["flops"] / tokens
            invs[name]["hbm_bytes_per_token"] = (
                invs[name]["hbm_bytes"] / tokens)
        invs[name]["geometry"] = dict(r.geometry)
    return invs, geoms, skipped


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--programs", default=None,
                    help="comma list restricting registry programs")
    ap.add_argument("--chip", default=None,
                    help="chip spec for the roofline (default: the "
                         "baseline's, else v5lite)")
    ap.add_argument("--baseline", default=DEFAULT_BASELINE)
    ap.add_argument("--update-baseline", action="store_true",
                    help="re-pin the budgets from this run (anchors "
                         "and notes preserved)")
    ap.add_argument("--json", default=None,
                    help="write the full report artifact to this path")
    ap.add_argument("--detail", action="store_true",
                    help="include per-kernel lists in the --json report")
    args = ap.parse_args()

    if not _env_ok():
        _reexec()

    sys.path.insert(0, ROOT)
    from paddle_tpu.analysis import (check_cost_baseline, count_findings,
                                     load_cost_baseline, terminal_record,
                                     updated_cost_baseline,
                                     write_report_artifact)
    from paddle_tpu.compilation import registry

    baseline = None
    if os.path.exists(args.baseline):
        baseline = load_cost_baseline(args.baseline)
    elif not args.update_baseline:
        print(f"note: no baseline at {args.baseline} — every program "
              "reads as unbaselined (run --update-baseline to pin)",
              file=sys.stderr)
    chip = args.chip or (baseline or {}).get("chip", "v5lite")

    wanted = ([p.strip() for p in args.programs.split(",") if p.strip()]
              if args.programs else None)
    live = registry.names(tag="manifest")
    if wanted and set(wanted) - set(live):
        # one terminal JSON record even on bad input (same hardening
        # as tools/warmup.py): a caller that asked for a renamed
        # program must see an error record, not an empty artifact
        msg = (f"unknown --programs {sorted(set(wanted) - set(live))}; "
               f"valid: {live}")
        print(msg, file=sys.stderr)
        print(json.dumps({"error": msg}))
        return 2

    try:
        invs, geoms, skipped = collect_inventories(
            wanted, chip=chip, detail=args.detail)
    except Exception as e:      # analyzer crash: loud, machine-readable
        import traceback
        traceback.print_exc(file=sys.stderr)
        print(json.dumps({"error": f"{type(e).__name__}: {e}"}))
        return 2

    if args.update_baseline:
        if wanted or skipped:
            # a partial run must not clobber budgets it didn't measure
            merged = dict((baseline or {}).get("budgets", {}))
            new = updated_cost_baseline(baseline, invs)
            merged.update(new["budgets"])
            new["budgets"] = dict(sorted(merged.items()))
            base = new
        else:
            base = updated_cost_baseline(baseline, invs)
        with open(args.baseline + ".part", "w") as fh:
            json.dump(base, fh, indent=1, sort_keys=True)
            fh.write("\n")
        os.replace(args.baseline + ".part", args.baseline)
        print(f"baseline updated: {args.baseline} "
              f"({len(base['budgets'])} budgets)", file=sys.stderr)
        baseline = base

    # the stale check compares against the FULL registry even on
    # partial runs — a rename is stale no matter what was measured —
    # and a FULL run additionally fails if any live baselined program
    # produced no inventory (a silently skipped site must not read as
    # its anchors passing)
    violations = check_cost_baseline(invs, baseline, live, geoms,
                                     require_all=wanted is None)
    record = {
        "version": 1,
        "chip": chip,
        "programs": sorted(invs),
        "skipped": skipped,
        "inventories": invs,
        "totals": {
            "flops": sum(i["flops"] for i in invs.values()),
            "hbm_bytes": sum(i["hbm_bytes"] for i in invs.values()),
            "kernel_count": sum(i["kernel_count"]
                                for i in invs.values()),
        },
        "counts": count_findings(violations) if violations else {},
        "new": [f.to_dict() for f in violations],
        "gate": "fail" if violations else "pass",
        "baseline": os.path.relpath(args.baseline, ROOT),
    }
    write_report_artifact(args.json, record)

    for name in sorted(invs):
        inv = invs[name]
        top = inv["top_unfused"][0] if inv["top_unfused"] else None
        print(f"[{name}] flops={inv['flops']:.3g} "
              f"matmul={inv['matmul_flop_share']:.1%} "
              f"hbm={inv['hbm_bytes']} "
              f"AI={inv['arithmetic_intensity']} "
              f"kernels={inv['kernel_count']} "
              f"roofline={inv['roofline_seconds']*1e6:.1f}us "
              f"({inv['bound']}-bound)"
              + (f" top-unfused={top['intermediate_bytes']}B"
                 f"x{top['kernel_count']}k" if top else ""),
              file=sys.stderr)
    for f in violations:
        print(f"[{f.severity:5s}] NEW {f.key}\n        {f.message}",
              file=sys.stderr)
    if violations:
        print(f"\ntpucost GATE FAILED: {len(violations)} violation(s) "
              "— fix the regression, or review + --update-baseline "
              "(anchors move only by hand)", file=sys.stderr)
    print(terminal_record(record, ("version", "chip", "programs",
                                   "skipped", "totals", "counts",
                                   "new", "gate", "baseline")))
    return 1 if violations else 0


if __name__ == "__main__":
    sys.exit(main())
