#!/usr/bin/env python
"""tpuprof CLI: MEASURED runtime kernel attribution over every
ProgramRegistry site, gated against a noise-tolerant dispatch-time
baseline.

tpucost (PR 6) models each registered program's FLOPs/HBM/roofline;
this tool measures where the time actually goes (ROADMAP item 3's
measurement->fusion loop): every registered program is built exactly as
its owner builds it, executed under the programmatic ``jax.profiler``,
and the chrome trace's device lanes are parsed (stdlib gzip+json — no
TensorBoard) and JOINED with tpucost's modeled kernel inventory by
kernel name. Per program: measured dispatch wall time (median of
interleaved rounds — one background spike cannot land on one program),
a time-weighted fusion-class histogram, measured-vs-modeled roofline
ratios per kernel, and PR 6's unfused chains re-ranked by measured
seconds. On a CPU backend the trace has no device plane, so the report
degrades to wall-time-per-dispatch with the join marked unavailable
(tests/test_runtime_profile.py holds it to that).

Usage:
    python tools/tpuprof.py                      # full run + gate
    python tools/tpuprof.py --update-baseline    # re-pin the budgets
    python tools/tpuprof.py --programs gpt_decode,train_step
    python tools/tpuprof.py --json report.json   # full report artifact
    python tools/tpuprof.py --rounds 5           # more noise samples

Exit codes: 0 = gate passes, 1 = budget/anchor violation vs
tools/tpuprof_baseline.json, 2 = profiler error. The last stdout line
is always one JSON record (one terminal JSON record) — a failing
gate is a GOOD record with "gate": "fail".

Baseline semantics (analysis/runtime_profile.py): per-program
``dispatch_ms`` medians re-pin wholesale on --update-baseline; the gate
fails only past ``budget * tolerance`` (the band absorbs this host's
seconds-scale jitter — a structural regression clears it easily).
``anchors`` are hand-set measured invariants that survive updates —
train_step's device time must stay matmul-dominated, the decode tick
must not drift past its measured-vs-roofline ceiling — evaluated
whenever the trace has a device plane and SKIPPED LOUDLY (the record's
``anchors_skipped``) when it does not, so a CPU run never reads as its
TPU anchors holding.

Multi-device sites (parallel_train_step) are excluded from the default
run: 8 virtual devices thrashing one core measures the host scheduler,
not the program, and executing persistent-cache-reloaded multi-device
CPU programs is the documented cpu_aot_loader abort hazard. Opt in
explicitly with --programs.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DEFAULT_BASELINE = os.path.join(ROOT, "tools", "tpuprof_baseline.json")

_WANT_FLAG = "--xla_force_host_platform_device_count=8"
_REEXEC_MARK = "_PADDLE_TPU_TPUPROF_REEXEC"


def _env_ok() -> bool:
    return (os.environ.get(_REEXEC_MARK) == "1"
            or (os.environ.get("JAX_PLATFORMS") == "cpu"
                and _WANT_FLAG in os.environ.get("XLA_FLAGS", "")))


def _reexec():
    """tpucost/tpulint parity: the platform/device-count env must be in
    place before jax is first imported — re-exec with it and the warm
    compile cache (the per-program compiles load instead of
    compiling)."""
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


def collect_profiles(programs=None, chip="v5lite", rounds=3, inner=3,
                     profile_dispatches=3, top=15):
    """Build, warm, measure (interleaved rounds) and profile every
    selected registry site. Returns (reports, skipped)."""
    import jax
    from paddle_tpu.analysis import runtime_profile as rp
    from paddle_tpu.analysis.hlo_cost import collect_kernels, \
        parse_hlo_module
    from paddle_tpu.compilation import registry

    n_dev = len(jax.devices())
    names = programs or registry.names(tag="manifest")
    built, skipped = [], {}
    try:
        for name in names:
            prog = registry.get(name)
            if prog.min_devices > n_dev:
                skipped[name] = (f"needs >= {prog.min_devices} devices, "
                                 f"have {n_dev}")
                continue
            if programs is None and prog.min_devices > 1:
                skipped[name] = (
                    "multi-device site excluded from the default run "
                    "(virtual-mesh wall time is scheduler noise; "
                    "cache-reloaded multi-device CPU executables are "
                    "the cpu_aot_loader abort hazard) — opt in with "
                    "--programs")
                continue
            r = prog.builder()
            try:
                hlo = r.fn.lower(*r.args).compile().as_text()
                args = rp.host_example_args(r.args)
                jax.block_until_ready(r.fn(*args))      # warm
                kernels = collect_kernels(parse_hlo_module(hlo))
            except BaseException:
                # not in `built` yet — the finally below would miss it
                # (a failed decode site must not leave its engine
                # thread + device buffers live while we unwind)
                if r.cleanup is not None:
                    try:
                        r.cleanup()
                    except Exception:
                        pass
                raise
            built.append({"name": name, "fn": r.fn, "args": args,
                          "kernels": kernels, "cleanup": r.cleanup,
                          "op_scopes": rp.hlo_op_scopes(hlo),
                          "geometry": dict(r.geometry),
                          "dispatch_s": []})

        # measured dispatch time: rounds INTERLEAVED across programs —
        # this 1-core host jitters at seconds scale, and a background
        # spike must spread over everyone instead of landing on
        # whichever program it coincided with
        for _ in range(max(1, rounds)):
            for b in built:
                b["dispatch_s"].extend(
                    rp.measure_dispatch(b["fn"], b["args"],
                                        rounds=1, inner=inner))

        # profiling pass: one jax.profiler session per program into its
        # own logdir — every device event in a trace belongs to exactly
        # one program (clean attribution, no cross-talk)
        reports = {}
        for b in built:
            logdir = tempfile.mkdtemp(prefix=f"tpuprof_{b['name']}_")
            events = rp.trace_dispatches(b["fn"], b["args"],
                                         profile_dispatches, logdir)
            reports[b["name"]] = rp.runtime_report(
                b["name"], kernels=b["kernels"], events=events,
                dispatch_s=b["dispatch_s"],
                dispatches_profiled=profile_dispatches,
                chip=chip, geometry=b["geometry"],
                op_scopes=b["op_scopes"], top=top)
    finally:
        for b in built:
            if b["cleanup"] is not None:
                try:
                    b["cleanup"]()
                except Exception:
                    pass
    return reports, skipped


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--programs", default=None,
                    help="comma list restricting registry programs "
                         "(also the opt-in for multi-device sites)")
    ap.add_argument("--chip", default=None,
                    help="chip spec for the modeled roofline side of "
                         "the join (default: the baseline's, else "
                         "v5lite)")
    ap.add_argument("--baseline", default=DEFAULT_BASELINE)
    ap.add_argument("--update-baseline", action="store_true",
                    help="re-pin dispatch budgets from this run "
                         "(anchors, notes and tolerance preserved)")
    ap.add_argument("--json", default=None,
                    help="write the full report artifact to this path")
    ap.add_argument("--rounds", type=int, default=3,
                    help="interleaved measurement rounds per program")
    ap.add_argument("--inner", type=int, default=3,
                    help="dispatches per measurement round")
    ap.add_argument("--profile-dispatches", type=int, default=3,
                    help="dispatches under the jax.profiler session")
    ap.add_argument("--top", type=int, default=15,
                    help="per-kernel rows kept in each report")
    args = ap.parse_args()

    if not _env_ok():
        _reexec()

    sys.path.insert(0, ROOT)
    from paddle_tpu.analysis import (check_profile_baseline,
                                     count_findings,
                                     load_profile_baseline,
                                     terminal_record,
                                     updated_profile_baseline,
                                     write_report_artifact)
    from paddle_tpu.compilation import registry

    baseline = None
    if os.path.exists(args.baseline):
        baseline = load_profile_baseline(args.baseline)
    elif not args.update_baseline:
        print(f"note: no baseline at {args.baseline} — every program "
              "reads as unbaselined (run --update-baseline to pin)",
              file=sys.stderr)
    chip = args.chip or (baseline or {}).get("chip", "v5lite")

    wanted = ([p.strip() for p in args.programs.split(",") if p.strip()]
              if args.programs else None)
    live = registry.names(tag="manifest")
    if wanted and set(wanted) - set(live):
        # one terminal JSON record even on bad input (warmup.py /
        # tpucost.py parity): a caller that asked for a renamed program
        # must see an error record, not an empty artifact
        msg = (f"unknown --programs {sorted(set(wanted) - set(live))}; "
               f"valid: {live}")
        print(msg, file=sys.stderr)
        print(json.dumps({"error": msg}))
        return 2

    try:
        reports, skipped = collect_profiles(
            wanted, chip=chip, rounds=args.rounds, inner=args.inner,
            profile_dispatches=args.profile_dispatches, top=args.top)
    except Exception as e:      # profiler crash: loud, machine-readable
        import traceback
        traceback.print_exc(file=sys.stderr)
        print(json.dumps({"error": f"{type(e).__name__}: {e}"}))
        return 2

    if args.update_baseline:
        if wanted or skipped:
            # a partial run must not clobber budgets it didn't measure
            # — but it MUST still prune entries whose program left the
            # registry, or the stale-prof-program failure could never
            # be fixed by its own documented remedy (the default run
            # always has a skipped multi-device site, so this merge
            # path is the one that actually runs)
            merged = {k: v for k, v in
                      (baseline or {}).get("budgets", {}).items()
                      if k in set(live)}
            new = updated_profile_baseline(baseline, reports)
            merged.update(new["budgets"])
            new["budgets"] = dict(sorted(merged.items()))
            base = new
        else:
            base = updated_profile_baseline(baseline, reports)
        with open(args.baseline + ".part", "w") as fh:
            json.dump(base, fh, indent=1, sort_keys=True)
            fh.write("\n")
        os.replace(args.baseline + ".part", args.baseline)
        print(f"baseline updated: {args.baseline} "
              f"({len(base['budgets'])} budgets)", file=sys.stderr)
        baseline = base

    violations, anchors_skipped = check_profile_baseline(
        reports, baseline, live, require_all=wanted is None)
    had_device = any(r.get("had_device_plane") for r in reports.values())
    # join quality averaged over the reports that HAVE a join — a
    # program whose trace lost its device plane must show up as
    # unattributed (its had_device_plane False in `reports`), not
    # silently drag the run-level rate toward zero
    join_rates = [r["join"]["join_rate_time_weighted"]
                  for r in reports.values()
                  if r.get("had_device_plane")
                  and r["join"].get("available")]
    record = {
        "version": 1,
        "chip": chip,
        "programs": sorted(reports),
        "skipped": skipped,
        "had_device_plane": had_device,
        "degraded": not had_device,
        "anchors_skipped": anchors_skipped,
        "reports": reports,
        "totals": {
            "dispatch_ms": round(sum(
                r.get("dispatch", {}).get("median_ms", 0.0) or 0.0
                for r in reports.values()), 3),
            "join_rate_time_weighted": (round(
                sum(join_rates) / len(join_rates), 4)
                if join_rates else None),
            "programs_unattributed": sum(
                1 for r in reports.values()
                if not r.get("had_device_plane")),
        },
        "counts": count_findings(violations) if violations else {},
        "new": [f.to_dict() for f in violations],
        "gate": "fail" if violations else "pass",
        "baseline": os.path.relpath(args.baseline, ROOT),
    }
    write_report_artifact(args.json, record)

    for name in sorted(reports):
        rep = reports[name]
        d = rep["dispatch"]
        line = (f"[{name}] dispatch={d.get('median_ms', '?')}ms "
                f"(n={d.get('n', 0)})")
        if rep["had_device_plane"]:
            line += (f" device={rep['join']['measured_total_us']}us "
                     f"join={rep['join']['join_rate_time_weighted']:.0%}"
                     f" vs-roofline={rep['measured_vs_roofline']}x"
                     f" matmul-time={rep['matmul_time_share']}")
        else:
            line += " (no device plane — wall-time only)"
        print(line, file=sys.stderr)
    for s in anchors_skipped:
        print(f"[skip ] anchor {s['kind']} on {s['program']}: "
              f"{s['reason']}", file=sys.stderr)
    for f in violations:
        print(f"[{f.severity:5s}] NEW {f.key}\n        {f.message}",
              file=sys.stderr)
    if violations:
        print(f"\ntpuprof GATE FAILED: {len(violations)} violation(s) "
              "— fix the regression, or review + --update-baseline "
              "(anchors move only by hand)", file=sys.stderr)
    print(terminal_record(record, ("version", "chip", "programs",
                                   "skipped", "had_device_plane",
                                   "degraded", "anchors_skipped",
                                   "totals", "counts", "new", "gate",
                                   "baseline")))
    return 1 if violations else 0


if __name__ == "__main__":
    sys.exit(main())
