#!/usr/bin/env python
"""tpulint CLI: static TPU-hazard analysis over the real compiled
programs + the codebase, gated against a checked-in baseline.

Role parity: the reference's graph-IR pass/inspection tooling
(FLAGS_check_nan_inf, memory-reuse checkers, the disabled-op ratchet
lists) — here as jaxpr/StableHLO analysis (paddle_tpu/analysis/).

Usage:
    python tools/tpulint.py                       # full run + gate
    python tools/tpulint.py --update-baseline     # accept current state
    python tools/tpulint.py --codebase-only       # fast AST-only pass
    python tools/tpulint.py --no-compile          # skip collective
                                                  # inventory compile
    python tools/tpulint.py --programs gpt_decode,train_step
    python tools/tpulint.py --json out.json       # also write JSON file

Exit codes: 0 = gate passes, 1 = NEW findings vs baseline (or a
must_stay_clean regression anchor hit), 2 = analyzer error.

The last stdout line is always one terminal JSON record, so a caller
can gate on the artifact. A gate failure is a good record with "gate": "fail" —
the measurement landed; CI failing is the POINT, not an error.

Baseline workflow: findings are identified by (code, program, site) —
never line numbers. The gate fails when a gating-severity key's count
exceeds the baseline's, or when any finding hits a `must_stay_clean`
anchor (a hazard that was FIXED — e.g. scatter cache writes in the
decode path, flush_accumulation retrace-per-call). To accept a new
intentional finding: review it, then `--update-baseline` and commit the
diff (anchors are preserved; re-introducing an anchored hazard requires
deleting its anchor by hand, which is the review point).
"""
from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DEFAULT_BASELINE = os.path.join(ROOT, "tools", "tpulint_baseline.json")

_WANT_FLAG = "--xla_force_host_platform_device_count=8"
_REEXEC_MARK = "_PADDLE_TPU_TPULINT_REEXEC"


def _env_ok() -> bool:
    return (os.environ.get(_REEXEC_MARK) == "1"
            or (os.environ.get("JAX_PLATFORMS") == "cpu"
                and _WANT_FLAG in os.environ.get("XLA_FLAGS", "")))


def _reexec():
    """The platform/device-count env must be in place before jax is
    first imported — re-exec with it."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") + " " + _WANT_FLAG).strip()
    # warm persistent compile cache, same scope as tools/ci.py
    env.setdefault("JAX_COMPILATION_CACHE_DIR",
                   os.path.join(ROOT, ".cache", "jax_ci_cpu"))
    env.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "1")
    env[_REEXEC_MARK] = "1"
    import subprocess
    # inherit the caller's cwd so relative --json/--baseline paths land
    # where the caller expects (internal paths are ROOT-absolute anyway)
    rc = subprocess.call([sys.executable] + sys.argv, env=env)
    sys.exit(rc)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--manifest", default="default",
                    choices=["default", "none"],
                    help="program set to lint (none = skip program "
                         "analysis entirely)")
    ap.add_argument("--programs", default=None,
                    help="comma list restricting manifest programs")
    ap.add_argument("--codebase-only", action="store_true",
                    help="AST + quarantine pass only (no jax tracing)")
    ap.add_argument("--no-compile", action="store_true",
                    help="skip the compile-requiring collective "
                         "inventory (trace/lower only)")
    ap.add_argument("--baseline", default=DEFAULT_BASELINE)
    ap.add_argument("--update-baseline", action="store_true",
                    help="rewrite the baseline's counts from this run "
                         "(must_stay_clean anchors and notes preserved)")
    ap.add_argument("--json", default=None,
                    help="also write the findings record to this path")
    args = ap.parse_args()

    if not args.codebase_only and args.manifest != "none" \
            and not _env_ok():
        _reexec()

    sys.path.insert(0, ROOT)
    from paddle_tpu.analysis import (count_findings, diff_against_baseline,
                                     findings_to_json, lint_quarantine,
                                     lint_tree, load_baseline,
                                     terminal_record,
                                     write_report_artifact)

    findings = []
    programs = []
    try:
        findings.extend(lint_tree(ROOT))
        findings.extend(lint_quarantine(ROOT))
        if not args.codebase_only and args.manifest != "none":
            from paddle_tpu.analysis import MANIFEST_PROGRAMS, run_manifest
            wanted = (args.programs.split(",") if args.programs else None)
            if wanted and set(wanted) - set(MANIFEST_PROGRAMS):
                ap.error(f"unknown --programs "
                         f"{sorted(set(wanted) - set(MANIFEST_PROGRAMS))}"
                         f"; valid: {list(MANIFEST_PROGRAMS)}")
            prog_findings, programs = run_manifest(
                wanted, compile_collectives=not args.no_compile)
            findings.extend(prog_findings)
    except Exception as e:   # analyzer crash: loud, machine-readable
        import traceback
        traceback.print_exc(file=sys.stderr)
        print(json.dumps({"error": f"{type(e).__name__}: {e}"}))
        return 2

    # a lint-error finding means a program was NOT actually analyzed
    # (lower/compile failed) — that is an analyzer failure, never a
    # baseline-able state: refuse to update and exit 2
    lint_errors = [f for f in findings if f.code == "lint-error"]
    if lint_errors:
        for f in lint_errors:
            print(f"[error] {f.key}: {f.message}", file=sys.stderr)
        print(json.dumps({"error": "lint-error findings — "
                          + "; ".join(f.key for f in lint_errors)}))
        return 2

    baseline = None
    if os.path.exists(args.baseline):
        baseline = load_baseline(args.baseline)
    elif not args.update_baseline:
        print(f"note: no baseline at {args.baseline} — every gating "
              "finding is NEW (run --update-baseline to accept)",
              file=sys.stderr)

    if args.update_baseline:
        base = baseline or {"version": 1, "must_stay_clean": [],
                            "notes": {}}
        # a partial run must not clobber counts it did not re-measure:
        # only full default runs rewrite wholesale (--no-compile skips
        # the collective inventory, so it is partial too)
        full_run = (args.manifest == "default" and not args.programs
                    and not args.codebase_only and not args.no_compile)
        counts = count_findings(findings)
        if not full_run:
            merged = dict(base.get("counts", {}))
            merged.update(counts)
            counts = merged
        base["counts"] = dict(sorted(counts.items()))
        base["version"] = 1
        with open(args.baseline + ".part", "w") as fh:
            json.dump(base, fh, indent=1, sort_keys=True)
            fh.write("\n")
        os.replace(args.baseline + ".part", args.baseline)
        print(f"baseline updated: {args.baseline} "
              f"({len(base['counts'])} keys)", file=sys.stderr)
        baseline = base

    new = diff_against_baseline(findings, baseline)
    record = findings_to_json(findings, new, programs)
    record["baseline"] = os.path.relpath(args.baseline, ROOT)
    # shared report-artifact contract with tools/tpucost.py
    # (analysis/report.py): atomic full-record write + the terminal
    # stdout JSON below
    write_report_artifact(args.json, record)

    for f in record["findings"]:
        flag = " NEW" if any(n["key"] == f["key"] for n in new) else ""
        print(f"[{f['severity']:5s}]{flag} {f['key']}\n"
              f"        {f['message']}", file=sys.stderr)
    if new:
        print(f"\ntpulint GATE FAILED: {len(new)} finding(s) beyond "
              f"baseline — fix them, or review + --update-baseline",
              file=sys.stderr)
    # terminal JSON record (one terminal JSON record)
    print(terminal_record(record, ("version", "programs", "counts",
                                   "new", "gate", "baseline")))
    return 1 if new else 0


if __name__ == "__main__":
    sys.exit(main())
