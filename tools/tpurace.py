#!/usr/bin/env python
"""tpurace CLI: static lock-discipline lint over the tree, gated
against a checked-in baseline — the concurrency pillar next to
tpulint (program hazards) and tpucost (roofline budgets).

Role parity: the reference debugs its concurrency surface with
FLAGS_benchmark-style serializing switches and xpu sync-debug
re-runs; tpurace makes the discipline a machine-checked gate instead
(paddle_tpu/analysis/concurrency.py — guarded-attribute inference,
blocking-under-lock, static lock-order cycles, check-then-act,
orphan threads; the runtime half is obs/locks.py + tools/race_hunt.py).

Usage:
    python tools/tpurace.py                       # lint + gate
    python tools/tpurace.py --update-baseline     # accept current state
    python tools/tpurace.py --json out.json       # also write JSON file

Exit codes: 0 = gate passes, 1 = NEW findings vs baseline (or a
must_stay_clean regression anchor hit), 2 = analyzer error.

Pure-AST: no jax import, no re-exec, runs in ~a second — cheap enough
that ci.py runs it after every --quick.

Baseline workflow (tools/tpurace_baseline.json): findings are keyed
(code, file, Class::attr-or-method) — never line numbers. `counts`
tolerates reviewed, accepted hazards (the benign single-caller
check-then-act warns). `must_stay_clean` anchors pin the classes whose
races were FIXED in the PRs that built this tool — the engine tick
loop, the request journal, the compilation store, the metrics
registry: any finding whose key matches an anchor prefix fails the
gate even with a count bump, so a fixed race cannot silently return.

The last stdout line is one terminal JSON record, so a caller can gate
on the artifact.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DEFAULT_BASELINE = os.path.join(ROOT, "tools", "tpurace_baseline.json")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--baseline", default=DEFAULT_BASELINE)
    ap.add_argument("--update-baseline", action="store_true",
                    help="rewrite the baseline's counts from this run "
                         "(must_stay_clean anchors and notes preserved)")
    ap.add_argument("--json", default=None,
                    help="also write the findings record to this path")
    args = ap.parse_args()

    sys.path.insert(0, ROOT)
    from paddle_tpu.analysis import (count_findings,
                                     diff_against_baseline,
                                     findings_to_json,
                                     lint_concurrency_tree,
                                     load_baseline, terminal_record,
                                     write_report_artifact)

    try:
        findings = lint_concurrency_tree(ROOT)
    except Exception as e:   # analyzer crash: loud, machine-readable
        import traceback
        traceback.print_exc(file=sys.stderr)
        print(json.dumps({"error": f"{type(e).__name__}: {e}"}))
        return 2

    # a lint-error finding means a file was NOT analyzed (syntax
    # error) — an analyzer failure, never a baseline-able state
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
        print(f"note: no baseline at {args.baseline} — every finding "
              "is NEW (run --update-baseline to accept)",
              file=sys.stderr)

    if args.update_baseline:
        base = baseline or {"version": 1, "must_stay_clean": [],
                            "notes": {}}
        base["counts"] = dict(sorted(count_findings(findings).items()))
        base["version"] = 1
        with open(args.baseline + ".part", "w") as fh:
            json.dump(base, fh, indent=1, sort_keys=True)
            fh.write("\n")
        os.replace(args.baseline + ".part", args.baseline)
        print(f"baseline updated: {args.baseline} "
              f"({len(base['counts'])} keys)", file=sys.stderr)
        baseline = base

    new = diff_against_baseline(findings, baseline)
    record = findings_to_json(findings, new, programs=[])
    record["baseline"] = os.path.relpath(args.baseline, ROOT)
    write_report_artifact(args.json, record)

    for f in record["findings"]:
        flag = " NEW" if any(n["key"] == f["key"] for n in new) else ""
        print(f"[{f['severity']:5s}]{flag} {f['key']}\n"
              f"        {f['message']}", file=sys.stderr)
    if new:
        print(f"\ntpurace GATE FAILED: {len(new)} finding(s) beyond "
              f"baseline — fix them, or review + --update-baseline",
              file=sys.stderr)
    print(terminal_record(record, ("version", "counts", "new", "gate",
                                   "baseline")))
    return 1 if new else 0


if __name__ == "__main__":
    sys.exit(main())
