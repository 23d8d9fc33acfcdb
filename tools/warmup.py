#!/usr/bin/env python
"""Executable-store CLI: prebuild / inspect / evict compiled programs.

Role parity: the reference ships models through save_inference_model +
pre-warmed predictor pools so a serving process never compiles at
traffic time; here the equivalent artifact is a serialized XLA
executable in the persistent store (paddle_tpu/compilation/store.py),
prebuilt from the ProgramRegistry — the same program set tpulint lints
and the benches measure.

Usage:
    python tools/warmup.py                       # warm ALL registered
    python tools/warmup.py --programs gpt_decode,train_step
    python tools/warmup.py --parallel 4          # thread-pool compiles
    python tools/warmup.py --list                # registered programs
    python tools/warmup.py --inspect             # store entries
    python tools/warmup.py --evict               # drop every entry
    python tools/warmup.py --evict --programs a,b
    python tools/warmup.py --evict --stale       # wrong jax/backend only

Exit codes: 0 = ok, 1 = some program failed to warm, 2 = CLI error.
The last stdout line is always one terminal JSON record, so a caller
can gate on the artifact.

The store directory (PADDLE_TPU_EXEC_STORE_DIR, default
<checkout>/.cache/exec_store) is machine-local: XLA:CPU artifacts are
machine-feature sensitive, and a foreign executable is rejected at load
by the (jax version, backend, signature, donation) header check.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_WANT_FLAG = "--xla_force_host_platform_device_count=8"
_REEXEC_MARK = "_PADDLE_TPU_WARMUP_REEXEC"


def _env_ok() -> bool:
    return (os.environ.get(_REEXEC_MARK) == "1"
            or (os.environ.get("JAX_PLATFORMS") == "cpu"
                and _WANT_FLAG in os.environ.get("XLA_FLAGS", "")))


def _reexec():
    """parallel_train_step needs >= 4 devices; the platform/device-count
    env must be in place before jax is first imported — re-exec with it
    (the tools/tpulint.py idiom)."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") + " " + _WANT_FLAG).strip()
    # prime the jax persistent cache too: the SAME programs tier-1 and
    # tpulint compile, so one warmup run self-services the warm-cache
    # dependency the 870s gate budget assumes
    env.setdefault("JAX_COMPILATION_CACHE_DIR",
                   os.path.join(ROOT, ".cache", "jax_ci_cpu"))
    env.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "1")
    env[_REEXEC_MARK] = "1"
    import subprocess
    rc = subprocess.call([sys.executable] + sys.argv, env=env)
    sys.exit(rc)


def _emit(record: dict) -> None:
    print(json.dumps(record))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--programs", default=None,
                    help="comma-separated registered program names "
                         "(default: all)")
    ap.add_argument("--parallel", type=int, default=1,
                    help="compile thread-pool width (XLA compiles "
                         "release the GIL); builds stay serial")
    ap.add_argument("--list", action="store_true",
                    help="print the ProgramRegistry and exit")
    ap.add_argument("--inspect", action="store_true",
                    help="print executable-store entries and exit")
    ap.add_argument("--evict", action="store_true",
                    help="remove store entries (scoped by --programs / "
                         "--stale) and exit")
    ap.add_argument("--stale", action="store_true",
                    help="with --evict: only entries whose jax version "
                         "or backend no longer match this process")
    args = ap.parse_args()

    if not _env_ok() and not (args.inspect or args.evict):
        _reexec()

    sys.path.insert(0, ROOT)
    from paddle_tpu.compilation import registry, warmup
    from paddle_tpu.compilation.store import default_store

    names = ([n.strip() for n in args.programs.split(",") if n.strip()]
             if args.programs else None)
    store = default_store()

    if args.list:
        progs = [{"name": n, "tags": list(registry.get(n).tags),
                  "min_devices": registry.get(n).min_devices,
                  "description": registry.get(n).description}
                 for n in registry.names()]
        _emit({"registry": progs, "count": len(progs)})
        return 0

    if args.inspect:
        entries = [{"name": e.name, "signature": e.signature_hash,
                    "size_kb": round(e.size / 1024, 1),
                    "jax_version": e.jax_version, "backend": e.backend,
                    "donated_args": len(e.donation),
                    "age_s": round(time.time() - e.created, 1)}
                   for e in store.entries()]
        _emit({"store_dir": store.root, "enabled": store.enabled,
               "entries": entries, "count": len(entries)})
        return 0

    if args.evict:
        n = store.evict(names=names, stale_only=args.stale)
        _emit({"store_dir": store.root, "evicted": n,
               "stale_only": args.stale})
        return 0

    try:
        report = warmup(names, parallel=max(1, args.parallel),
                        store=store)
    except ValueError as e:
        # unknown --programs name: still a CLI error (exit 2) and still
        # one terminal JSON record, on every path
        _emit({"error": str(e), "known": registry.names()})
        return 2
    for rec in report["programs"]:
        src = rec.get("source", "?")
        extra = (f" ({rec.get('reason', rec.get('error', ''))})"
                 if src in ("skipped", "error") else
                 f" trace {rec.get('trace_s', 0):.2f}s"
                 f" compile {rec.get('compile_s', 0):.2f}s")
        print(f"[{src:>18}] {rec['name']}{extra}", file=sys.stderr)
    _emit(dict(report))
    return 0 if report.ok else 1


if __name__ == "__main__":
    sys.exit(main())
