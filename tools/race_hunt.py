#!/usr/bin/env python
"""race_hunt: schedule-fuzzing hammers over the serving tier's
concurrency surface, with the tpurace lock sanitizer on.

Each hammer drives one REAL contended object (no mocks) from
barrier-aligned threads under ``sys.setswitchinterval(1e-5)`` — a
~1000x higher preemption rate than the default 5ms, so interleavings
that normally need an unlucky night happen in seconds — and asserts
the object's own invariants. The lock sanitizer (obs/locks.py,
PADDLE_TPU_LOCK_SAN) runs throughout: any lock-order cycle or wedged
waits-for cycle the schedule exercises dumps a flight artifact, and
ANY artifact fails the run.

Hammers (``--hammers`` comma-list; ``--host-only`` keeps to the ones
that never import jax — the test-suite smoke):

  journal_extend_reap   [host] replica threads extend ONE request
                        journal at overlapping bases (the primary +
                        hedge shape) while a reaper thread snapshots
                        synthesize_body()/complete()/size();
                        invariant: the journal equals the greedy
                        stream exactly, no mismatch flag, no torn
                        snapshot.
  qos_admit_shed        [host] tenants hammer try_acquire/release
                        under tiny capacity; invariant: inflight
                        never exceeds capacity and drains to exactly
                        0 (shed/timeout under load is truthful, not a
                        violation).
  metrics_scrape_record [host] writer threads inc/observe while
                        scrapers render()+parse_text(); invariant:
                        every scrape parses and the final counters
                        equal the exact increment count (no lost
                        updates).
  engine_submit_cancel  [jax]  submit/cancel storm against a live
                        tiny-GPT engine mid-tick, with stats() reader
                        pressure; invariant: every future resolves
                        (result or RequestCancelled), slots and queue
                        drain, and submitted == completed + cancelled
                        at quiesce (no leaked or double-counted
                        request).
  warmup_concurrent     [jax]  several threads warmup() one engine at
                        once (the check-then-act surface the static
                        lint flags on _copy_prog/_decode_prog);
                        invariant: no exception, engine warmed and
                        still serving afterwards.

Exit codes: 0 = all hammers clean, 1 = invariant violation or
sanitizer artifact, 2 = harness error. The last stdout line is one
JSON record (one terminal JSON record); ``--json`` also writes
the full record. tools/tpurace.py is the static half of the race
gate; this is the dynamic half ci.py --quick runs after the tests.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

HOST_HAMMERS = ("journal_extend_reap", "qos_admit_shed",
                "metrics_scrape_record")
JAX_HAMMERS = ("engine_submit_cancel", "warmup_concurrent")
ALL_HAMMERS = HOST_HAMMERS + JAX_HAMMERS


def _barrier_run(n_threads: int, fn) -> list:
    """Start n threads against one barrier so they all enter the
    contended region together; returns per-thread error strings."""
    bar = threading.Barrier(n_threads)
    errs: list = []
    errs_lock = threading.Lock()

    def wrap(i):
        try:
            bar.wait(timeout=30)
            fn(i)
        except Exception as e:   # noqa: BLE001 — collected, reported
            with errs_lock:
                errs.append(f"thread {i}: {type(e).__name__}: {e}")

    ts = [threading.Thread(target=wrap, args=(i,), daemon=True)
          for i in range(n_threads)]
    for t in ts:
        t.start()
    for t in ts:
        t.join(timeout=120)
    if any(t.is_alive() for t in ts):
        errs.append("threads wedged past 120s join timeout")
    return errs


# ---------------------------------------------------------------------------
# host-only hammers
# ---------------------------------------------------------------------------

def hammer_journal_extend_reap(iters: int) -> list:
    from paddle_tpu.inference.router import _ReqJournal
    violations = []
    n_extenders = 3
    for it in range(iters):
        want = [(7 * i + it) % 251 for i in range(64)]
        j = _ReqJournal(prompt=[1, 2, 3], max_new=len(want), eos=None,
                        seed=0, rid=f"race-{it}")
        done = [0]
        done_lock = threading.Lock()

        def run(i):
            if i == 0:
                # the reaper: relentless failover-shaped snapshots
                # while extends land
                while True:
                    body = j.synthesize_body()
                    got = body["tokens"][3:3 + body["tokens_generated"]]
                    if got != want[:len(got)]:
                        violations.append(
                            f"iter {it}: torn snapshot {got[:8]}...")
                        return
                    j.complete()
                    j.size()
                    with done_lock:
                        if done[0] >= n_extenders:
                            return
            else:
                # extender threads: every thread replays the SAME
                # greedy stream in overlapping blocks — a primary plus
                # hedges re-sending verified prefixes (the merge is
                # first-writer-wins, so all interleavings are legal)
                base = 0
                while base < len(want):
                    k = 1 + (i + base) % 4
                    if not j.extend(base, want[base:base + k],
                                    f"rep{i}"):
                        violations.append(
                            f"iter {it}: consistent extend refused "
                            f"at base {base} (rep{i})")
                        break
                    base += k
                with done_lock:
                    done[0] += 1

        violations.extend(_barrier_run(1 + n_extenders, run))
        with j.cond:
            if j.tokens != want:
                violations.append(
                    f"iter {it}: journal diverged "
                    f"({len(j.tokens)}/{len(want)} tokens)")
            if j.mismatched:
                violations.append(f"iter {it}: mismatch flag raised "
                                  "on consistent extends")
    return violations


def hammer_qos_admit_shed(iters: int) -> list:
    from paddle_tpu.inference.router import _QosScheduler
    violations = []
    cap = 3
    for it in range(iters):
        qos = _QosScheduler(capacity=cap, queue_limit=4,
                            starvation_s=0.5)
        peak = [0]
        peak_lock = threading.Lock()

        def worker(i):
            tenant = f"t{i % 3}"
            qcls = ("interactive", "standard", "batch")[i % 3]
            for _ in range(20):
                verdict, _retry = qos.try_acquire(tenant, qcls,
                                                  timeout=5.0)
                if verdict != "admitted":
                    continue     # truthful shed/timeout under load
                snap = qos.snapshot()
                with peak_lock:
                    peak[0] = max(peak[0], snap["inflight"])
                time.sleep(0.0005)
                qos.release(tenant, qcls, tokens=3)

        violations.extend(_barrier_run(8, worker))
        snap = qos.snapshot()
        if snap["inflight"] != 0:
            violations.append(f"iter {it}: {snap['inflight']} inflight "
                              "after full drain")
        if peak[0] > cap:
            violations.append(f"iter {it}: inflight peaked {peak[0]} "
                              f"> capacity {cap}")
    return violations


def hammer_metrics_scrape_record(iters: int) -> list:
    from paddle_tpu.obs import metrics as m
    violations = []
    per_writer = 200
    for it in range(iters):
        reg = m.Registry()
        ctr = reg.counter("rh_ops_total", "race hunt", labels=("w",))
        hist = reg.histogram("rh_ms", "race hunt", labels=("w",))

        def worker(i):
            if i < 2:            # scrapers
                for _ in range(40):
                    m.parse_text(reg.render())   # must always parse
                return
            w = f"w{i}"
            for k in range(per_writer):
                ctr.inc(w=w)
                hist.observe(float(k % 7), w=w)

        violations.extend(_barrier_run(6, worker))
        for i in range(2, 6):
            got = ctr.value(w=f"w{i}")
            if got != per_writer:
                violations.append(f"iter {it}: counter w{i} = {got} "
                                  f"!= {per_writer} (lost update)")
    return violations


# ---------------------------------------------------------------------------
# jax hammers (a real engine, tiny model)
# ---------------------------------------------------------------------------

def _tiny_engine():
    import paddle_tpu as paddle
    from paddle_tpu.inference.engine import ContinuousBatchingEngine
    from paddle_tpu.models import GPTForCausalLM, gpt_tiny
    paddle.seed(7)
    model = GPTForCausalLM(gpt_tiny())
    model.eval()
    return ContinuousBatchingEngine(
        model, slots=4, max_len=64, cache_dtype="float32",
        prefill_buckets=(8,), tick_tokens=4, max_queue=16)


def hammer_engine_submit_cancel(iters: int) -> list:
    import numpy as np
    from paddle_tpu.inference.engine import (EngineOverloaded,
                                             RequestCancelled)
    violations = []
    eng = _tiny_engine()
    submitted = 0       # successful submits, cumulative (engine reused)
    try:
        for it in range(iters):
            futs: dict = {}
            futs_lock = threading.Lock()

            def worker(i):
                rng = np.random.RandomState(100 * it + i)
                for k in range(6):
                    rid = f"rh-{it}-{i}-{k}"
                    prompt = rng.randint(0, 250, (5,)).astype("int64")
                    try:
                        f = eng.submit(prompt, max_new_tokens=4,
                                       request_id=rid, seed=0)
                    except EngineOverloaded:
                        continue      # truthful shed under the storm
                    with futs_lock:
                        futs[rid] = f
                    if (i + k) % 2:
                        eng.cancel(rid)      # race cancel vs tick
                    eng.stats()              # reader-thread pressure

            violations.extend(_barrier_run(4, worker))
            submitted += len(futs)
            for rid, f in futs.items():
                try:
                    f.result(timeout=60)
                except RequestCancelled:
                    pass
                except Exception as e:   # noqa: BLE001
                    violations.append(
                        f"{rid}: {type(e).__name__}: {e}")
            st = eng.stats()
            if st["active"] or st["queued"]:
                violations.append(
                    f"iter {it}: engine failed to drain "
                    f"(active={st['active']} queued={st['queued']})")
            # every submitted request must land in EXACTLY one of
            # completed / cancelled — a miss means a leaked slot or a
            # double-retired request
            if st["completed"] + st["cancelled"] != submitted:
                violations.append(
                    f"iter {it}: conservation broke — submitted="
                    f"{submitted} completed={st['completed']} "
                    f"cancelled={st['cancelled']}")
    finally:
        eng.stop()
    return violations


def hammer_warmup_concurrent(iters: int) -> list:
    import numpy as np
    violations = []
    for it in range(max(1, iters // 2)):
        eng = _tiny_engine()
        try:
            violations.extend(
                _barrier_run(3, lambda i: eng.warmup(store=None)))
            if not eng._warmed:
                violations.append(f"iter {it}: warmup raced itself "
                                  "to an unwarmed engine")
            out = eng.generate(
                np.arange(5, dtype="int64"), max_new_tokens=3)
            if out.shape[0] != 5 + 3:
                violations.append(f"iter {it}: post-warmup generate "
                                  f"shape {tuple(out.shape)}")
        finally:
            eng.stop()
    return violations


# ---------------------------------------------------------------------------

def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--hammers", default=None,
                    help=f"comma list from {','.join(ALL_HAMMERS)}")
    ap.add_argument("--host-only", action="store_true",
                    help="only the hammers that never import jax")
    ap.add_argument("--iters", type=int, default=3,
                    help="fuzz rounds per hammer (default 3)")
    ap.add_argument("--json", default=None,
                    help="also write the full record to this path")
    args = ap.parse_args()

    wanted = list(HOST_HAMMERS if args.host_only else ALL_HAMMERS)
    if args.hammers:
        wanted = [h.strip() for h in args.hammers.split(",")
                  if h.strip()]
        bad = set(wanted) - set(ALL_HAMMERS)
        if bad:
            ap.error(f"unknown hammers {sorted(bad)}; "
                     f"valid: {list(ALL_HAMMERS)}")
        if args.host_only:
            wanted = [h for h in wanted if h in HOST_HAMMERS]

    if any(h in JAX_HAMMERS for h in wanted):
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        os.environ.setdefault(
            "JAX_COMPILATION_CACHE_DIR",
            os.path.join(ROOT, ".cache", "jax_ci_cpu"))
        os.environ.setdefault(
            "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "1")

    from paddle_tpu.distributed import resilience  # noqa: F401 —
    # imported so the lock_hold fault site is reachable from
    # InstrumentedLock.release under PADDLE_TPU_FAULT_SITES
    from paddle_tpu.obs import locks

    locks.set_lock_san(True)
    san = locks.reset_sanitizer()
    san._watchdog_interval = 0.5
    old_interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)

    record: dict = {"version": 1, "switch_interval": 1e-5,
                    "hammers": {}, "violations": []}
    try:
        for name in wanted:
            fn = globals()[f"hammer_{name}"]
            t0 = time.perf_counter()
            try:
                v = fn(args.iters)
            except Exception as e:   # harness crash, not a finding
                import traceback
                traceback.print_exc(file=sys.stderr)
                print(json.dumps({"error": f"{name}: "
                                  f"{type(e).__name__}: {e}"}))
                return 2
            dt = time.perf_counter() - t0
            record["hammers"][name] = {
                "iters": args.iters, "seconds": round(dt, 2),
                "violations": v}
            record["violations"].extend(f"{name}: {x}" for x in v)
            print(f"[{'FAIL' if v else ' ok '}] {name:22s} "
                  f"{dt:6.2f}s  {len(v)} violation(s)",
                  file=sys.stderr)
    finally:
        sys.setswitchinterval(old_interval)
        locks.set_lock_san(None)
        san.stop_watchdog()

    snap = san.snapshot()
    record["sanitizer"] = snap
    if snap["cycle_artifacts"]:
        record["violations"].append(
            "sanitizer: lock-order cycle artifact(s) "
            f"{snap['cycle_artifacts']}")
    if snap["deadlock_artifacts"]:
        record["violations"].append(
            "sanitizer: deadlock artifact(s) "
            f"{snap['deadlock_artifacts']}")
    record["gate"] = "fail" if record["violations"] else "pass"

    from paddle_tpu.analysis import terminal_record, write_report_artifact
    write_report_artifact(args.json, record)
    for v in record["violations"]:
        print(f"VIOLATION: {v}", file=sys.stderr)
    print(terminal_record(record, ("version", "gate", "violations",
                                   "sanitizer")))
    return 1 if record["violations"] else 0


if __name__ == "__main__":
    sys.exit(main())
