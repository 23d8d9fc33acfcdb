#!/usr/bin/env python
"""CI runner: pytest with flaky quarantine, retries, and optional
trace-based line coverage.

Parity role: the reference's test tooling (tools/get_quick_disable_lt.py
flaky quarantine, tools/coverage/, paddle_build.sh test stage).

Usage:
    python tools/ci.py                 # fast profile (slow-marked skipped)
    python tools/ci.py --quick         # core-correctness subset (<5 min)
    python tools/ci.py --full          # everything incl. slow marks
    python tools/ci.py --coverage      # + stdlib-trace line coverage
    python tools/ci.py --retries 2     # re-run failures up to 2x

Wall-time reality: this environment has ONE cpu core (nproc=1), so the
reference's parallel test grouping (tools/group_case_for_parallel.py)
cannot buy anything — profiles cut WORK instead. Measured 2026-07-30:
full 24:40, fast 12:50 warm, quick targets <5:00. Per-test wall-clock
limits live in tests/conftest.py (default 300s, marker-overridable) so
one hung test cannot eat the budget.

Quarantined tests live in tools/flaky_quarantine.txt (one pytest nodeid
or substring per line, '#' comments). They are deselected from the main
run and executed afterwards in best-effort mode (failures reported but
non-fatal), the same policy as the reference's disabled-list.
"""
from __future__ import annotations

import argparse
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
QUARANTINE = os.path.join(ROOT, "tools", "flaky_quarantine.txt")

# --quick: the core-correctness slice — tensor/autograd/nn/optimizer
# semantics, the jit engines, collectives + hybrid parallelism, the
# Pallas kernel, and the 2-process world. Breadth (model zoo, vision
# ops, datasets, long tail) belongs to the fast/full profiles.
QUICK_FILES = [
    "tests/test_tensor_ops.py", "tests/test_autograd.py",
    "tests/test_nn.py", "tests/test_optimizer.py", "tests/test_jit.py",
    "tests/test_distributed.py", "tests/test_pipeline.py",
    "tests/test_flash_kernel.py", "tests/test_multihost.py",
    "tests/test_zero_accumulation.py", "tests/test_api_surface.py",
    "tests/test_op_numerics.py", "tests/test_functional_numerics.py",
    "tests/test_incubate_geometric.py", "tests/test_gpt_scan_layers.py",
    "tests/test_tpu_lowering.py", "tests/test_chip_smoke.py",
    "tests/test_checkpoint_resume_zero3.py",
    "tests/test_quickstart_parity.py",
    # serving engine: continuous batching is a core-correctness surface
    # (greedy token-identity + the no-recompile guarantee)
    "tests/test_engine.py",
    # paged KV cache + shared-prefix reuse (ISSUE 9): page allocator /
    # prefix-trie units + paged-engine token-identity, prefix-skips-
    # prefill, zero-recompile and cache_exhausted shed contract
    "tests/test_paged_engine.py",
    # speculative decoding (ISSUE 13): n-gram/draft proposers, the
    # batched verify-k program's bitwise token identity (f32/int8,
    # slot/paged), zero-recompile under k/acceptance drift, and the
    # /generate accounting fields
    "tests/test_speculative.py",
    # fused K-step train loop: scanned-vs-sequential bitwise identity +
    # the 2-programs-per-epoch trace-counter bound
    "tests/test_scan_train.py",
    # static analyzer: hazard-class detection must stay exact
    "tests/test_analysis.py",
    # program registry / AOT warmup / executable store: warmup
    # idempotence + store invalidation + the warming->ready contract
    "tests/test_compilation.py",
    # serving tier: health-aware routing, kill -9 recovery, store-warm
    # rolling restart (0-compile successors), truthful tier 503s
    "tests/test_router.py",
    # observability: metrics registry semantics, request-id -> phase
    # spans, flight-recorder crash dumps, tier metric aggregation
    "tests/test_obs.py",
    # self-healing supervisor (ISSUE 11): rollback-on-divergence is
    # bitwise, preemption requeues + resumes flaglessly, retention GC
    # never touches the last verified checkpoint, kill -9 respawn
    "tests/test_supervisor.py",
    # topology-elastic checkpoints (ISSUE 12): layout manifest stamped
    # per checkpoint, 8->4->8 / ZeRO-stage / scan-K reshard-on-restore
    # bitwise, corrupt shards NAMED per leaf + supervisor fall-back,
    # killed reshard leaves the checkpoint untouched
    "tests/test_elastic_checkpoint.py",
    # measured runtime profiling (ISSUE 14): trace parser + measured<->
    # modeled join + CPU degrade from checked-in fixtures (zero
    # compiles), the dispatch-ratchet/anchor gate semantics, one live
    # profiled registry program, and the efficiency gauges
    "tests/test_runtime_profile.py",
    # quantized ZeRO collectives (ISSUE 17): RS/AG wire round-trips
    # (padded tails, block edges, integer exactness) + the train-step
    # knob — fp32 bitwise, bf16/int8 drift bounds, zero-recompile
    # flips, stage-3 gather chain/schedule, sharded optimizer state
    "tests/test_quantized_allreduce.py",
    "tests/test_quantized_trainstep.py",
    # tpurace concurrency tooling (ISSUE 18): lock-discipline lint on
    # fixture snippets, lock-sanitizer histograms + cycle/deadlock
    # artifacts, race_hunt host-hammer smoke — zero device work
    "tests/test_concurrency.py",
    # fused Pallas kernel library (ISSUE 19): interpret-mode identity
    # of fused CE / cache-write / mega-decode vs the unfused chains
    # they replace, incl. bf16, padded-vocab tails, int8 dict caches,
    # paged gating, GQA and pos corners — plus the env-knob dispatch
    "tests/test_kernels.py",
    # tensor-parallel serving slice (ISSUE 20): tp=2/4 greedy token
    # identity vs the single-chip engine (slot/paged x f32/int8 x
    # plain/speculative), zero-recompile drift, stacked paged block
    # tables under scan_layers, fused-knob TP fallback, registry
    # completeness, and a live 2-replica tier of tp=2 slices
    "tests/test_tp_engine.py",
]


def _run_chaos_smoke(env) -> int:
    """Chaos smoke (ISSUE 11): tools/chaos_train.py --smoke drives a
    supervised run through an injected NaN storm, a wedged step, a
    synthetic preemption (+ flagless resume), and a poison-batch
    loss spike with a skipped window — in-process only, asserting
    bitwise recovery and ptpu_supervisor_* visibility."""
    print("\n=== chaos smoke (self-healing supervisor) ===")
    return subprocess.run(
        [sys.executable, os.path.join("tools", "chaos_train.py"),
         "--smoke"],
        cwd=ROOT, env=env).returncode


def _run_elastic_smoke(env) -> int:
    """Elastic smoke (ISSUE 12): tools/chaos_train.py --elastic drives
    a ZeRO-3 supervised run through an 8->4->8 virtual-device
    preempt/reshard/resume chain (bitwise vs a clean run at the new
    topology) plus a killed-reshard retry — the topology-elastic
    checkpoint guarantee, in-process only. The tool re-execs itself
    onto the 8-virtual-device CPU mesh WITHOUT the persistent compile
    cache (multi-device reload hazard)."""
    print("\n=== elastic smoke (topology-elastic checkpoints) ===")
    return subprocess.run(
        [sys.executable, os.path.join("tools", "chaos_train.py"),
         "--elastic"],
        cwd=ROOT, env=env).returncode


def _run_obs_smoke(env) -> int:
    """Obs smoke (ISSUE 8): tools/trace_tool.py --self-test drives a
    LIVE tiny server — /metrics scraped twice and parsed (series must
    be monotonic), /healthz freshness token, and POST /admin/trace
    resolving a request id to its queue-wait/prefill/decode spans —
    plus the span/ring/export and metrics render->parse round trips.
    The quick-path guarantee that the telemetry surface stays up."""
    print("\n=== obs smoke (metrics scrape + trace self-test) ===")
    return subprocess.run(
        [sys.executable, os.path.join("tools", "trace_tool.py"),
         "--self-test"],
        cwd=ROOT, env=env).returncode


def _run_tpulint(env, update_baseline=False) -> int:
    """tpulint gate: static analysis of the real compiled programs +
    codebase vs tools/tpulint_baseline.json (PR 3). Nonzero when a NEW
    hazard (scatter on the decode path, dropped donation, retrace-per-
    call jit, ...) appears — same ratchet policy as the quarantine
    list, but machine-diffed. Accept an intentional finding with
    `python tools/ci.py --tpulint --update-baseline` after review."""
    print("\n=== tpulint static-analysis gate ===")
    cmd = [sys.executable, os.path.join("tools", "tpulint.py")]
    if update_baseline:
        cmd.append("--update-baseline")
    return subprocess.run(cmd, cwd=ROOT, env=env).returncode


def _run_tpurace(env, update_baseline=False) -> int:
    """tpurace gate: static lock-discipline lint of the tree vs
    tools/tpurace_baseline.json (ISSUE 18). Nonzero when a NEW
    concurrency hazard (guarded attr touched outside its lock, static
    lock-order cycle, blocking call under a lock, ...) appears, or a
    must_stay_clean anchor — the engine tick loop, the request
    journal, the metrics registry, the compilation store, concurrent
    warmup — regresses. Pure AST, no jax: runs in ~2 s. Accept an
    intentional finding with `python tools/ci.py --tpurace
    --update-baseline` after review."""
    print("\n=== tpurace lock-discipline gate ===")
    cmd = [sys.executable, os.path.join("tools", "tpurace.py")]
    if update_baseline:
        cmd.append("--update-baseline")
    return subprocess.run(cmd, cwd=ROOT, env=env).returncode


def _run_race_hunt(env) -> int:
    """race_hunt smoke: the dynamic half of the tpurace gate —
    schedule-fuzzed hammers (journal extend vs reap, QoS admit vs
    shed, metrics scrape vs record, engine submit/cancel vs tick,
    concurrent warmup) under a 10us switch interval with the lock
    sanitizer on. Nonzero on any invariant violation or sanitizer
    cycle/deadlock artifact."""
    print("\n=== race_hunt schedule-fuzzing smoke ===")
    return subprocess.run(
        [sys.executable, os.path.join("tools", "race_hunt.py"),
         "--iters", "2"],
        cwd=ROOT, env=env).returncode


def _run_tpucost(env, update_baseline=False) -> int:
    """tpucost gate: static fusion/HBM roofline inventory of the real
    compiled programs vs tools/tpucost_baseline.json (PR 6). Nonzero
    when a ratcheted budget (HBM bytes, kernel count, matmul-FLOP
    share) or a hand-set anchor (decode-tick HBM bound, train-step
    matmul floor) regresses. Re-pin after review with
    `python tools/ci.py --tpucost --update-baseline`."""
    print("\n=== tpucost fusion/HBM roofline gate ===")
    cmd = [sys.executable, os.path.join("tools", "tpucost.py")]
    if update_baseline:
        cmd.append("--update-baseline")
    return subprocess.run(cmd, cwd=ROOT, env=env).returncode


def _run_tpuprof(env, update_baseline=False) -> int:
    """tpuprof gate: MEASURED dispatch-time + kernel-attribution
    inventory of the real compiled programs vs
    tools/tpuprof_baseline.json (ISSUE 14). Nonzero when a program's
    measured dispatch median blows past its pinned budget * tolerance,
    or (on a device-plane backend) a measured anchor — train-step
    matmul time share, decode measured-vs-roofline — breaks. Re-pin
    after review with `python tools/ci.py --tpuprof
    --update-baseline`. Not appended to --quick/--full automatically:
    it EXECUTES every program under the profiler, and wall-time gates
    belong where wall time is quiet (run it by hand when touching a
    hot program)."""
    print("\n=== tpuprof measured-runtime gate ===")
    cmd = [sys.executable, os.path.join("tools", "tpuprof.py")]
    if update_baseline:
        cmd.append("--update-baseline")
    return subprocess.run(cmd, cwd=ROOT, env=env).returncode


def _run_warmup(env) -> int:
    """Prime the persistent executable store + the warm jax compile
    cache from the ProgramRegistry (tools/warmup.py) BEFORE the test
    profiles run: one `ci.py --warmup --quick` on a fresh machine
    compiles the real programs once (the same set the tpulint/tpucost
    gates rebuild — they share the registry), and every later GATE and
    warm-start serving run loads them. The pytest runs themselves stay
    off the persistent cache (multi-device reload hazard — see the
    cache_env note in main). Warmup failures are non-fatal: tests
    lazily compile whatever is missing."""
    print("=== program warmup (registry -> executable store) ===")
    return subprocess.run(
        [sys.executable, os.path.join("tools", "warmup.py")],
        cwd=ROOT, env=env).returncode


def _quarantine():
    if not os.path.exists(QUARANTINE):
        return []
    out = []
    for line in open(QUARANTINE):
        line = line.split("#", 1)[0].strip()
        if line:
            out.append(line)
    return out


def _run_pytest(extra, env=None, default_target=True):
    cmd = [sys.executable, "-m", "pytest", "-q"]
    if default_target:
        cmd.append("tests/")
    cmd += extra
    return subprocess.run(cmd, cwd=ROOT, env=env).returncode


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--coverage", action="store_true")
    ap.add_argument("--retries", type=int, default=0)
    ap.add_argument("--full", action="store_true",
                    help="include tests marked slow (whole-step "
                         "compiles for the described chip, chip_smoke.py "
                         "rehearsals); the default fast "
                         "profile skips them — this machine has ONE cpu "
                         "core, so wall time is cut by cutting work, not "
                         "by sharding")
    ap.add_argument("--quick", action="store_true",
                    help="core-correctness subset only (<5 min target)")
    ap.add_argument("--tpulint", action="store_true",
                    help="run ONLY the tpulint static-analysis gate")
    ap.add_argument("--tpucost", action="store_true",
                    help="run ONLY the tpucost fusion/HBM roofline gate")
    ap.add_argument("--tpurace", action="store_true",
                    help="run ONLY the tpurace lock-discipline gate "
                         "(static concurrency lint vs "
                         "tools/tpurace_baseline.json)")
    ap.add_argument("--tpuprof", action="store_true",
                    help="run ONLY the tpuprof measured-runtime gate "
                         "(executes every registry program under the "
                         "profiler — dispatch-time ratchet + measured "
                         "anchors vs tools/tpuprof_baseline.json)")
    ap.add_argument("--update-baseline", action="store_true",
                    help="with --tpucost/--tpulint/--tpuprof/"
                         "--tpurace: re-pin that gate's baseline from "
                         "this run (tpucost/tpuprof anchors and "
                         "tpulint/tpurace must_stay_clean entries "
                         "preserved) — the review-then-accept ratchet "
                         "flow")
    ap.add_argument("--warmup", action="store_true",
                    help="prime the executable store + warm jax cache "
                         "(tools/warmup.py) before the tests — "
                         "self-services the warm-cache dependency the "
                         "tier-1 budget assumes; alone = ONLY warm up")
    ap.add_argument("--no-tpulint", action="store_true",
                    help="skip the tpulint gate that --quick/--full "
                         "append after the tests")
    ap.add_argument("--no-tpucost", action="store_true",
                    help="skip the tpucost gate that --quick/--full "
                         "append after the tests")
    ap.add_argument("--no-tpurace", action="store_true",
                    help="skip the tpurace lock-discipline gate and "
                         "the race_hunt schedule-fuzzing smoke that "
                         "--quick/--full append after the tests")
    ap.add_argument("--no-obs-smoke", action="store_true",
                    help="skip the obs /metrics + trace self-test "
                         "smoke that --quick/--full append after the "
                         "tests")
    ap.add_argument("--no-chaos-smoke", action="store_true",
                    help="skip the self-healing chaos smoke "
                         "(tools/chaos_train.py --smoke) that "
                         "--quick/--full append after the tests")
    ap.add_argument("--no-elastic-smoke", action="store_true",
                    help="skip the topology-elastic chaos smoke "
                         "(tools/chaos_train.py --elastic) that "
                         "--quick/--full append after the tests")
    ap.add_argument("-k", default=None)
    args = ap.parse_args()
    if args.full and args.quick:
        ap.error("--full and --quick are mutually exclusive")

    quarantined = _quarantine()
    # nodeids/paths use --deselect; substrings fold into one -k
    # "not a and not b" expression (pytest keeps only the last -k flag)
    node_q = [q for q in quarantined if "::" in q or q.endswith(".py")]
    substr_q = [q for q in quarantined if q not in node_q]
    extra = ["--runslow"] if args.full else []
    k_parts = []
    if args.k:
        k_parts.append(f"({args.k})")
    k_parts += [f"not {q}" for q in substr_q]
    if k_parts:
        extra += ["-k", " and ".join(k_parts)]
    deselect = []
    for q in node_q:
        deselect += ["--deselect", q]

    env = dict(os.environ)
    if args.coverage:
        # trace-based coverage collected by tests/conftest.py (no
        # external deps in this image); report written at session end
        env["PADDLE_TPU_COVERAGE"] = "1"
    # Warm persistent XLA compile cache for the TOOL subprocesses only
    # (warmup + the tpulint/tpucost gates — compile-heavy, measured ~2x
    # warm). The PYTEST runs stay cache-free like tests/conftest.py's
    # raw path: reloading a cached MULTI-DEVICE CPU program aborts the
    # process (the cpu_aot_loader hazard paddle_tpu/__init__.py
    # documents — measured 2026-08-03 on the ZeRO-3/pipeline tests once
    # the shared dir held multi-device entries from earlier runs), and
    # a crashed suite costs more than the recompiles it saves.
    cache_env = dict(env)
    cache_env.setdefault("JAX_COMPILATION_CACHE_DIR",
                         os.path.join(ROOT, ".cache", "jax_ci_cpu"))
    cache_env.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS",
                         "1")

    if args.tpulint:
        return _run_tpulint(cache_env, args.update_baseline)
    if args.tpucost:
        return _run_tpucost(cache_env, args.update_baseline)
    if args.tpuprof:
        return _run_tpuprof(cache_env, args.update_baseline)
    if args.tpurace:
        # plain env: pure AST, never compiles (no cache dir to offer)
        return _run_tpurace(env, args.update_baseline)
    if args.update_baseline:
        ap.error("--update-baseline only applies with --tpulint, "
                 "--tpucost, --tpuprof or --tpurace (a full test run "
                 "must never silently re-pin a gate baseline)")
    if args.warmup:
        warm_rc = _run_warmup(cache_env)
        if not (args.quick or args.full or args.k or args.coverage):
            return warm_rc       # --warmup alone: just prime and exit
        if warm_rc != 0:
            print("warmup step failed (non-fatal: tests compile lazily)")

    # --quick keeps its file scope through retries: an empty last-failed
    # cache (collection error) must not balloon a retry into the full
    # fast suite on this 1-core machine
    target = QUICK_FILES if args.quick else []
    rc = _run_pytest(target + extra + deselect, env,
                     default_target=not args.quick)
    attempt = 0
    while rc != 0 and attempt < args.retries:
        attempt += 1
        print(f"\n=== retry {attempt}/{args.retries} (failed tests only) ===")
        rc = _run_pytest(target + extra + deselect + ["--last-failed"],
                         env, default_target=not args.quick)

    if quarantined:
        print("\n=== quarantined tests (best-effort, non-fatal) ===")
        # node ids and -k substrings need separate invocations: a -k
        # expression would also filter the explicitly listed node ids
        bad = False
        if node_q:
            bad |= _run_pytest(list(node_q), env,
                               default_target=False) not in (0, 5)
        if substr_q:
            bad |= _run_pytest(["tests/", "-k", " or ".join(substr_q)],
                               env, default_target=False) not in (0, 5)
        if bad:
            print("quarantined tests still failing (non-fatal)")

    # static-analysis gates ride after the test gates in the blocking
    # profiles (tpulint ~15 s warm — trace/lower only; tpucost
    # additionally compiles every registry program, which the warm
    # persistent cache turns into loads)
    if (args.quick or args.full) and not args.no_tpulint:
        lint_rc = _run_tpulint(cache_env)
        rc = rc or lint_rc
    if (args.quick or args.full) and not args.no_tpucost:
        cost_rc = _run_tpucost(cache_env)
        rc = rc or cost_rc
    if (args.quick or args.full) and not args.no_tpurace:
        # static half plain env (pure AST); dynamic half cache_env —
        # the engine hammers compile the tiny-GPT programs and the
        # single-device entries are safe to share
        race_rc = _run_tpurace(env)
        rc = rc or race_rc
        hunt_rc = _run_race_hunt(cache_env)
        rc = rc or hunt_rc
    if (args.quick or args.full) and not args.no_obs_smoke:
        obs_rc = _run_obs_smoke(cache_env)
        rc = rc or obs_rc
    if (args.quick or args.full) and not args.no_chaos_smoke:
        chaos_rc = _run_chaos_smoke(cache_env)
        rc = rc or chaos_rc
    if (args.quick or args.full) and not args.no_elastic_smoke:
        # plain env (not cache_env): the tool strips the persistent
        # cache itself, but don't even offer it the multi-device trap
        elastic_rc = _run_elastic_smoke(env)
        rc = rc or elastic_rc
    return rc


if __name__ == "__main__":
    sys.exit(main())
