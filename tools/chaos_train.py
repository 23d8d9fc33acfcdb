#!/usr/bin/env python
"""Chaos gate for the self-healing training supervisor (ISSUE 11).

Drives ONE deterministic tiny trainer through every recovery path the
TrainSupervisor promises and asserts the runs actually heal:

  baseline   unfaulted supervised run (the bitwise comparison object)
  nan_storm  injected train_step_nan x3 -> rollback -> final state
             BITWISE-identical to baseline + flight artifact
  wedge      injected step_hang under a step deadline -> StepTimeout
             rollback -> bitwise + flight artifact
  preempt    injected preempt_signal -> grace checkpoint + requeue
             outcome, then flagless auto-resume -> bitwise
  sigterm    REAL SIGTERM to a supervisor child process mid-epoch ->
             requeue exit code 75, relaunch of the SAME command line
             resumes flaglessly -> bitwise            (full run only)
  kill9      kill -9 of the subprocess-mode trainer child mid-epoch ->
             crash-loop-bounded respawn from the last atomic
             checkpoint -> bitwise                    (full run only)
  skip       a FINITE poison batch -> loss-spike rollback, retry,
             then the poison window is skipped; final state equals a
             clean run told to skip the same window (the
             documented-bounded-drift case, pinned exactly)
  elastic    topology-elastic checkpoints (ISSUE 12): a ZeRO-3 run on
             8 virtual devices (dp4 x sharding2) is preempted, resumes
             on the 4-device slice (dp2 x sharding2, RESHARDING the
             checkpoint), is preempted again, and grows back to 8 —
             the shrink/grow chain ends BITWISE-identical to a clean
             run executed at the new topology from the same step, and
             every reshard is visible (manifest incident + counter)
  reshard_kill  an injected ckpt_reshard fault kills the first resume
             attempt MID-reshard: the checkpoint directory must be
             byte-identical after the kill, the retry must succeed
             (one restart-budget strike), and the run completes

Every phase's recovery must be visible: manifest incident records +
ptpu_supervisor_* counters + a flight-recorder artifact per
watchdog-detected incident.

Usage:
    python tools/chaos_train.py            # full gate (spawns children)
    python tools/chaos_train.py --smoke    # in-process phases only
    python tools/chaos_train.py --elastic  # ONLY the elastic phases

The terminal stdout line is one JSON record ({"error": ...} + nonzero
exit on any unhealed run).
"""
from __future__ import annotations

import json
import os
import signal
import subprocess
import sys
import tempfile
import threading
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
SELF = os.path.abspath(__file__)

STEP_SLEEP = os.environ.get("PTPU_CHAOS_STEP_SLEEP", "0.2")

# The elastic phases need the 8-virtual-device CPU mesh, which must be
# in the environment before jax is first imported — re-exec with it
# (tools/tpulint.py pattern)
_WANT_FLAG = "--xla_force_host_platform_device_count=8"
_REEXEC_MARK = "_PADDLE_TPU_CHAOS_REEXEC"


def _env_ok() -> bool:
    # a persistent compile cache also forces the re-exec (which strips
    # it): reloading cached MULTI-device CPU programs hard-aborts
    return (os.environ.get(_REEXEC_MARK) == "1"
            or (os.environ.get("JAX_PLATFORMS") == "cpu"
                and _WANT_FLAG in os.environ.get("XLA_FLAGS", "")
                and not os.environ.get("JAX_COMPILATION_CACHE_DIR")))


def _reexec():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") + " " + _WANT_FLAG).strip()
    # NO persistent compile cache on this CPU-only run (the platform
    # is forced to cpu just above): the elastic phases compile
    # MULTI-device CPU programs, and reloading those from a shared
    # cache dir hard-aborts the process (the cpu_aot_loader hazard
    # tests/conftest.py and ci.py document). A chip run never strips
    # the variable.
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    env[_REEXEC_MARK] = "1"
    rc = subprocess.call([sys.executable] + sys.argv, env=env)
    sys.exit(rc)


# ---------------------------------------------------------------------------
# the one trainer every phase runs (children load it as file.py:fn)
# ---------------------------------------------------------------------------

class _Rows:
    def __init__(self, xs, ys):
        self.xs, self.ys = xs, ys

    def __len__(self):
        return len(self.xs)

    def __getitem__(self, i):
        return self.xs[i], self.ys[i]


def _build(poison_at=None):
    import numpy as np

    import paddle_tpu as paddle
    import paddle_tpu.nn as nn
    import paddle_tpu.nn.functional as F
    from paddle_tpu.hapi import Model
    from paddle_tpu.hapi.callbacks import Callback
    from paddle_tpu.io.dataloader import DataLoader

    paddle.seed(11)
    net = nn.Sequential(nn.Linear(8, 16), nn.Tanh(), nn.Linear(16, 8))
    model = Model(net)
    opt = paddle.optimizer.SGD(learning_rate=0.05,
                               parameters=net.parameters())
    model.prepare(optimizer=opt, loss=lambda o, y: F.mse_loss(o, y))
    rng = np.random.RandomState(5)
    xs = rng.randn(48, 8).astype("float32")
    ys = rng.randn(48, 8).astype("float32")
    if poison_at is not None:
        ys[poison_at * 4:(poison_at + 1) * 4] = 1e6
    loader = DataLoader(_Rows(xs, ys), batch_size=4, shuffle=False)

    sleep_s = float(os.environ.get("PTPU_TEST_STEP_SLEEP", "0") or 0)

    class SlowStep(Callback):
        def on_train_batch_end(self, step, logs=None):
            if sleep_s:
                time.sleep(sleep_s)

    return model, loader, {"epochs": 2, "verbose": 0,
                           "callbacks": [SlowStep()]}


def make_trainer():
    return _build()


def make_poisoned_trainer():
    return _build(poison_at=5)


def _build_elastic(degrees, zero_stage=3):
    """The elastic trainer: one deterministic hybrid-parallel (ZeRO)
    hapi model on an explicit mesh over a SLICE of the 8 virtual
    devices — the same weights train at every topology, so
    preempt/reshard/resume chains can be compared bitwise."""
    import numpy as np

    import paddle_tpu as paddle
    import paddle_tpu.distributed as dist
    import paddle_tpu.nn as nn
    import paddle_tpu.nn.functional as F
    from paddle_tpu.hapi import Model
    from paddle_tpu.io.dataloader import DataLoader

    dist.set_mesh(None)
    dist.init_mesh(degrees)
    paddle.seed(11)
    net = nn.Sequential(nn.Linear(8, 16), nn.Tanh(), nn.Linear(16, 8))
    model = Model(net)
    opt = paddle.optimizer.AdamW(learning_rate=0.01,
                                 parameters=net.parameters())
    model.prepare(optimizer=opt, loss=lambda o, y: F.mse_loss(o, y),
                  parallel={"zero_stage": zero_stage})
    rng = np.random.RandomState(5)
    xs = rng.randn(48, 8).astype("float32")
    ys = rng.randn(48, 8).astype("float32")
    loader = DataLoader(_Rows(xs, ys), batch_size=8, shuffle=False)
    return model, loader, {"epochs": 3, "verbose": 0}


def make_elastic_8():
    """8 virtual devices: dp4 x sharding2, ZeRO-3."""
    return _build_elastic({"dp": 4, "sharding": 2})


def make_elastic_4():
    """The 4-device slice a preempted pod gets back: dp2 x sharding2."""
    return _build_elastic({"dp": 2, "sharding": 2})


TOTAL_STEPS = 24        # 12 batches x 2 epochs
ELASTIC_STEPS = 18      # 6 batches x 3 epochs
POLICY = {"ckpt_every": 5, "max_to_keep": 3}
ELASTIC_POLICY = {"ckpt_every": 4, "max_to_keep": 3}


# ---------------------------------------------------------------------------
# harness plumbing
# ---------------------------------------------------------------------------

def _fast_backoff():
    from paddle_tpu.distributed.resilience import RetryPolicy
    return RetryPolicy(max_attempts=16, base_delay=0.0, jitter=0.0)


def _run_inprocess(d, factory=make_trainer, **policy):
    from paddle_tpu.distributed.supervisor import TrainSupervisor
    model, loader, kw = factory()
    kw.pop("callbacks", None)        # no step sleep for in-process runs
    sup = TrainSupervisor(model, loader, directory=d, fit_kwargs=kw,
                          backoff=_fast_backoff(),
                          **{**POLICY, **policy})
    return sup, sup.run()


def _run_elastic(d, factory, preempt_at=None, **policy):
    """One supervised life of the elastic trainer. ``preempt_at=N``
    lands the preemption signal at the N-th trained batch of THIS life
    (what a scheduler SIGTERM mid-run does, deterministically)."""
    from paddle_tpu.distributed.supervisor import TrainSupervisor
    from paddle_tpu.hapi.callbacks import Callback
    model, loader, kw = factory()
    kw = dict(kw)
    box = {}
    if preempt_at is not None:
        class PreemptAt(Callback):
            def __init__(self):
                self.n = 0

            def on_train_batch_end(self, step, logs=None):
                self.n += 1
                if self.n == preempt_at:
                    box["sup"]._note_preempt("elastic_preempt")

        kw["callbacks"] = [PreemptAt()]
    sup = TrainSupervisor(model, loader, directory=d, fit_kwargs=kw,
                          backoff=_fast_backoff(),
                          **{**ELASTIC_POLICY, **policy})
    box["sup"] = sup
    return sup, sup.run()


def _dir_snapshot(path):
    """(relpath, content-hash) of every file under a checkpoint dir —
    the "killed reshard left it BYTE-identical" comparison object
    (size alone would miss same-length in-place corruption)."""
    import hashlib
    out = []
    for root, _dirs, files in os.walk(path):
        for fn in sorted(files):
            full = os.path.join(root, fn)
            with open(full, "rb") as f:
                digest = hashlib.sha256(f.read()).hexdigest()
            out.append((os.path.relpath(full, path), digest))
    return sorted(out)


def _final_tree(d):
    from paddle_tpu.distributed import checkpoint as ckpt
    path = ckpt.latest_checkpoint(d)
    if path is None:
        raise AssertionError(f"no checkpoint landed in {d}")
    return ckpt.load_state_dict(path)


def _bitwise(a, b):
    import jax
    import numpy as np
    la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    return len(la) == len(lb) and all(
        np.array_equal(np.asarray(x), np.asarray(y))
        for x, y in zip(la, lb))


def _assert(cond, what):
    if not cond:
        raise AssertionError(what)


def _flight_artifacts(obs_dir, needle):
    try:
        return [f for f in os.listdir(obs_dir) if needle in f]
    except OSError:
        return []


def _child_env():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = ROOT + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
    env["PTPU_TEST_STEP_SLEEP"] = STEP_SLEEP
    return env


def _child_argv(d, factory="make_trainer"):
    spec = {"factory": f"{SELF}:{factory}", "policy": POLICY}
    return [sys.executable, "-m", "paddle_tpu.distributed.supervisor",
            "--child", "--dir", d, "--spec", json.dumps(spec)]


def _wait_ckpt(d, min_step, timeout=120.0):
    from paddle_tpu.distributed.checkpoint import list_checkpoints
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if any(s >= min_step for s, _ in list_checkpoints(d)):
            return True
        time.sleep(0.1)
    return False


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------

def phase_baseline(work):
    d = os.path.join(work, "baseline")
    _sup, r = _run_inprocess(d)
    _assert(r.outcome == "completed" and r.final_step == TOTAL_STEPS,
            f"baseline did not complete: {r.as_dict()}")
    return _final_tree(d), {"final_step": r.final_step}


def phase_nan_storm(work, base, obs_dir):
    from paddle_tpu.distributed.resilience import FaultInjector
    from paddle_tpu.distributed.supervisor import load_manifest
    d = os.path.join(work, "nan_storm")
    with FaultInjector({"train_step_nan": 3}):
        _sup, r = _run_inprocess(d, nan_limit=3)
    _assert(r.outcome == "completed" and r.rollbacks == 1,
            f"nan_storm not healed by one rollback: {r.as_dict()}")
    tree = _final_tree(d)
    _assert(_bitwise(tree["params"], base["params"]) and
            _bitwise(tree["opt"], base["opt"]),
            "nan_storm recovery drifted from the unfaulted run")
    m = load_manifest(d)
    _assert([i["kind"] for i in m["incidents"]] == ["nan_storm"],
            f"unexpected incidents: {m['incidents']}")
    flights = _flight_artifacts(obs_dir, "nan_storm")
    _assert(flights, "no flight-recorder artifact for the NaN storm")
    return {"rollbacks": r.rollbacks, "flight": flights[0]}


def phase_wedge(work, base, obs_dir):
    from paddle_tpu.distributed.resilience import FaultInjector
    d = os.path.join(work, "wedge")
    with FaultInjector({"step_hang": 1}, wedge_s=5.0):
        _sup, r = _run_inprocess(d, step_timeout=1.0)
    _assert(r.outcome == "completed" and r.rollbacks == 1,
            f"wedge not healed by one rollback: {r.as_dict()}")
    _assert(_bitwise(_final_tree(d)["params"], base["params"]),
            "wedge recovery drifted from the unfaulted run")
    flights = _flight_artifacts(obs_dir, "hang")
    _assert(flights, "no flight-recorder artifact for the wedged step")
    return {"rollbacks": r.rollbacks, "flight": flights[0]}


def phase_preempt(work, base):
    from paddle_tpu.distributed.resilience import FaultInjector
    from paddle_tpu.distributed.supervisor import REQUEUE_EXIT_CODE
    d = os.path.join(work, "preempt")
    with FaultInjector({"preempt_signal": 1}):
        _sup, r = _run_inprocess(d)
    _assert(r.outcome == "preempted" and
            r.exit_code == REQUEUE_EXIT_CODE,
            f"injected preemption did not requeue: {r.as_dict()}")
    _sup2, r2 = _run_inprocess(d)          # flagless auto-resume
    _assert(r2.outcome == "completed" and r2.final_step == TOTAL_STEPS,
            f"auto-resume did not complete: {r2.as_dict()}")
    _assert(_bitwise(_final_tree(d)["params"], base["params"]),
            "preempt-resume drifted from the unfaulted run")
    return {"requeue_code": r.exit_code, "resumed_to": r2.final_step}


def phase_skip_window(work):
    """The documented-bounded-drift case, pinned exactly: the faulted
    run's final state must equal a clean run that skipped the same
    window a priori."""
    from paddle_tpu.distributed.supervisor import load_manifest
    d = os.path.join(work, "skip")
    _sup, r = _run_inprocess(d, factory=make_poisoned_trainer,
                             spike_window=8, spike_z=6.0,
                             spike_min_points=4, retries_per_window=1)
    _assert(r.outcome == "completed" and r.skipped_steps > 0,
            f"poison run did not skip a window: {r.as_dict()}")
    m = load_manifest(d)
    windows = [tuple(w) for w in m["skipped_windows"]]
    model, loader, kw = make_poisoned_trainer()
    kw.pop("callbacks", None)
    model.fit(loader, skip_windows=windows, **kw)
    _assert(_bitwise(_final_tree(d)["params"], model._train_step.params),
            "skip-window recovery does not match the clean skip run")
    return {"skipped_windows": windows, "rollbacks": r.rollbacks}


def phase_sigterm(work, factory_base):
    from paddle_tpu.distributed.supervisor import (REQUEUE_EXIT_CODE,
                                                   load_manifest)
    d = os.path.join(work, "sigterm")
    proc = subprocess.Popen(_child_argv(d), env=_child_env(), cwd=ROOT,
                            stdout=subprocess.DEVNULL,
                            stderr=subprocess.STDOUT)
    try:
        _assert(_wait_ckpt(d, POLICY["ckpt_every"]),
                "no checkpoint before SIGTERM")
        proc.send_signal(signal.SIGTERM)
        rc = proc.wait(timeout=90)
    finally:
        if proc.poll() is None:
            proc.kill()
    _assert(rc == REQUEUE_EXIT_CODE,
            f"SIGTERM exit code {rc} != requeue {REQUEUE_EXIT_CODE}")
    # requeue: the SAME command, zero flags
    rc2 = subprocess.run(_child_argv(d), env=_child_env(), cwd=ROOT,
                         stdout=subprocess.DEVNULL,
                         stderr=subprocess.STDOUT, timeout=300).returncode
    _assert(rc2 == 0, f"flagless relaunch rc={rc2}")
    m = load_manifest(d)
    _assert(m["done"] and m["final_step"] == TOTAL_STEPS,
            f"resume did not finish: {m.get('final_step')}")
    _assert(_bitwise(_final_tree(d)["params"], factory_base["params"]),
            "SIGTERM resume drifted from the unfaulted run")
    return {"requeue_code": rc, "preemptions": m["preemptions"]}


def phase_kill9(work, factory_base):
    from paddle_tpu.distributed.supervisor import (TrainSupervisor,
                                                   load_manifest)
    d = os.path.join(work, "kill9")
    env = _child_env()
    sup = TrainSupervisor(
        factory=f"{SELF}:make_trainer", directory=d,
        subprocess_mode=True, restart_budget=3,
        backoff=_fast_backoff(),
        child_env={"JAX_PLATFORMS": "cpu",
                   "PYTHONPATH": env["PYTHONPATH"],
                   "PTPU_TEST_STEP_SLEEP": STEP_SLEEP},
        **POLICY)
    box = {}

    def run():
        try:
            box["result"] = sup.run()
        except BaseException as e:
            box["error"] = e

    t = threading.Thread(target=run, daemon=True)
    t.start()
    _assert(_wait_ckpt(d, POLICY["ckpt_every"]),
            "no checkpoint before kill -9")
    _assert(sup.child_pid is not None, "no trainer child pid")
    os.kill(sup.child_pid, signal.SIGKILL)
    t.join(timeout=300)
    _assert(not t.is_alive(), "supervisor wedged after kill -9")
    _assert("error" not in box, f"supervisor raised: {box.get('error')}")
    r = box["result"]
    _assert(r.outcome == "completed" and r.respawns >= 1,
            f"kill -9 not healed by respawn: {r.as_dict()}")
    m = load_manifest(d)
    _assert(_bitwise(_final_tree(d)["params"], factory_base["params"]) and
            _bitwise(_final_tree(d)["opt"], factory_base["opt"]),
            "kill -9 respawn drifted from the unfaulted run")
    return {"respawns": r.respawns,
            "crashes": [i["rc"] for i in m["incidents"]
                        if i["kind"] == "trainer_crash"]}


def phase_elastic(work):
    """Topology-elastic resume, the shrink/grow chain (ISSUE 12):
    preempt an 8-device ZeRO-3 run, resume it on a 4-device slice
    (reshard), preempt again, grow back to 8 (reshard) — and the whole
    chaotic chain must end BITWISE-identical to a clean run executed at
    the new topology from the same step."""
    import shutil

    from paddle_tpu.distributed import checkpoint as ckpt_mod
    from paddle_tpu.distributed import resilience as resil_mod
    from paddle_tpu.distributed.supervisor import (REQUEUE_EXIT_CODE,
                                                   load_manifest)
    d = os.path.join(work, "elastic")

    # leg 1: 8 virtual devices (dp4 x sharding2), preempted mid-run
    _s1, r1 = _run_elastic(d, make_elastic_8, preempt_at=6)
    _assert(r1.outcome == "preempted"
            and r1.exit_code == REQUEUE_EXIT_CODE,
            f"elastic leg 1 did not requeue: {r1.as_dict()}")

    # leg 2: flagless resume on the 4-device slice — reshards 8->4
    _s2, r2 = _run_elastic(d, make_elastic_4, preempt_at=4)
    _assert(r2.outcome == "preempted" and r2.reshards >= 1,
            f"elastic leg 2 did not reshard+requeue: {r2.as_dict()}")

    # the grow point: snapshot the directory for the clean comparator
    d_cmp = os.path.join(work, "elastic_cmp")
    shutil.copytree(d, d_cmp)
    resume_path = ckpt_mod.latest_checkpoint(d_cmp)
    _assert(resume_path is not None, "no checkpoint at the grow point")
    saved_layout = ckpt_mod.read_layout(resume_path)
    _assert(saved_layout and ckpt_mod._mesh_str(saved_layout)
            == "dp2xsharding2",
            f"grow-point checkpoint not stamped from the 4-device "
            f"slice: {saved_layout and ckpt_mod._mesh_str(saved_layout)}")

    # leg 3: grow back to 8 devices — reshards 4->8 and completes
    _s3, r3 = _run_elastic(d, make_elastic_8)
    _assert(r3.outcome == "completed"
            and r3.final_step == ELASTIC_STEPS and r3.reshards >= 1,
            f"elastic leg 3 did not reshard+complete: {r3.as_dict()}")
    final = _final_tree(d)

    # recovery must be visible: reshard incidents name the topologies,
    # every checkpoint entry is stamped with the mesh that produced it
    m = load_manifest(d)
    reshards = [i for i in m["incidents"] if i["kind"] == "reshard"]
    transitions = [(i["from"], i["to"]) for i in reshards]
    _assert(("dp4xsharding2", "dp2xsharding2") in transitions
            and ("dp2xsharding2", "dp4xsharding2") in transitions,
            f"reshard incidents missing the 8->4->8 chain: {transitions}")
    _assert(all(e.get("topology") for e in m["checkpoints"]),
            f"manifest entries are topology-blind: {m['checkpoints']}")
    last_good = next(e for e in m["checkpoints"]
                     if e["name"] == m["last_good"])
    _assert(last_good["topology"]["mesh"]["shape"] == [4, 2],
            f"final entry not stamped with the grown 8-device mesh: "
            f"{last_good['topology']}")

    # clean comparator: the SAME grow-point checkpoint restored at the
    # new topology WITHOUT the supervisor, trained to completion — the
    # chaotic chain must match it bitwise (params AND opt slots)
    model, loader, kw = make_elastic_8()
    kw.pop("callbacks", None)
    batch = next(iter(loader))
    x, _y = model._split_batch(batch)
    model._ensure_train_step(len(x))
    resil_mod.restore_train_state(model._train_step, resume_path)
    start = int(model._train_step.step_count)
    model.fit(loader, resume_step=start, **kw)
    _assert(int(model._train_step.step_count) == ELASTIC_STEPS,
            "comparator did not reach the end")
    _assert(_bitwise(final["params"], model._train_step.params) and
            _bitwise(final["opt"], model._train_step.opt_state),
            "elastic chain drifted from the clean run at the new "
            "topology")
    return {"transitions": transitions, "resumed_from": start,
            "final_step": r3.final_step}


def phase_reshard_kill(work):
    """A reshard killed mid-stream must leave the checkpoint directory
    untouched, cost ONE restart-budget strike, and succeed on retry."""
    from paddle_tpu.distributed.resilience import FaultInjector
    from paddle_tpu.distributed import checkpoint as ckpt_mod
    from paddle_tpu.distributed.supervisor import load_manifest
    d = os.path.join(work, "reshard_kill")
    _s1, r1 = _run_elastic(d, make_elastic_8, preempt_at=5)
    _assert(r1.outcome == "preempted",
            f"reshard_kill setup did not preempt: {r1.as_dict()}")
    path = ckpt_mod.latest_checkpoint(d)
    before = _dir_snapshot(path)

    with FaultInjector({"ckpt_reshard": 1}):
        _s2, r2 = _run_elastic(d, make_elastic_4, max_to_keep=99)
    _assert(r2.outcome == "completed"
            and r2.final_step == ELASTIC_STEPS,
            f"killed reshard did not heal: {r2.as_dict()}")
    _assert(r2.restarts >= 1 and r2.reshards >= 1,
            f"killed reshard cost no budget strike: {r2.as_dict()}")
    _assert(_dir_snapshot(path) == before,
            "killed reshard modified the checkpoint directory")
    m = load_manifest(d)
    fails = [i for i in m["incidents"] if i["kind"] == "restore_failed"]
    _assert(fails and fails[0]["action"] == "retry"
            and "ckpt_reshard" in fails[0]["error"],
            f"restore_failed incident missing/wrong: {fails}")
    return {"strikes": r2.restarts,
            "failed_ckpt": fails[0]["name"]}


# ---------------------------------------------------------------------------
# main
# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="in-process phases only (no child processes) — "
                         "the ci.py --quick chaos smoke")
    ap.add_argument("--elastic", action="store_true",
                    help="ONLY the topology-elastic phases (8->4->8 "
                         "reshard-on-resume + killed-reshard retry) — "
                         "the ci.py --quick elastic smoke")
    args = ap.parse_args(argv)

    if (args.elastic or not args.smoke) and not _env_ok():
        _reexec()      # elastic phases need the 8-virtual-device mesh

    work = tempfile.mkdtemp(prefix="paddle_tpu_chaos_")
    obs_dir = os.path.join(work, "obs")
    os.environ["PADDLE_TPU_OBS_DIR"] = obs_dir
    os.makedirs(obs_dir, exist_ok=True)

    mode = "elastic" if args.elastic else (
        "smoke" if args.smoke else "full")
    record = {"mode": mode, "phases": {}}
    run_base = not args.elastic
    run_elastic = args.elastic or not args.smoke
    t0 = time.monotonic()
    try:
        if run_base:
            base, info = phase_baseline(work)
            record["phases"]["baseline"] = info
            record["phases"]["nan_storm"] = phase_nan_storm(work, base,
                                                            obs_dir)
            record["phases"]["wedge"] = phase_wedge(work, base, obs_dir)
            record["phases"]["preempt"] = phase_preempt(work, base)
            record["phases"]["skip"] = phase_skip_window(work)
            if not args.smoke:
                record["phases"]["sigterm"] = phase_sigterm(work, base)
                record["phases"]["kill9"] = phase_kill9(work, base)
        if run_elastic:
            record["phases"]["elastic"] = phase_elastic(work)
            record["phases"]["reshard_kill"] = phase_reshard_kill(work)
        # every recovery must be visible in the supervisor metrics
        from paddle_tpu import obs
        if obs.enabled():
            reg = obs.metrics.registry
            record["metrics"] = {}
            if run_base:
                rb = reg.get("ptpu_supervisor_rollbacks_total")
                record["metrics"].update({
                    "rollbacks_nan_storm": rb.value(reason="nan_storm"),
                    "rollbacks_hang": rb.value(reason="hang"),
                    "rollbacks_loss_spike": rb.value(
                        reason="loss_spike"),
                    "preemptions": reg.get(
                        "ptpu_supervisor_preemptions_total").value(),
                    "skipped_windows": reg.get(
                        "ptpu_supervisor_skipped_windows_total").value(),
                    "checkpoints": reg.get(
                        "ptpu_supervisor_checkpoints_total").value(),
                })
                _assert(record["metrics"]["rollbacks_nan_storm"] >= 1
                        and record["metrics"]["rollbacks_hang"] >= 1
                        and record["metrics"]["rollbacks_loss_spike"]
                        >= 1
                        and record["metrics"]["preemptions"] >= 1
                        and record["metrics"]["skipped_windows"] >= 1,
                        f"recovery not visible in ptpu_supervisor_* "
                        f"metrics: {record['metrics']}")
            if run_elastic:
                record["metrics"]["reshards"] = reg.get(
                    "ptpu_supervisor_reshards_total").value()
                # 8->4 + 4->8 in phase_elastic, + the killed-reshard
                # retry's successful 8->4 in phase_reshard_kill
                _assert(record["metrics"]["reshards"] >= 3,
                        f"reshards not visible in "
                        f"ptpu_supervisor_reshards_total: "
                        f"{record['metrics']}")
        record["elapsed_s"] = round(time.monotonic() - t0, 1)
        record["ok"] = True
        print(json.dumps(record))
        return 0
    except (AssertionError, Exception) as e:   # noqa: BLE001
        import traceback
        traceback.print_exc()
        print(json.dumps({"error": f"{type(e).__name__}: {e}",
                          "phases": record["phases"]}))
        return 1


if __name__ == "__main__":
    sys.exit(main())
