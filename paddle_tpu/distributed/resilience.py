"""Fault tolerance for the distributed runtime (the resilience subsystem).

The reference ships fault tolerance as a fleet of loosely-coupled
mechanisms — elastic training (python/paddle/distributed/elastic),
auto-checkpoint relaunch (incubate/checkpoint/auto_checkpoint.py), the
launch watchdog, and per-RPC retry loops. Here those converge into one
layer with three primitives shared by every consumer:

* ``RetryPolicy`` / ``with_retries`` — the ONE backoff schedule
  (exponential + jitter, attempt caps, deadline budgets) used by
  TCPStore rendezvous, DataLoader worker restarts and the serving
  tier's respawn governor.
* ``StepWatchdog`` — runs train steps under a deadline, detects hangs
  (a wedged collective never returns; device dispatch exceeding
  ``PADDLE_TPU_STEP_TIMEOUT``) and NaN/Inf storms (framework/nan_inf
  scan over the step loss), and triggers checkpoint-on-failure through
  the atomic tmp+rename path in distributed/checkpoint.py.
* ``FaultInjector`` — env-var and context-manager driven fault
  simulation (wedged collective, dropped host, corrupt checkpoint
  shard, crashing dataloader worker, unavailable serving backend), so
  every recovery path is exercisable under JAX_PLATFORMS=cpu.

Import cost contract: this module imports ONLY the stdlib at module
scope — a tool that must not hold the chip (a probe's parent, the
watcher) must be able to read the retry schedule without pulling jax.

Env knobs (documented in COMPONENTS.md "Resilience"):
  PADDLE_TPU_STEP_TIMEOUT     step deadline in seconds (arms Model.fit)
  PADDLE_TPU_NAN_LIMIT        consecutive non-finite losses -> storm (3)
  PADDLE_TPU_FAULT_INJECT     "site[:count],site..." fault spec
  PADDLE_TPU_FAULT_WEDGE_S    wedge-style fault duration (3600)
  PADDLE_TPU_WORKER_RESTARTS  DataLoader worker respawn budget (0)
  PADDLE_TPU_RETRY_*          MAX_ATTEMPTS / BASE_DELAY / MAX_DELAY
"""
from __future__ import annotations

import math
import os
import queue
import random
import threading
import time
from typing import Any, Callable, Dict, Optional, Tuple

__all__ = [
    "RetryPolicy", "with_retries",
    "StepWatchdog", "StepTimeout", "NanInfStorm",
    "LossSpike", "LossSpikeDetector",
    "FaultInjector", "FaultInjected", "maybe_inject", "should_fire",
    "wedge_seconds", "arm_fault",
    "CheckpointCorrupt",
    "save_train_state", "restore_train_state", "train_state_layout",
    "RngState",
]


# ---------------------------------------------------------------------------
# errors
# ---------------------------------------------------------------------------

class ResilienceError(RuntimeError):
    """Base class for failures the resilience layer detects/raises."""


class StepTimeout(ResilienceError):
    """A train step exceeded its deadline (hung collective / wedged
    device dispatch). The step's worker thread may still be blocked in
    the runtime; the training loop should checkpoint + exit, not retry
    in-process (parity: elastic relaunches the worker)."""


class NanInfStorm(FloatingPointError, ResilienceError):
    """N consecutive steps produced a non-finite loss — the run has
    diverged; continuing only burns accelerator time (reference:
    FLAGS_check_nan_inf abort semantics, nan_inf_utils_detail.cc)."""


class LossSpike(ResilienceError):
    """The step loss jumped far outside its recent window (z-score
    over the last W finite losses) — the run is diverging on FINITE
    values a NaN scan can never see (poison batch, optimizer blow-up).
    The supervisor treats it exactly like a NaN storm: roll back to
    the last good checkpoint and escalate."""


class CheckpointCorrupt(ResilienceError):
    """A checkpoint directory failed its integrity check (missing
    commit marker / truncated shard) — refuse to restore from it."""


class FaultInjected(ResilienceError):
    """Raised at an injection site when the configured fault fires."""

    def __init__(self, site: str):
        super().__init__(f"injected fault at site {site!r} "
                         "(PADDLE_TPU_FAULT_INJECT)")
        self.site = site


# ---------------------------------------------------------------------------
# RetryPolicy — the one backoff schedule
# ---------------------------------------------------------------------------

class RetryPolicy:
    """Exponential backoff with jitter, attempt caps, and a deadline.

    ``delay(attempt)`` is the DETERMINISTIC schedule (attempt is
    1-based; the delay is what to sleep *after* that attempt fails):
    ``min(base_delay * multiplier**(attempt-1), max_delay)``. Jitter is
    applied only in ``sleep(attempt)`` so callers that need the exact
    schedule (tests, the shell watcher mirroring these semantics) can
    read it.

    ``deadline`` is the TOTAL retry-time budget across attempts and
    sleeps: sleeps are capped to the remaining budget and once it is
    exhausted ``run`` re-raises instead of sleeping again — an attempt
    cap bounds tries, the deadline bounds wall-clock. A retry storm
    against a dead tier therefore gives up within the caller's
    deadline, never after attempts x max_delay. ``run(...,
    deadline=...)`` overrides per call so one shared policy can honor
    each request's own remaining budget.

    ``full_jitter=True`` switches the jittered sleep to the AWS
    full-jitter scheme — ``uniform(0, delay(attempt))`` — which
    decorrelates a thundering herd of retriers far better than the
    default +/-``jitter`` band around the deterministic schedule.
    ``delay``/``schedule`` stay deterministic either way.

    **Retry-After hints**: when a failed attempt's exception carries a
    ``retry_after_s`` attribute (the serving layer attaches the 503
    body's advisory backoff to every shed it relays), ``run`` sleeps
    exactly that hint — capped by the remaining deadline — instead of
    the policy schedule. The server's own word about when capacity
    clears beats any client-side guess; the hint is used verbatim (no
    jitter) so tests and the shell watcher can rely on it.

    ``clock``/``sleep_fn`` are injectable for tests (fake clock): they
    default to ``time.monotonic``/``time.sleep`` and are the ONLY
    time sources ``run`` consults.
    """

    def __init__(self, max_attempts: int = 3, base_delay: float = 0.5,
                 max_delay: float = 60.0, multiplier: float = 2.0,
                 jitter: float = 0.1, deadline: Optional[float] = None,
                 retry_on: Tuple[type, ...] = (Exception,),
                 full_jitter: bool = False,
                 clock: Optional[Callable[[], float]] = None,
                 sleep_fn: Optional[Callable[[float], None]] = None):
        if max_attempts < 1:
            raise ValueError("max_attempts must be >= 1")
        if multiplier < 1.0:
            raise ValueError("multiplier must be >= 1")
        if not 0.0 <= jitter <= 1.0:
            raise ValueError("jitter must be in [0, 1]")
        self.max_attempts = int(max_attempts)
        self.base_delay = float(base_delay)
        self.max_delay = float(max_delay)
        self.multiplier = float(multiplier)
        self.jitter = float(jitter)
        self.deadline = deadline
        self.retry_on = retry_on
        self.full_jitter = bool(full_jitter)
        self._clock = clock if clock is not None else time.monotonic
        self._sleep = sleep_fn if sleep_fn is not None else time.sleep

    @classmethod
    def from_env(cls, prefix: str = "PADDLE_TPU_RETRY", **defaults):
        """Build a policy from ``<prefix>_MAX_ATTEMPTS / _BASE_DELAY /
        _MAX_DELAY / _DEADLINE`` env vars; malformed values fall back to
        the given defaults (a typo'd knob must never crash rendezvous)."""
        def num(name, cast, dflt):
            raw = os.environ.get(f"{prefix}_{name}")
            if raw is None:
                return dflt
            try:
                return cast(raw)
            except ValueError:
                return dflt
        kw = dict(defaults)
        kw["max_attempts"] = num("MAX_ATTEMPTS", int,
                                 defaults.get("max_attempts", 3))
        kw["base_delay"] = num("BASE_DELAY", float,
                               defaults.get("base_delay", 0.5))
        kw["max_delay"] = num("MAX_DELAY", float,
                              defaults.get("max_delay", 60.0))
        kw["deadline"] = num("DEADLINE", float, defaults.get("deadline"))
        return cls(**kw)

    # -- schedule --------------------------------------------------------
    def delay(self, attempt: int) -> float:
        """Deterministic post-attempt delay (attempt is 1-based)."""
        if attempt < 1:
            raise ValueError("attempt is 1-based")
        return min(self.base_delay * self.multiplier ** (attempt - 1),
                   self.max_delay)

    def schedule(self) -> Tuple[float, ...]:
        """The full inter-attempt delay schedule (len max_attempts-1)."""
        return tuple(self.delay(a) for a in range(1, self.max_attempts))

    def sleep(self, attempt: int, budget: Optional[float] = None,
              hint: Optional[float] = None) -> float:
        """Sleep the (jittered) post-attempt delay; returns the time
        slept. ``budget`` caps the sleep (remaining deadline). With
        ``full_jitter`` the sleep is drawn uniform from
        [0, delay(attempt)] instead of a +/-jitter band. A ``hint``
        (the server's Retry-After, in seconds) REPLACES the schedule
        verbatim — still capped by ``budget``."""
        if hint is not None:
            d = max(0.0, float(hint))
        else:
            d = self.delay(attempt)
            if self.full_jitter:
                d = random.uniform(0.0, d)
            elif self.jitter:
                d *= 1.0 + random.uniform(-self.jitter, self.jitter)
        if budget is not None:
            d = max(0.0, min(d, budget))
        if d > 0:
            self._sleep(d)
        return d

    # -- execution -------------------------------------------------------
    def run(self, fn: Callable, *args,
            on_retry: Optional[Callable[[int, BaseException], None]] = None,
            deadline: Optional[float] = None,
            **kwargs):
        """Call ``fn`` under this policy. ``on_retry(attempt, exc)`` is
        invoked before each backoff sleep (logging hook). ``deadline``
        overrides the policy's total retry-time budget for THIS call
        (a router passes each request's remaining forward budget)."""
        total = self.deadline if deadline is None else deadline
        start = self._clock()
        for attempt in range(1, self.max_attempts + 1):
            try:
                return fn(*args, **kwargs)
            except self.retry_on as e:
                if attempt >= self.max_attempts:
                    raise
                if total is not None:
                    remaining = total - (self._clock() - start)
                    if remaining <= 0:
                        # budget exhausted: give up NOW — within the
                        # caller's deadline, not attempts x max_delay
                        raise
                else:
                    remaining = None
                if on_retry is not None:
                    on_retry(attempt, e)
                hint = getattr(e, "retry_after_s", None)
                try:
                    hint = None if hint is None else float(hint)
                except (TypeError, ValueError):
                    hint = None
                self.sleep(attempt, budget=remaining, hint=hint)
        raise AssertionError("unreachable")


def with_retries(fn: Callable, *args,
                 policy: Optional[RetryPolicy] = None,
                 on_retry: Optional[Callable] = None, **kwargs):
    """Functional spelling: ``with_retries(fn, a, b, policy=p)``."""
    return (policy or RetryPolicy()).run(fn, *args, on_retry=on_retry,
                                         **kwargs)


# ---------------------------------------------------------------------------
# FaultInjector — env-var and context-manager driven fault simulation
# ---------------------------------------------------------------------------

# Known sites, each instrumented at exactly one layer:
#   collective          wedge inside an eager collective (sleeps)
#   host_drop           TCPStore get/wait raises TimeoutError
#   ckpt_shard          corrupt a just-written checkpoint (marker+shard)
#   ckpt_crash          die mid-save, AFTER shard bytes, BEFORE publish
#   dataloader_worker   hard-kill a forked DataLoader worker (os._exit)
#   step_hang           a train step wedges (sleeps)
#   step_nan            a train step's loss comes back NaN
#   train_crash         the training process dies mid-epoch (raises)
#   serve_backend       predictor backend unavailable (raises)
#   serve_hang          predictor wedges (sleeps)
#   router_forward      a router->replica forward attempt fails (raises;
#                       the router treats it like a connection failure
#                       and retries on a DIFFERENT replica)
#   replica_spawn       spawning a serving-tier replica fails (raises;
#                       the tier control loop retries on its next pass)
#   replica_health      a replica health poll fails (raises; counts
#                       toward the router's unhealthy streak)
#   replica_stall       a replica's engine decode loop WEDGES (sleeps —
#                       latency injection, not death: the process stays
#                       alive, /healthz keeps answering ready, only
#                       token progress stops; the straggler scenario
#                       the router's hedged decode exists for)
#   train_step_nan      hapi Model.train_batch reports a NaN loss for
#                       one step (the real program still ran — a
#                       transient divergence the supervisor's rollback
#                       must survive; N firings under nan_limit=N make
#                       a full storm)
#   preempt_signal      the TrainSupervisor observes a synthetic
#                       SIGTERM at the next step boundary (preemption
#                       grace path without a real signal — drivable
#                       from env in subprocess children)
#   ckpt_gc             checkpoint retention GC fails before deleting
#                       anything (distributed/checkpoint.gc_checkpoints
#                       — GC failure must never take training down)
#   lock_hold           an InstrumentedLock (obs/locks.py, the tpurace
#                       sanitizer) holds its lock for wedge_seconds()
#                       INSIDE release() — an artificial hold-time
#                       spike that lights up ptpu_lock_wait_ms and the
#                       deadlock watchdog without a real wedge
#   ckpt_reshard        a topology-elastic restore dies MID-reshard
#                       (checkpoint.reshard_state_dict, after >= 1 leaf
#                       landed) — restore is read-only, so the
#                       checkpoint must survive untouched and the next
#                       attempt must succeed; the supervisor books the
#                       failure as one restart-budget strike
_KNOWN_SITES = frozenset([
    "collective", "host_drop", "ckpt_shard", "ckpt_crash",
    "dataloader_worker", "step_hang", "step_nan", "train_crash",
    "serve_backend", "serve_hang",
    "router_forward", "replica_spawn", "replica_health",
    "replica_stall",
    "train_step_nan", "preempt_signal", "ckpt_gc", "ckpt_reshard",
    "lock_hold",
])

_inject_lock = threading.Lock()
_active: Dict[str, int] = {}       # site -> remaining fire count
_env_parsed = False
_wedge_s: Optional[float] = None


def _parse_spec(spec: str) -> Dict[str, int]:
    """``"site[:count],site2"`` -> {site: count}. Unknown sites raise —
    a typo'd site name silently never firing is the worst failure mode
    a fault-injection harness can have."""
    out: Dict[str, int] = {}
    for part in spec.split(","):
        part = part.strip()
        if not part:
            continue
        name, _, cnt = part.partition(":")
        name = name.strip()
        if name not in _KNOWN_SITES:
            raise ValueError(
                f"unknown fault-injection site {name!r}; known: "
                f"{sorted(_KNOWN_SITES)}")
        out[name] = int(cnt) if cnt else 1
    return out


def _ensure_env_loaded():
    global _env_parsed
    with _inject_lock:
        if _env_parsed:
            return
        _env_parsed = True
        spec = os.environ.get("PADDLE_TPU_FAULT_INJECT", "")
        if spec:
            for site, cnt in _parse_spec(spec).items():
                _active[site] = _active.get(site, 0) + cnt


def should_fire(site: str) -> bool:
    """Consume one firing of ``site`` if armed. Thread-safe; each
    configured count fires exactly once per process (forked DataLoader
    workers inherit a copy of the counters, so a per-worker site fires
    up to count times in EACH worker — tests account for this)."""
    _ensure_env_loaded()
    with _inject_lock:
        n = _active.get(site, 0)
        if n <= 0:
            return False
        _active[site] = n - 1
        return True


def wedge_seconds(default: float = 3600.0) -> float:
    """How long a wedge-style fault blocks. Production default is an
    hour (indistinguishable from a real wedged device); tests set
    PADDLE_TPU_FAULT_WEDGE_S (or FaultInjector(wedge_s=...)) small."""
    if _wedge_s is not None:
        return _wedge_s
    try:
        return float(os.environ.get("PADDLE_TPU_FAULT_WEDGE_S", default))
    except ValueError:
        return default


def maybe_inject(site: str) -> None:
    """The one hook instrumented code calls. Raises ``FaultInjected``
    for crash-type sites; SLEEPS for wedge-type sites (a wedge hangs,
    it does not error — that is the whole point)."""
    if not should_fire(site):
        return
    if site in ("collective", "step_hang", "serve_hang",
                "replica_stall", "lock_hold"):
        time.sleep(wedge_seconds())
        return
    if site == "host_drop":
        raise TimeoutError(
            "injected: peer host dropped out of rendezvous "
            "(PADDLE_TPU_FAULT_INJECT=host_drop)")
    raise FaultInjected(site)


def arm_fault(site: str, count: int = 1,
              wedge_s: Optional[float] = None) -> None:
    """Programmatic (non-context) arming of an injection site — the
    serving tier's chaos admin endpoint (``POST /admin/inject``, gated
    on PADDLE_TPU_CHAOS_ADMIN) uses it to wedge/fail a LIVE replica
    from outside the process. Counts add like nested FaultInjectors;
    there is no paired disarm — an armed-but-unfired count stays armed
    for the life of the process (chaos benches arm exactly what they
    intend to fire)."""
    global _wedge_s
    if site not in _KNOWN_SITES:
        raise ValueError(
            f"unknown fault-injection site {site!r}; known: "
            f"{sorted(_KNOWN_SITES)}")
    _ensure_env_loaded()
    with _inject_lock:
        _active[site] = _active.get(site, 0) + int(count)
        if wedge_s is not None:
            _wedge_s = float(wedge_s)


class FaultInjector:
    """Context-manager arming of injection sites::

        with FaultInjector({"step_hang": 1}, wedge_s=2.0):
            ...   # the next step through an instrumented site wedges 2s

    Spec values are fire counts. Nests; counts add. Fork-aware the
    cheap way: children inherit the armed counters by COW, each with an
    independent copy.
    """

    def __init__(self, spec: Dict[str, int] | str,
                 wedge_s: Optional[float] = None):
        self.spec = _parse_spec(spec) if isinstance(spec, str) else {
            s: int(c) for s, c in spec.items()}
        for s in self.spec:
            if s not in _KNOWN_SITES:
                raise ValueError(f"unknown fault-injection site {s!r}")
        self.wedge_s = wedge_s

    def __enter__(self):
        global _wedge_s
        _ensure_env_loaded()
        with _inject_lock:
            for site, cnt in self.spec.items():
                _active[site] = _active.get(site, 0) + cnt
            if self.wedge_s is not None:
                self._prev_wedge = _wedge_s
                _wedge_s = float(self.wedge_s)
            else:
                self._prev_wedge = None
        return self

    def __exit__(self, *exc):
        global _wedge_s
        with _inject_lock:
            # disarm whatever this context armed and did not fire
            for site, cnt in self.spec.items():
                _active[site] = max(0, _active.get(site, 0) - cnt)
            if self.wedge_s is not None:
                _wedge_s = self._prev_wedge
        return False


# ---------------------------------------------------------------------------
# StepWatchdog — hang + NaN-storm detection with checkpoint-on-failure
# ---------------------------------------------------------------------------

class StepWatchdog:
    """Run train steps under a heartbeat with a deadline.

    The step runs in a dedicated worker thread; the caller waits at
    most ``deadline`` seconds. A jitted step that wedges (hung
    collective, dead device link) blocks the worker, the wait expires, the
    watchdog fires ``on_failure("hang", ...)`` (checkpoint-on-failure)
    and raises ``StepTimeout`` — the caller's thread is NEVER the one
    stuck in the runtime, so the process can still save state and exit.

    NaN/Inf storms: every returned loss is scanned (framework/nan_inf
    semantics — non-finite detection on concrete values); ``nan_limit``
    consecutive non-finite losses raise ``NanInfStorm`` after firing
    ``on_failure("nan_storm", ...)``. A single non-finite step does not
    kill the run (bf16 loss-scale hiccups recover); a storm does.

    ``on_failure(kind, exc)`` is the checkpoint-on-failure hook — wire
    it to ``save_train_state`` (ParallelTrainStep) or ``Model``'s
    emergency save. It must not raise; failures there are swallowed so
    the original error surfaces.
    """

    def __init__(self, deadline: Optional[float] = None,
                 nan_limit: Optional[int] = None,
                 on_failure: Optional[Callable[[str, BaseException],
                                              None]] = None):
        if deadline is None:
            raw = os.environ.get("PADDLE_TPU_STEP_TIMEOUT")
            if raw:
                try:
                    deadline = float(raw)
                except ValueError:
                    deadline = None
        if deadline is not None and deadline <= 0:
            deadline = None  # 0 disables, matching DataLoader timeout=0
        if nan_limit is None:
            try:
                nan_limit = int(os.environ.get("PADDLE_TPU_NAN_LIMIT", 3))
            except ValueError:
                nan_limit = 3
        self.deadline = deadline
        self.nan_limit = max(1, int(nan_limit))
        self.on_failure = on_failure
        self.nonfinite_streak = 0
        self.steps_run = 0
        self._work: "queue.Queue" = queue.Queue(maxsize=1)
        self._worker: Optional[threading.Thread] = None
        self._dead = False

    @classmethod
    def enabled_by_env(cls) -> bool:
        """True when env asks for watchdog supervision (Model.fit arms
        itself off this). A 0/negative/unparseable timeout means
        disabled, matching the DataLoader timeout=0 convention."""
        from ..framework import flags
        if flags.flag_value("check_nan_inf"):
            return True
        raw = os.environ.get("PADDLE_TPU_STEP_TIMEOUT")
        if not raw:
            return False
        try:
            return float(raw) > 0
        except ValueError:
            return False

    # -- worker plumbing -------------------------------------------------
    def _ensure_worker(self):
        if self._worker is None or not self._worker.is_alive() \
                or self._dead:
            # a timed-out worker is abandoned (daemon, still blocked in
            # the runtime); a fresh one serves subsequent steps
            self._work = queue.Queue(maxsize=1)
            self._worker = threading.Thread(
                target=self._loop, args=(self._work,),
                name="paddle-tpu-step-watchdog", daemon=True)
            self._worker.start()
            self._dead = False

    @staticmethod
    def _loop(work: "queue.Queue"):
        while True:
            item = work.get()
            if item is None:
                return
            fn, args, kwargs, box, done = item
            try:
                box.append((True, fn(*args, **kwargs)))
            except BaseException as e:  # noqa: BLE001 — relayed to caller
                box.append((False, e))
            done.set()

    # -- failure path ----------------------------------------------------
    def _fail(self, kind: str, exc: BaseException):
        try:
            # postmortem first (paddle_tpu.obs): dump the flight
            # recorder — what the process was doing in the seconds
            # before the hang/storm — to a timestamped artifact BEFORE
            # any rescue path can wedge. obs is stdlib-only, imported
            # lazily to keep this module's stdlib-at-module-scope
            # contract; best-effort like the checkpoint below.
            from ..obs.trace import dump_flight
            dump_flight(f"watchdog_{kind}",
                        extra={"deadline_s": self.deadline,
                               "steps_run": self.steps_run})
        except Exception:
            pass
        if self.on_failure is not None:
            try:
                self.on_failure(kind, exc)
            except Exception:
                # checkpoint-on-failure is best-effort: the ORIGINAL
                # failure must surface, not the rescue attempt's
                pass
        raise exc

    @staticmethod
    def _loss_finite_seq(result):
        """Per-step finiteness of a step result's loss(es), in step
        order. Handles a scalar (float / Tensor / lazy loss — anything
        float()-able or numpy-coercible) AND a fused K-step window's
        STACKED losses (a [K] array: one entry per optimizer step, so a
        storm inside a window is still counted step-by-step). Reading
        the values is the fused loop's one sync per supervised window
        (a LossWindow result shares its fetch with the training loop's
        lazy losses). A non-numeric result counts as ONE finite step —
        the pre-fused watchdog's contract: nothing to scan means the
        consecutive-NaN streak is broken, not paused."""
        v = result
        if isinstance(v, (tuple, list)) and v:
            v = v[0]
        if v is None:
            return (True,)
        try:
            import numpy as np  # lazy: module contract is stdlib-only
            arr = np.asarray(v, dtype=np.float64).reshape(-1)
            return [bool(np.isfinite(x)) for x in arr]
        except Exception:
            try:
                return (math.isfinite(float(v)),)
            except (TypeError, ValueError):
                return (True,)

    # -- API -------------------------------------------------------------
    def run(self, step_fn: Callable, *args, deadline_scale: int = 1,
            **kwargs):
        """Execute one supervised step (or one fused K-step window —
        pass ``deadline_scale=K`` so the single dispatch gets K per-step
        budgets); returns its result."""
        self.steps_run += 1
        deadline = self.deadline
        if deadline is not None:
            deadline = deadline * max(1, int(deadline_scale))
        if deadline is None:
            result = step_fn(*args, **kwargs)
            finite_seq = self._loss_finite_seq(result)
        else:
            self._ensure_worker()
            box: list = []
            done = threading.Event()

            def supervised():
                # jax dispatch is ASYNC and the loop's losses are lazy:
                # step_fn returns in microseconds whatever the device is
                # doing. The loss scan below is the step's first (and
                # only) blocking device read, so it must run HERE, in
                # the deadline-covered worker — a wedged collective
                # hangs THIS fetch, trips done.wait, and raises
                # StepTimeout instead of hanging the caller. The fetch
                # lands in the step's shared LazyLoss/LossWindow cache,
                # so it is still the one counted sync per supervised
                # step/window.
                res = step_fn(*args, **kwargs)
                return res, self._loss_finite_seq(res)

            self._work.put((supervised, (), {}, box, done))
            if not done.wait(deadline):
                self._dead = True   # worker is wedged; abandon it
                self._fail("hang", StepTimeout(
                    f"train step exceeded its {deadline:.1f}s "
                    "deadline (wedged collective / hung device "
                    "dispatch?) — state checkpointed on failure"))
            ok, payload = box[0]
            if not ok:
                raise payload
            result, finite_seq = payload
        # nan/inf storm accounting on the (synced) loss(es) — a fused
        # window contributes its K stacked losses one by one, so the
        # consecutive-step streak spans window boundaries exactly as it
        # would in the per-step loop
        for finite in finite_seq:
            if finite:
                self.nonfinite_streak = 0
                continue
            self.nonfinite_streak += 1
            if self.nonfinite_streak >= self.nan_limit:
                streak = self.nonfinite_streak
                self.nonfinite_streak = 0
                self._fail("nan_storm", NanInfStorm(
                    f"{streak} consecutive train steps produced a "
                    "non-finite loss — run has diverged "
                    "(FLAGS_check_nan_inf semantics); state "
                    "checkpointed on failure"))
        return result

    def close(self):
        if self._worker is not None and self._worker.is_alive() \
                and not self._dead:
            self._work.put(None)
        self._worker = None


# ---------------------------------------------------------------------------
# LossSpikeDetector — windowed z-score divergence scan (beside the NaN scan)
# ---------------------------------------------------------------------------

class LossSpikeDetector:
    """Detect finite-loss divergence the NaN scan cannot: a loss that
    jumps ``z`` standard deviations above the mean of the last
    ``window`` finite losses raises :class:`LossSpike`.

    The scan is one-sided (a loss *collapsing* is not an incident),
    needs ``min_points`` history before it can fire (cold-start losses
    swing legitimately), and never admits the spiking value into its
    window — a poison batch must not teach the detector that poison is
    normal. Non-finite losses are ignored entirely: the NaN-storm scan
    (:class:`StepWatchdog`) owns those.

    The deviation scale is ``max(std, rel_floor * |mean|)``: on a
    converged plateau (or a window holding rollback-replay duplicates)
    the raw std collapses toward zero and ordinary batch-to-batch
    wobble would z-score as a spike — the relative floor means a real
    incident must ALSO clear ``z * rel_floor`` of the mean (the
    divergences this exists for are orders of magnitude, not percent).
    ``abs_floor`` additionally requires the jump to exceed a fixed
    value in absolute terms.
    """

    def __init__(self, window: int = 32, z: float = 8.0,
                 min_points: int = 8, abs_floor: float = 0.0,
                 rel_floor: float = 0.1):
        if window < 2:
            raise ValueError("window must be >= 2")
        self.window = int(window)
        self.z = float(z)
        self.min_points = max(2, int(min_points))
        self.abs_floor = float(abs_floor)
        self.rel_floor = float(rel_floor)
        self._values: list = []

    def observe(self, loss) -> None:
        """Feed one step loss; raises :class:`LossSpike` on divergence."""
        try:
            v = float(loss)
        except (TypeError, ValueError):
            return
        if not math.isfinite(v):
            return                       # the NaN-storm scan owns these
        vals = self._values
        if len(vals) >= self.min_points:
            mean = sum(vals) / len(vals)
            var = sum((x - mean) ** 2 for x in vals) / len(vals)
            std = math.sqrt(var)
            scale = max(std, self.rel_floor * abs(mean), 1e-12)
            if (v - mean) > self.z * scale and (v - mean) > self.abs_floor:
                raise LossSpike(
                    f"step loss {v:.6g} is {(v - mean) / scale:.1f} "
                    f"sigma above the last-{len(vals)}-step mean "
                    f"{mean:.6g} — run is diverging; rolling back")
        vals.append(v)
        if len(vals) > self.window:
            del vals[0]

    def reset(self) -> None:
        """Forget history (after a rollback the window restarts: the
        replayed region must re-earn min_points before firing)."""
        self._values.clear()


# ---------------------------------------------------------------------------
# crash-safe train-state round trip (ParallelTrainStep / TrainStep)
# ---------------------------------------------------------------------------

def _train_state_tree(step) -> Dict[str, Any]:
    """Full restart state of a (Parallel)TrainStep: params + optimizer
    slots + step counters + host RNG key — everything ``__call__``
    consumes besides the batch. jax imported lazily (module contract)."""
    import jax
    import numpy as np
    from ..framework import random as _rng
    key_data = np.asarray(jax.random.key_data(_rng.get_rng_state()))
    return {
        "params": step.params,
        "buffers": step.buffers,
        "opt": step.opt_state,
        "meta": {
            "step_count": np.int64(step.step_count),
            "update_count": np.int64(step.update_count),
            "rng_key_data": key_data,
        },
    }


def train_state_layout(step, scan_steps: Optional[int] = None) -> dict:
    """The layout manifest of a (Parallel)TrainStep's train state as
    the live process would save it: mesh (ParallelTrainStep) or
    single-device (TrainStep), ZeRO stage, fused-window K, and every
    leaf's placement — what ``save_train_state`` stamps into each
    checkpoint and ``restore_train_state`` diffs on resume."""
    from .checkpoint import describe_layout
    return describe_layout(
        _train_state_tree(step), mesh=getattr(step, "mesh", None),
        zero_stage=getattr(step, "zero_stage", None),
        scan_steps=scan_steps)


def save_train_state(step, path: str,
                     scan_steps: Optional[int] = None) -> str:
    """Atomically checkpoint a (Parallel)TrainStep for crash-resume.

    Goes through distributed/checkpoint.py's tmp+rename publish: a kill
    at ANY point leaves either the previous complete checkpoint or none
    — never a partial directory that looks restorable. The layout
    manifest (mesh/ZeRO/scan-K/per-leaf specs) rides the same commit,
    making the checkpoint topology-neutral: it can restore onto a
    DIFFERENT mesh, device count, or ZeRO stage (see
    ``restore_train_state``).
    """
    from .checkpoint import save_state_dict
    save_state_dict(_train_state_tree(step), path,
                    layout=train_state_layout(step, scan_steps))
    return path


def restore_train_state(step, path: str,
                        scan_steps: Optional[int] = None,
                        on_reshard: Optional[Callable] = None):
    """Restore ``save_train_state`` output into a freshly-built step —
    on ANY topology.

    Same-layout restores take the whole-tree fast path. When the
    stamped layout differs from the live step's — different mesh shape
    (dp4xsharding2 -> dp2xsharding4), device count (8 -> 4 -> 8), ZeRO
    stage (2 <-> 3) — the reshard path streams the checkpoint leaf by
    leaf through canonical-layout assembly + re-placement
    (``checkpoint.reshard_state_dict``), so peak host memory stays ~one
    leaf; ``on_reshard(saved_layout, live_layout, changes)`` is called
    after it succeeds (the supervisor's telemetry hook). A changed
    fused-window ``scan_steps`` alone moves no shards (state is
    identical either way) and stays on the fast path.

    Counters and the host RNG key round-trip so step N after resume
    draws the same fold_in key as an uninterrupted step N — the
    contract that makes resume bitwise; the reshard path preserves it
    exactly (re-placement moves bytes, never values).
    """
    import jax
    from ..framework import random as _rng
    from .checkpoint import (layout_changes, load_state_dict,
                             read_layout, reshard_state_dict)
    # meta leaves are plain host scalars/arrays: int placeholders map to
    # RestoreArgs() (restore-as-saved) in the restore-args target walk
    target = {"params": step.params, "buffers": step.buffers,
              "opt": step.opt_state,
              "meta": {"step_count": 0, "update_count": 0,
                       "rng_key_data": 0}}
    saved = read_layout(path)
    changes: list = []
    if saved is not None:
        changes = layout_changes(saved,
                                 train_state_layout(step, scan_steps))
    reshard = any(not c.startswith("scan_steps") for c in changes)
    if reshard:
        restored = reshard_state_dict(path, target)
    else:
        restored = load_state_dict(path, target=target)
    step.params = restored["params"]
    step.buffers = restored["buffers"]
    step.opt_state = restored["opt"]
    meta = restored["meta"]
    step.step_count = int(meta["step_count"])
    step.update_count = int(meta["update_count"])
    _rng.set_rng_state(jax.random.wrap_key_data(
        jax.numpy.asarray(meta["rng_key_data"])))
    if reshard and on_reshard is not None:
        on_reshard(saved, train_state_layout(step, scan_steps), changes)
    return step


class RngState:
    """state_dict adapter for the global RNG so it can ride along any
    snapshot protocol that saves attach()ed objects (e.g.
    incubate.checkpoint.TrainEpochRange.attach(rng=RngState()))."""

    def state_dict(self):
        import jax
        import numpy as np
        from ..framework import random as _rng
        return {"rng_key_data":
                np.asarray(jax.random.key_data(_rng.get_rng_state()))}

    def set_state_dict(self, state):
        import jax
        import jax.numpy as jnp
        from ..framework import random as _rng
        data = state["rng_key_data"]
        data = getattr(data, "numpy", lambda: data)()
        _rng.set_rng_state(jax.random.wrap_key_data(jnp.asarray(data)))
