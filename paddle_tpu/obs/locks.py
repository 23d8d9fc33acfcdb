"""tpurace runtime half: the lock sanitizer.

The static lint (analysis/concurrency.py) proves discipline the AST
can see; this module watches the discipline the SCHEDULE exercises.
``make_lock``/``make_rlock``/``make_condition`` are drop-in factories
adopted at the tier's hottest lock sites (engine cv, router lock,
request journals, metrics registry + families, compilation store).
With ``PADDLE_TPU_LOCK_SAN`` unset they return PLAIN ``threading``
primitives — the zero-overhead-when-off contract the obs package made
in PR 8 (tests/test_concurrency.py::test_factories_plain_when_off; the
decode tick's cost with obs on is not checked by any test). With the
sanitizer on, every
acquire/release is measured and modeled:

* wait + hold times land in the ``ptpu_lock_wait_ms`` /
  ``ptpu_lock_hold_ms`` histograms (label ``lock=<site name>``) — the
  alerting surface for "a lock got slow" long before it deadlocks;
* acquisition ORDER edges (lock A held while taking lock B) build a
  runtime lock-order graph, checked inline: the first edge that closes
  a cycle dumps a ``lock_order_cycle`` flight artifact naming the
  cycle — you learn two sites disagree on order the first time EITHER
  interleaving runs, not the unlucky night both run at once;
* a watchdog thread walks the waits-for graph (thread -> lock it is
  blocked on -> holders) and dumps a ``lock_deadlock`` artifact naming
  both locks AND the holder stacks (``sys._current_frames``) when a
  cycle persists across two scans.

Instance names are SITE names, shared across instances of the same
class (every request journal is ``journal.cond``): the graph and the
histogram label set stay bounded no matter how many requests flow.
Edges between two instances of one name are therefore ignored — two
journals locked in either order is not an order inversion.

Fault site: ``resilience`` ``lock_hold`` (a wedge-type site) fires
INSIDE ``release()`` while the lock is still held, spiking hold time
artificially so the ``ptpu_lock_wait_ms`` alerting path and the
watchdog are testable without a real wedge. Reached via
``sys.modules`` — this module keeps the obs stdlib-only import
contract, and a resilience module nobody imported can have no armed
faults.

Like the rest of obs, stdlib-only; ``metrics`` is imported lazily at
first record (it imports this module for its own family locks — the
lazy import plus a per-thread reentrancy guard breaks the cycle).
"""
from __future__ import annotations

import os
import sys
import threading
import time
import traceback
from typing import Dict, List, Optional, Set, Tuple

__all__ = ["lock_san_enabled", "set_lock_san", "make_lock",
           "make_rlock", "make_condition", "InstrumentedLock",
           "sanitizer", "LockSanitizer"]

_san_override = None          # set_lock_san() tri-state; None -> env
_san_env = None               # cached env read


def lock_san_enabled() -> bool:
    """Is the lock sanitizer on? One cached read of
    ``PADDLE_TPU_LOCK_SAN`` (default OFF — the factories must cost
    nothing on the serving hot path unless asked); ``set_lock_san``
    overrides for tests and race_hunt."""
    global _san_env
    if _san_override is not None:
        return _san_override
    if _san_env is None:
        raw = os.environ.get("PADDLE_TPU_LOCK_SAN")
        _san_env = (raw is not None
                    and raw.strip().lower() not in ("0", "false", "off",
                                                    ""))
    return _san_env


def set_lock_san(on) -> None:
    """Force the sanitizer on/off (``None`` re-reads the env). Affects
    locks built AFTER the call — existing plain locks stay plain."""
    global _san_override, _san_env
    _san_override = None if on is None else bool(on)
    _san_env = None


# ---------------------------------------------------------------------------
# sanitizer core
# ---------------------------------------------------------------------------

# buckets tuned for lock times: microseconds to wedge-class seconds
_LOCK_BUCKETS_MS = (0.01, 0.05, 0.1, 0.5, 1.0, 5.0, 10.0, 50.0,
                    100.0, 500.0, 1000.0, 5000.0)


class LockSanitizer:
    """Process-wide sanitizer state. ONE instance (module singleton);
    its own bookkeeping is guarded by a PLAIN lock — instrumenting the
    instrument would recurse."""

    def __init__(self, watchdog_interval_s: float = 2.0):
        self._lock = threading.Lock()
        self._tl = threading.local()
        # name-level order graph: (a, b) -> hit count
        self.order_edges: Dict[Tuple[str, str], int] = {}
        self._adj: Dict[str, Set[str]] = {}
        self._cycles_dumped: Set[frozenset] = set()
        self.cycle_artifacts: List[str] = []
        self.deadlock_artifacts: List[str] = []
        # instance-level live state for the watchdog
        self._holders: Dict[int, Tuple[str, Set[int]]] = {}
        self._waiting: Dict[int, Tuple[int, str]] = {}  # tid -> (lockid, name)
        self._watchdog: Optional[threading.Thread] = None
        self._watchdog_stop = threading.Event()
        self._watchdog_interval = watchdog_interval_s
        self._suspect: Optional[frozenset] = None
        self._deadlocks_dumped: Set[frozenset] = set()

    # -- thread-local plumbing ------------------------------------------
    def _held_stack(self) -> List[list]:
        st = getattr(self._tl, "held", None)
        if st is None:
            st = self._tl.held = []
        return st

    # -- acquire / release events ---------------------------------------
    def note_wait_start(self, lock: "InstrumentedLock") -> None:
        tid = threading.get_ident()
        with self._lock:
            self._waiting[tid] = (id(lock), lock.name)
        self._ensure_watchdog()

    def note_wait_end(self, lock: "InstrumentedLock") -> None:
        tid = threading.get_ident()
        with self._lock:
            self._waiting.pop(tid, None)

    def note_acquired(self, lock: "InstrumentedLock",
                      wait_s: float) -> None:
        tid = threading.get_ident()
        stack = self._held_stack()
        for entry in stack:
            if entry[0] is lock:         # reentrant re-acquire
                entry[2] += 1
                return
        new_edges = []
        for entry in stack:
            if entry[0].name != lock.name:
                new_edges.append((entry[0].name, lock.name))
        stack.append([lock, time.perf_counter(), 1])
        with self._lock:
            self._holders.setdefault(id(lock),
                                     (lock.name, set()))[1].add(tid)
            fresh = []
            for e in new_edges:
                n = self.order_edges.get(e, 0)
                self.order_edges[e] = n + 1
                if n == 0:
                    self._adj.setdefault(e[0], set()).add(e[1])
                    fresh.append(e)
            cycles = [self._cycle_through_locked(e) for e in fresh]
        self._observe("ptpu_lock_wait_ms", lock.name, wait_s * 1e3)
        for cyc in cycles:
            if cyc:
                self._dump_cycle(cyc)

    def note_release(self, lock: "InstrumentedLock") -> Optional[float]:
        """Called BEFORE the inner release — the ``lock_hold`` fault,
        if armed, fires while still held. Returns the hold time in ms
        (the CALLER records it, after the real release: recording
        takes a metrics family lock, and doing that while this lock is
        still held would put instrumentation edges — or worse, a
        same-instance re-acquire — into the graph being measured)."""
        stack = self._held_stack()
        for i in range(len(stack) - 1, -1, -1):
            if stack[i][0] is lock:
                stack[i][2] -= 1
                if stack[i][2] > 0:
                    return None                 # still reentrantly held
                t0 = stack[i][1]
                del stack[i]
                break
        else:
            return None     # release of a lock we never saw acquired
        resil = sys.modules.get("paddle_tpu.distributed.resilience")
        if resil is not None:
            try:
                resil.maybe_inject("lock_hold")
            except Exception:   # noqa: BLE001 — injection must not wedge
                pass            # the release path itself
        tid = threading.get_ident()
        with self._lock:
            h = self._holders.get(id(lock))
            if h is not None:
                h[1].discard(tid)
                if not h[1]:
                    self._holders.pop(id(lock), None)
        return (time.perf_counter() - t0) * 1e3

    def in_record(self) -> bool:
        """True while THIS thread is inside a sanitizer->metrics
        record. Instrumented locks bypass all bookkeeping under it —
        the family locks the recording itself takes must not feed
        back into the graph (or deadlock re-acquiring themselves)."""
        return getattr(self._tl, "in_record", False)

    # -- metrics (lazy, reentrancy-guarded) ------------------------------
    def _observe(self, hist_name: str, lock_name: str, ms: float) -> None:
        if getattr(self._tl, "in_record", False):
            return
        if lock_name.startswith("metrics."):
            # the metrics locks guard the histograms that would hold
            # their own timings — self-referential; the order graph
            # and watchdog still cover them
            return
        self._tl.in_record = True
        try:
            from . import metrics as _m
            _m.registry.histogram(
                hist_name, "lock sanitizer timing", labels=("lock",),
                buckets=_LOCK_BUCKETS_MS).observe(ms, lock=lock_name)
        except Exception:   # noqa: BLE001 — telemetry must never
            pass            # break the lock it measures
        finally:
            self._tl.in_record = False

    # -- static-order cycle check (inline, on new edge) ------------------
    def _cycle_through_locked(self, edge: Tuple[str, str]
                              ) -> Optional[List[str]]:
        """Path edge[1] ->* edge[0] in the name graph closes a cycle
        through the new edge. Caller holds self._lock."""
        a, b = edge
        path = self._find_path_locked(b, a)
        if path is None:
            return None
        cyc = path                      # b ... a; edge a->b closes it
        key = frozenset(cyc)
        if key in self._cycles_dumped:
            return None
        self._cycles_dumped.add(key)
        return cyc

    def _find_path_locked(self, src: str,
                          dst: str) -> Optional[List[str]]:
        seen = {src}
        stack = [(src, [src])]
        while stack:
            node, path = stack.pop()
            if node == dst:
                return path
            for nxt in self._adj.get(node, ()):
                if nxt not in seen:
                    seen.add(nxt)
                    stack.append((nxt, path + [nxt]))
        return None

    def _dump_cycle(self, cyc: List[str]) -> None:
        with self._lock:
            edges = {f"{a}->{b}": n
                     for (a, b), n in sorted(self.order_edges.items())}
        try:
            from .trace import dump_flight
            path = dump_flight("lock_order_cycle", extra={
                "locks": cyc,
                "cycle": "->".join(cyc + [cyc[0]]),
                "thread": threading.current_thread().name,
                "stack": traceback.format_stack()[-12:],
                "edges": edges,
            })
            self.cycle_artifacts.append(path)
        except Exception:   # noqa: BLE001
            pass

    # -- deadlock watchdog ----------------------------------------------
    def _ensure_watchdog(self) -> None:
        # intentional double-checked fast path: a stale read only costs
        # one trip into the locked re-check below
        w = self._watchdog  # tpurace: disable=race-unguarded-attr
        if w is not None and w.is_alive():
            return
        with self._lock:
            if self._watchdog is not None and self._watchdog.is_alive():
                return
            self._watchdog = threading.Thread(
                target=self._watch, name="ptpu-lock-watchdog",
                daemon=True)
            self._watchdog.start()

    def _scan(self) -> Optional[Tuple[frozenset, dict]]:
        """One waits-for pass: thread -> lock it waits on -> holder
        threads. A thread-cycle is a deadlock candidate."""
        with self._lock:
            waits = dict(self._waiting)
            holders = {lid: (name, set(tids))
                       for lid, (name, tids) in self._holders.items()}
        # tid -> set of tids it waits on (via the lock's holders)
        graph: Dict[int, Set[int]] = {}
        via: Dict[int, str] = {}
        for tid, (lid, name) in waits.items():
            h = holders.get(lid)
            if not h:
                continue
            graph[tid] = set(h[1]) - {tid}
            via[tid] = name
        # cycle over thread ids
        for start in graph:
            stack = [(start, [start])]
            seen = {start}
            while stack:
                node, path = stack.pop()
                for nxt in graph.get(node, ()):
                    if nxt == start and len(path) > 1:
                        cyc = frozenset(path)
                        return cyc, {
                            "threads": sorted(path),
                            "locks": sorted({via[t] for t in path
                                             if t in via})}
                    if nxt not in seen and nxt in graph:
                        seen.add(nxt)
                        stack.append((nxt, path + [nxt]))
        return None

    def _watch(self) -> None:
        while not self._watchdog_stop.wait(self._watchdog_interval):
            hit = self._scan()
            if hit is None:
                self._suspect = None
                continue
            cyc, info = hit
            # _suspect is touched only by this watchdog thread
            if self._suspect != cyc:  # tpurace: disable=race-check-then-act
                self._suspect = cyc     # confirm on the NEXT scan: a
                continue                # slow critical section is not
            self._suspect = None        # a deadlock
            if cyc in self._deadlocks_dumped:
                continue        # one artifact per distinct wait cycle
            self._deadlocks_dumped.add(cyc)
            frames = sys._current_frames()
            stacks = {
                str(t): "".join(traceback.format_stack(frames[t]))
                for t in cyc if t in frames}
            try:
                from .trace import dump_flight
                path = dump_flight("lock_deadlock", extra=dict(
                    info, holder_stacks=stacks))
                self.deadlock_artifacts.append(path)
            except Exception:   # noqa: BLE001
                pass

    def stop_watchdog(self) -> None:
        self._watchdog_stop.set()
        with self._lock:
            w = self._watchdog
            self._watchdog = None
        if w is not None and w.is_alive():
            # join OUTSIDE self._lock: the watchdog's scan takes it
            w.join(timeout=2 * self._watchdog_interval + 1)
        self._watchdog_stop = threading.Event()

    def snapshot(self) -> dict:
        with self._lock:
            return {
                "edges": {f"{a}->{b}": n
                          for (a, b), n in sorted(self.order_edges.items())},
                "cycle_artifacts": list(self.cycle_artifacts),
                "deadlock_artifacts": list(self.deadlock_artifacts),
            }


_sanitizer: Optional[LockSanitizer] = None
_sanitizer_guard = threading.Lock()


def sanitizer() -> LockSanitizer:
    """The process-wide sanitizer (created on first instrumented
    lock)."""
    global _sanitizer
    if _sanitizer is None:
        with _sanitizer_guard:
            if _sanitizer is None:
                _sanitizer = LockSanitizer()
    return _sanitizer


def reset_sanitizer() -> LockSanitizer:
    """Fresh sanitizer state (tests / race_hunt runs). Locks made
    before the reset keep reporting — into the NEW state."""
    global _sanitizer
    with _sanitizer_guard:
        if _sanitizer is not None:
            _sanitizer.stop_watchdog()
        _sanitizer = LockSanitizer()
    return _sanitizer


# ---------------------------------------------------------------------------
# the instrumented primitive + factories
# ---------------------------------------------------------------------------

class InstrumentedLock:
    """Drop-in for ``threading.Lock``/``RLock`` that reports to the
    sanitizer. Also speaks the ``Condition`` inner-lock protocol
    (``_release_save``/``_acquire_restore``/``_is_owned``) when built
    on an RLock, so ``make_condition`` can wrap one."""

    def __init__(self, name: str, reentrant: bool = False):
        self.name = name
        self.reentrant = reentrant
        self._inner = threading.RLock() if reentrant else threading.Lock()

    def acquire(self, blocking: bool = True, timeout: float = -1) -> bool:
        san = sanitizer()
        if san.in_record():
            return self._inner.acquire(blocking, timeout)
        t0 = time.perf_counter()
        san.note_wait_start(self)
        try:
            got = self._inner.acquire(blocking, timeout)
        finally:
            san.note_wait_end(self)
        if got:
            san.note_acquired(self, time.perf_counter() - t0)
        return got

    def release(self) -> None:
        san = sanitizer()
        if san.in_record():
            self._inner.release()
            return
        hold_ms = san.note_release(self)
        self._inner.release()
        if hold_ms is not None:
            san._observe("ptpu_lock_hold_ms", self.name, hold_ms)

    def locked(self) -> bool:
        inner = self._inner
        if hasattr(inner, "locked"):
            return inner.locked()
        # RLock pre-3.12 has no locked(): probe without blocking
        if inner.acquire(blocking=False):
            inner.release()
            return False
        return True

    def __enter__(self) -> bool:
        return self.acquire()

    def __exit__(self, *exc) -> None:
        self.release()

    # -- threading.Condition inner-lock protocol (RLock-backed) ---------
    def _release_save(self):
        # cond.wait(): the lock is FULLY released however deep the
        # reentry — collapse the sanitizer's depth so the hold ends too
        san = sanitizer()
        if san.in_record():
            return self._inner._release_save()
        for entry in san._held_stack():
            if entry[0] is self:
                entry[2] = 1
                break
        hold_ms = san.note_release(self)
        state = self._inner._release_save()
        if hold_ms is not None:
            san._observe("ptpu_lock_hold_ms", self.name, hold_ms)
        return state

    def _acquire_restore(self, state) -> None:
        san = sanitizer()
        if san.in_record():
            self._inner._acquire_restore(state)
            return
        t0 = time.perf_counter()
        san.note_wait_start(self)
        try:
            self._inner._acquire_restore(state)
        finally:
            san.note_wait_end(self)
        san.note_acquired(self, time.perf_counter() - t0)

    def _is_owned(self) -> bool:
        return self._inner._is_owned()


def make_lock(name: str):
    """A mutex for the named site: plain ``threading.Lock`` unless the
    sanitizer is on."""
    if not lock_san_enabled():
        return threading.Lock()
    return InstrumentedLock(name)


def make_rlock(name: str):
    if not lock_san_enabled():
        return threading.RLock()
    return InstrumentedLock(name, reentrant=True)


def make_condition(name: str):
    """A condition variable whose inner lock is instrumented (RLock
    semantics, matching ``threading.Condition()``'s default)."""
    if not lock_san_enabled():
        return threading.Condition()
    return threading.Condition(InstrumentedLock(name, reentrant=True))
