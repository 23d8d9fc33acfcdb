"""Profiler implementation (see package docstring for the reference map).

Re-seated on the obs subsystem (paddle_tpu.obs, ISSUE 8): RecordEvent
scopes land in the SAME ring-buffer flight recorder as the engine's
request spans and the training loop's window spans (cat="profiler"),
and export goes through the ONE Chrome/Perfetto writer
(obs.trace.export_chrome). This class remains the reference-parity
FACE — scheduler states, on_trace_ready, summary tables — over that
single event stream; a Profiler session is just a time window
[start mark, now) onto the shared ring (so a profiled window also
shows whatever the serving/training instrumentation recorded inside
it). MIGRATING.md maps the paddle.profiler surface onto the obs
primitives.
"""
from __future__ import annotations

import contextlib
import json
import os
import threading
import time
from enum import Enum
from typing import Callable, Iterable, List, Optional

import jax

from ..obs import trace as _obs_trace

__all__ = ["Profiler", "ProfilerState", "ProfilerTarget", "RecordEvent",
           "make_scheduler", "export_chrome_tracing",
           "load_profiler_result"]


class ProfilerState(Enum):
    """Parity: paddle.profiler.ProfilerState (profiler.py:79)."""
    CLOSED = 0
    READY = 1
    RECORD = 2
    RECORD_AND_RETURN = 3


class ProfilerTarget(Enum):
    CPU = 0
    GPU = 1
    CUSTOM_DEVICE = 2
    TPU = 3


class RecordEvent(_obs_trace.Span):
    """Host annotation scope.

    Parity: paddle.profiler.RecordEvent (event_tracing.h:43). The
    ungated face of ``obs.trace.Span``: it records into the obs flight
    recorder (cat="profiler") and enters the profiler's own host scope,
    so it shows up inside the XLA xplane trace too. An explicit
    annotation is its own opt-in, so it records even with ambient
    telemetry (PADDLE_TPU_OBS) off.
    """

    __slots__ = ()

    def __init__(self, name: str, event_type=None):
        super().__init__(name, cat="profiler")


def make_scheduler(*, closed: int, ready: int, record: int, repeat: int = 0,
                   skip_first: int = 0) -> Callable[[int], ProfilerState]:
    """Parity: paddle.profiler.make_scheduler."""
    period = closed + ready + record

    def scheduler(step: int) -> ProfilerState:
        if step < skip_first:
            return ProfilerState.CLOSED
        s = step - skip_first
        if repeat and s >= repeat * period:
            return ProfilerState.CLOSED
        pos = s % period
        if pos < closed:
            return ProfilerState.CLOSED
        if pos < closed + ready:
            return ProfilerState.READY
        if pos == period - 1:
            return ProfilerState.RECORD_AND_RETURN
        return ProfilerState.RECORD

    return scheduler


def export_chrome_tracing(dir_name: str, worker_name: Optional[str] = None):
    """Parity: paddle.profiler.export_chrome_tracing — returns an on_trace_
    ready callback writing chrome trace JSON."""

    def handler(prof: "Profiler"):
        os.makedirs(dir_name, exist_ok=True)
        fname = f"{worker_name or 'worker'}_{os.getpid()}" \
                f"_{int(time.time())}.pb.trace.json"
        prof._export_chrome(os.path.join(dir_name, fname))

    return handler


def load_profiler_result(path: str):
    with open(path) as f:
        return json.load(f)


class Profiler:
    """Parity: paddle.profiler.Profiler (profiler.py:344).

    - targets: accepted for API parity; on TPU both host and device land
      in the XLA trace.
    - scheduler: (closed, ready, record) state machine per step.
    - on_trace_ready: callback at RECORD_AND_RETURN (default: chrome
      trace into ./profiler_log + xplane dump for TensorBoard).
    """

    def __init__(self, *, targets: Optional[Iterable] = None,
                 scheduler=None, on_trace_ready=None, timer_only=False,
                 record_shapes=False, profile_memory=False,
                 with_flops=False):
        if isinstance(scheduler, tuple):
            start, end = scheduler
            scheduler = make_scheduler(closed=max(start, 0), ready=0,
                                       record=end - start, repeat=1)
        self._scheduler = scheduler
        self._on_trace_ready = on_trace_ready or export_chrome_tracing(
            "./profiler_log")
        self.timer_only = timer_only
        self._step = 0
        self._state = ProfilerState.CLOSED
        self._xplane_dir = None
        self._xprof_active = False
        # the obs-ring window this session owns: [mark, end_mark] on
        # the perf_counter clock; end_mark stays None while recording
        self._mark = None
        self._end_mark = None
        self._step_times: List[float] = []
        self._last_step_t = None

    # -- lifecycle -------------------------------------------------------
    def start(self):
        self._state = self._scheduler(self._step) if self._scheduler \
            else ProfilerState.RECORD
        if self._state in (ProfilerState.RECORD,
                           ProfilerState.RECORD_AND_RETURN):
            self._begin_record()
        return self

    def stop(self):
        if self._xprof_active:
            self._end_record()
            if self._on_trace_ready:
                self._on_trace_ready(self)
        self._state = ProfilerState.CLOSED

    def step(self, num_samples: Optional[int] = None):
        """Advance the scheduler one training step."""
        now = time.perf_counter()
        if self._last_step_t is not None:
            self._step_times.append(now - self._last_step_t)
        self._last_step_t = now

        self._step += 1
        if self._scheduler is None:
            return
        new = self._scheduler(self._step)
        if new == self._state:
            return
        rec_states = (ProfilerState.RECORD, ProfilerState.RECORD_AND_RETURN)
        if new in rec_states and not self._xprof_active:
            self._begin_record()
        elif new not in rec_states and self._xprof_active:
            self._end_record()
            if self._on_trace_ready:
                self._on_trace_ready(self)
        self._state = new

    def __enter__(self):
        return self.start()

    def __exit__(self, *exc):
        self.stop()
        return False

    # -- recording -------------------------------------------------------
    def _begin_record(self):
        # a recording session is a WINDOW onto the always-on obs ring:
        # mark its start; export/summary read events inside the window
        self._mark = time.perf_counter()
        self._end_mark = None
        if not self.timer_only:
            import tempfile
            self._xplane_dir = tempfile.mkdtemp(prefix="paddle_tpu_prof_")
            try:
                jax.profiler.start_trace(self._xplane_dir)
                self._xprof_active = True
            except Exception:
                self._xprof_active = False
        else:
            self._xprof_active = True

    def _end_record(self):
        if not self.timer_only and self._xplane_dir is not None:
            try:
                jax.profiler.stop_trace()
            except Exception:
                pass
        self._xprof_active = False
        self._end_mark = time.perf_counter()

    # -- export ----------------------------------------------------------
    def _window_events(self):
        """Ring events inside THIS session's window. Both ends are
        bounded: events recorded after stop() must not leak into
        summary()/export() (the old recorder froze at stop), and a
        never-started Profiler owns no window at all — not the whole
        process ring."""
        if self._mark is None:
            return []
        evs = _obs_trace.recorder.events(since_s=self._mark)
        if self._end_mark is not None:
            cutoff = self._end_mark * 1e6
            evs = [e for e in evs if e["ts"] <= cutoff]
        return evs

    def _export_chrome(self, path: str):
        # the ONE Chrome-trace writer (obs.trace) — the legacy format's
        # traceEvents/metadata shape is exactly what it emits
        return _obs_trace.export_chrome(
            path, events=self._window_events(),
            metadata={"xplane_dir": self._xplane_dir,
                      "format": "paddle_tpu chrome trace (obs)"})

    def export(self, path: str, format: str = "json"):
        """Parity: Profiler.export — chrome trace json (the xplane protobuf
        for TensorBoard lives in the dir recorded in metadata)."""
        return self._export_chrome(path)

    def summary(self, sorted_by=None, op_detail=True, thread_sep=False,
                time_unit="ms"):
        """Host-event summary table (reference: profiler_statistic.py).
        Device-side op breakdown lives in the xplane viewed via
        TensorBoard; host RecordEvent scopes are aggregated here."""
        agg = {}
        for e in self._window_events():
            a = agg.setdefault(e["name"], [0, 0.0])
            a[0] += 1
            a[1] += e["dur"] / 1e3  # ms
        lines = [f"{'name':<40} {'calls':>8} {'total_ms':>12}"]
        for name, (calls, ms) in sorted(agg.items(), key=lambda x: -x[1][1]):
            lines.append(f"{name:<40} {calls:>8} {ms:>12.3f}")
        if self._step_times:
            import numpy as np
            ts = np.asarray(self._step_times)
            lines.append(f"steps: {len(ts)}  avg {ts.mean()*1e3:.2f}ms  "
                         f"p50 {np.percentile(ts, 50)*1e3:.2f}ms  "
                         f"p99 {np.percentile(ts, 99)*1e3:.2f}ms")
        table = "\n".join(lines)
        print(table)
        return table
