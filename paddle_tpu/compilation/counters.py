"""XLA compile accounting — the compilation subsystem's syncs.py.

`framework/syncs.py` counts device->host round-trips because the fused
train loop's whole point is amortizing them; this module counts XLA
backend compiles because the warmup/store subsystem's whole point is
eliminating them. One process-global set of counters fed by
`jax.monitoring` events:

- ``backend_compiles``: every invocation of the backend compile path
  (`/jax/core/compile/backend_compile_duration`). NOTE: a persistent
  jax-compilation-cache HIT still routes through this path (the event
  wraps compile-or-load), so this alone over-counts real compiles.
- ``persistent_cache_hits``: `/jax/compilation_cache/cache_hits` — the
  loads that did NOT actually run XLA.
- ``xla_compiles()`` = backend_compiles - persistent_cache_hits: the
  truthful "XLA actually compiled a program" count. An executable
  deserialized from the paddle_tpu executable store fires NOTHING here
  (it never enters jax's compile path at all) — which is exactly the
  cold-start claim tests/test_compilation.py::TestWarmup::
  test_warmup_idempotent_second_pass_compiles_zero asserts.
- ``compile_secs``: wall time spent inside the backend compile path.

A trace (function -> jaxpr), a lowering (jaxpr -> MLIR module) and a
backend compile each also land in the obs flight recorder, where
ambient instrumentation is on, as a span ``compile.trace`` /
``compile.lower`` / ``compile.backend`` (cat ``compile``; end = the
moment the event fired, start = end - duration; ``fun_name`` where the
event carries one), so that a reader can tell one phase's compiles from
another's by WHEN they happened (an inner function's trace lies inside
its caller's: a reader takes overlaps once). The ring is where the
seconds of traces and lowerings live; no counter sums them. Events
shorter than ``RING_FLOOR_S`` (a millisecond) are not recorded (traces
are still counted, backend compiles counted and summed): a training
set-up fires two thousand traces of ``multiply``/``add``/``sqrt``
inside its programs' own traces, 1.4% of the compile seconds and nine
tenths of the events (CPU rehearsal of the benchmark's driver, PR 28),
which would push everything else out of the ring.

Writers (the listeners) fire on whatever thread is compiling —
parallel warmup means concurrent increments, so they serialize on a
lock (compiles are rare; the cost is nil). Readers stay the syncs.py
idiom: plain delta reads on one consumer thread between phases.
"""
from __future__ import annotations

import threading
import time

__all__ = ["backend_compiles", "persistent_cache_hits", "xla_compiles",
           "compile_secs", "traces", "CompileTracker", "install",
           "RING_FLOOR_S"]

_BACKEND_COMPILE_EVT = "/jax/core/compile/backend_compile_duration"
_TRACE_EVT = "/jax/core/compile/jaxpr_trace_duration"
_LOWER_EVT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_CACHE_HIT_EVT = "/jax/compilation_cache/cache_hits"
#: shortest event that lands in the ring
RING_FLOOR_S = 1e-3
# event -> its span in the obs ring
_SPAN_OF = {_TRACE_EVT: "compile.trace", _LOWER_EVT: "compile.lower",
            _BACKEND_COMPILE_EVT: "compile.backend"}

_backend_compiles = 0
_cache_hits = 0
_traces = 0
_compile_secs = 0.0
_installed = False
_install_lock = threading.Lock()
_count_lock = threading.Lock()
_obs_metrics = None      # lazy (compiles, secs, hits) counters; False=off


def _obs() -> tuple:
    """Mirror every compile event into the obs registry (exported on
    /metrics) — same listener, second face, like framework/syncs."""
    global _obs_metrics
    if _obs_metrics is None:
        try:
            from .. import obs
            if not obs.enabled():
                # live read, not cached: obs.set_enabled is tri-state
                # and a later re-enable must start mirroring again
                return (None, None, None)
            reg = obs.metrics.registry
            _obs_metrics = (
                reg.counter("ptpu_xla_backend_compiles_total",
                            "backend compile-path invocations "
                            "(includes persistent-cache loads)"),
                reg.counter("ptpu_xla_compile_seconds_total",
                            "wall seconds inside the backend "
                            "compile path"),
                reg.counter("ptpu_xla_cache_hits_total",
                            "persistent compilation-cache hits"))
        except Exception:    # noqa: BLE001 — accounting must not crash
            _obs_metrics = False
    return _obs_metrics or (None, None, None)


def _on_duration(event: str, duration_secs: float, **kw) -> None:
    global _backend_compiles, _traces, _compile_secs
    span = _SPAN_OF.get(event)
    if span is None:
        return
    now = time.perf_counter()
    if event == _BACKEND_COMPILE_EVT:
        with _count_lock:
            _backend_compiles += 1
            _compile_secs += duration_secs
        compiles, secs, _ = _obs()
        if compiles is not None:
            compiles.inc()
            secs.inc(duration_secs)
    elif event == _TRACE_EVT:
        with _count_lock:
            _traces += 1
    if duration_secs < RING_FLOOR_S:
        return
    from .. import obs
    if obs.enabled():
        args = {"fun_name": str(kw["fun_name"])} if "fun_name" in kw else {}
        obs.record_span(span, now - duration_secs, now, cat="compile",
                        **args)


def _on_event(event: str, **kw) -> None:
    global _cache_hits
    if event == _CACHE_HIT_EVT:
        with _count_lock:
            _cache_hits += 1
        _, _, hits = _obs()
        if hits is not None:
            hits.inc()


def install() -> None:
    """Register the monitoring listeners (idempotent). Importing
    paddle_tpu.compilation does this; events before that are unseen —
    counters are for DELTAS, not process totals."""
    global _installed
    with _install_lock:
        if _installed:
            return
        import jax.monitoring as monitoring
        monitoring.register_event_duration_secs_listener(_on_duration)
        monitoring.register_event_listener(_on_event)
        _installed = True


def backend_compiles() -> int:
    """Backend compile-path invocations (includes persistent-cache
    loads — see module docstring)."""
    return _backend_compiles


def persistent_cache_hits() -> int:
    return _cache_hits


def xla_compiles() -> int:
    """Programs XLA actually compiled (compile-path invocations minus
    persistent-cache loads)."""
    return _backend_compiles - _cache_hits


def traces() -> int:
    return _traces


def compile_secs() -> float:
    return _compile_secs


class CompileTracker:
    """Delta reader over one phase, the ``syncs.SyncTracker`` idiom::

        with CompileTracker() as t:
            ...
        assert t.xla_compiles == 0
    """

    def __enter__(self):
        install()
        self._c0 = _backend_compiles
        self._h0 = _cache_hits
        self._t0 = _traces
        self._s0 = _compile_secs
        return self

    def __exit__(self, *exc):
        self.backend_compiles = _backend_compiles - self._c0
        self.persistent_cache_hits = _cache_hits - self._h0
        self.traces = _traces - self._t0
        self.compile_secs = _compile_secs - self._s0
        self.xla_compiles = self.backend_compiles - \
            self.persistent_cache_hits
        return False

    @property
    def so_far(self) -> int:
        return (_backend_compiles - self._c0) - (_cache_hits - self._h0)
