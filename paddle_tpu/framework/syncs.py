"""Host-sync accounting for the training/eval hot paths.

A device->host materialization (``float(loss)``, a lazy-loss window
fetch, evaluate's batched loss fetch) is the blocking round-trip the
fused K-step training loop exists to amortize — so the loop's tools
need to COUNT them. tests/test_scan_train.py::
test_train_batch_lazy_and_sync_counter pins the per-window fetch count
through this counter (a dispatch costs no sync, the read costs one);
zero mid-window syncs over a whole fused `fit` window is not checked
by any test.

Deliberately tiny: a process-global counter bumped from
``Tensor.__float__`` and ``hapi.lazy.LossWindow.fetch``. A plain int
under the GIL is plenty for accounting (the consumers read deltas
between phases on one thread); no locks on the hot path.

The same signal feeds the obs metrics registry
(``ptpu_host_syncs_total`` — paddle_tpu.obs, exported on /metrics) so
the fleet view and the in-process delta readers can never disagree:
ONE record site, two faces.
"""
from __future__ import annotations

__all__ = ["record_sync", "sync_count", "SyncTracker"]

_count = 0
_obs_counter = None      # lazy: obs Counter, or False when obs is off


def _obs_record(n: int) -> None:
    global _obs_counter
    if _obs_counter is False:
        return
    try:
        if _obs_counter is None:
            from .. import obs
            if not obs.enabled():
                # disabled is a LIVE read (obs.set_enabled is
                # tri-state): don't cache, the next sync re-checks
                return
            _obs_counter = obs.metrics.registry.counter(
                "ptpu_host_syncs_total",
                "device->host materializations (framework/syncs)")
        _obs_counter.inc(n)
    except Exception:          # noqa: BLE001 — accounting must not crash
        _obs_counter = False


def record_sync(n: int = 1) -> None:
    """Note that a device->host materialization happened."""
    global _count
    _count += n
    _obs_record(n)


def sync_count() -> int:
    """Total host syncs recorded since process start."""
    return _count


class SyncTracker:
    """Delta reader: ``with SyncTracker() as t: ...; t.delta``."""

    def __enter__(self):
        self.start = sync_count()
        return self

    def __exit__(self, *exc):
        self.delta = sync_count() - self.start
        return False

    @property
    def so_far(self) -> int:
        return sync_count() - self.start
