"""Process-global compile log — per-program compile accounting as a
JSON-ready record.

`framework/syncs.py` gives the training loop its host-sync ledger; this
is the same idea for program compiles: every warmup / AOT compile /
store load appends one record (name, source, trace_s, compile_s,
signature), and consumers — ``/healthz``, ``tools/warmup.py`` — read
one summary dict instead of re-deriving state. With
``PADDLE_TPU_COMPILE_LOG=<path>`` the log is also mirrored to disk
(atomic rewrite per append) so a crashed process leaves its compile
history behind.
"""
from __future__ import annotations

import json
import os
import threading
import time
from typing import Dict, List, Optional

from . import counters

__all__ = ["record", "records", "summary", "reset"]

_lock = threading.Lock()
_records: List[dict] = []
_started = time.time()


def record(rec: dict) -> dict:
    """Append one compile-log record (a dict at least carrying
    ``name`` and ``source``); returns it. Timestamps are added here."""
    rec = dict(rec)
    rec.setdefault("t", round(time.time() - _started, 3))
    with _lock:
        _records.append(rec)
    path = os.environ.get("PADDLE_TPU_COMPILE_LOG")
    if path:
        try:
            with _lock:
                snap = list(_records)
            tmp = f"{path}.tmp{os.getpid()}"
            with open(tmp, "w") as fh:
                json.dump({"records": snap, "summary": summary()}, fh,
                          indent=1)
            os.replace(tmp, path)
        except OSError:
            pass
    return rec


def records() -> List[dict]:
    with _lock:
        return list(_records)


def summary() -> Dict[str, object]:
    """One dict for /healthz and bench output: how many programs came
    from where, plus the process-wide compile counters."""
    with _lock:
        recs = list(_records)
    by_source: Dict[str, int] = {}
    for r in recs:
        src = r.get("source", "unknown")
        by_source[src] = by_source.get(src, 0) + 1
    return {
        "programs": len(recs),
        "by_source": by_source,
        "compile_wall_s": round(sum(r.get("compile_s", 0.0)
                                    for r in recs), 3),
        "backend_compiles": counters.backend_compiles(),
        "persistent_cache_hits": counters.persistent_cache_hits(),
        "xla_compiles": counters.xla_compiles(),
    }


def reset() -> None:
    """Test hook: empty the in-memory log (counters keep running)."""
    with _lock:
        _records.clear()
