"""Shared report-artifact emission for the analysis CLIs.

tools/tpulint.py and tools/tpucost.py share one output contract:

- `--json <path>` writes the FULL findings/inventory record atomically
  (.part + rename, so a mid-write kill never leaves a truncated
  file);
- the LAST stdout line is always one terminal JSON record. A failing
  gate is a GOOD record with "gate": "fail" (the measurement landed;
  CI failing is the point), an analyzer crash is {"error": ...}.

One definition here instead of a copy per CLI — callers can only gate
on the artifact if every tool agrees on what a landed record is.
"""
from __future__ import annotations

import json
import os
from typing import Optional, Sequence

__all__ = ["write_report_artifact", "terminal_record"]


def write_report_artifact(path: Optional[str], record: dict) -> None:
    """Atomically write `record` to `path` (no-op when path is None)."""
    if not path:
        return
    with open(path + ".part", "w") as fh:
        json.dump(record, fh, indent=1)
        fh.write("\n")
    os.replace(path + ".part", path)


def terminal_record(record: dict,
                    keys: Sequence[str] = ()) -> str:
    """The one-line terminal JSON (print as the LAST stdout line).
    `keys` selects a summary subset of `record`; empty = whole record."""
    if keys:
        record = {k: record[k] for k in keys if k in record}
    return json.dumps(record)
