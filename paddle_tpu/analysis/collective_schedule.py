"""AOT collective-schedule analysis: did the gathers overlap compute?

The ZeRO-3 chunked-overlap schedule (PAPERS.md arXiv 2112.01075; wired
in distributed/parallel_step.py `gather_chained`) claims each layer
group's weight all-gather rides UNDER the previous group's matmuls
instead of front-loading every gather before the first layer. Two
statically-checkable artifacts back that claim, both available without
running a step:

1. the LOWERED (StableHLO) text carries one `optimization_barrier` per
   gathered leaf — the token chain that makes gather i+1 data-dependent
   on gather i's output, so NO backend scheduler can front-load or
   combine the per-layer gathers (`gather_chain_links`);
2. the COMPILED module is scheduled (`is_scheduled=true`), so the
   printed instruction order of the entry computation IS the execution
   schedule — `gather_overlap_report` measures how the all-gathers
   actually interleave with compute, and `diff_schedules` puts two
   programs' schedules side by side (fp32-GSPMD against quantized
   collectives).
"""
from __future__ import annotations

import re
from typing import Dict, List, Tuple

__all__ = ["schedule_events", "gather_overlap_report",
           "gather_chain_links", "diff_schedules"]

_ENTRY_RE = re.compile(r"ENTRY [^{]*\{(.*?)\n\}", re.S)
_COLLECTIVE_RE = re.compile(
    r"=\s*\S+\s+(all-reduce|all-gather|reduce-scatter|"
    r"collective-permute|all-to-all)(?:-start)?\(")
# compute carriers in a post-fusion entry computation: fusions, raw
# dots/convolutions that escaped fusion, and backend custom-calls
# (oneDNN/oneAPI matmul on CPU, Mosaic kernels on TPU)
_COMPUTE_RE = re.compile(
    r"=\s*\S+\s+(fusion|dot|convolution|custom-call)\(")


def schedule_events(compiled_hlo: str) -> List[Tuple[int, str]]:
    """Ordered (instruction_index, kind) events of the entry
    computation, kind one of the collective op names or "compute".
    Only meaningful on a SCHEDULED module (compiled `.as_text()` with
    `is_scheduled=true`) where printed order is execution order; raises
    ValueError otherwise so a caller can't silently diff garbage."""
    if "is_scheduled=true" not in compiled_hlo.split("\n", 1)[0]:
        raise ValueError(
            "schedule_events needs a scheduled module (compiled "
            "HloModule with is_scheduled=true); got unscheduled text — "
            "pass compiled.as_text(), not lowered StableHLO")
    m = _ENTRY_RE.search(compiled_hlo)
    if m is None:
        raise ValueError("no ENTRY computation found in HLO text")
    events: List[Tuple[int, str]] = []
    for i, line in enumerate(m.group(1).splitlines()):
        cm = _COLLECTIVE_RE.search(line)
        if cm is not None:
            events.append((i, cm.group(1)))
            continue
        if _COMPUTE_RE.search(line):
            events.append((i, "compute"))
    return events


def gather_overlap_report(compiled_hlo: str) -> Dict[str, object]:
    """Interleaving metrics for the all-gathers in a scheduled program:

    - n_gathers / n_compute: event counts;
    - interleaved_gaps: adjacent gather pairs with >= 1 compute event
      scheduled BETWEEN them — a front-loaded schedule (every gather
      in one block before the first matmul) scores 0;
    - max_gather_run: longest run of gathers with no compute between
      (combined/front-loaded schedules show one run == n_gathers);
    - front_loaded: True when every gather precedes every compute.
    """
    events = schedule_events(compiled_hlo)
    kinds = [k for _, k in events]
    n_g = sum(1 for k in kinds if k == "all-gather")
    n_c = sum(1 for k in kinds if k == "compute")
    gaps = 0
    run = 0
    max_run = 0
    since_last_gather_compute = False
    seen_gather = False
    for k in kinds:
        if k == "all-gather":
            if seen_gather and since_last_gather_compute:
                gaps += 1
                run = 1
            else:
                run += 1
            max_run = max(max_run, run)
            seen_gather = True
            since_last_gather_compute = False
        elif k == "compute":
            since_last_gather_compute = True
    first_c = kinds.index("compute") if n_c else len(kinds)
    last_g = (len(kinds) - 1 - kinds[::-1].index("all-gather")) \
        if n_g else -1
    return {"n_gathers": n_g, "n_compute": n_c,
            "interleaved_gaps": gaps, "max_gather_run": max_run,
            "front_loaded": bool(n_g and n_c and last_g < first_c)}


def gather_chain_links(lowered_text: str) -> int:
    """Number of optimization_barrier chain links in LOWERED text (the
    `.lower(...).as_text()` StableHLO) — one per stage-3 gathered leaf
    when the chunked-overlap schedule is active, 0 in fp32/GSPMD mode.
    XLA legally drops the barriers after scheduling, so this must read
    the pre-optimization module."""
    return len(re.findall(r"\boptimization_barrier\b", lowered_text))


def diff_schedules(compiled_a: str, compiled_b: str,
                   label_a: str = "a", label_b: str = "b") -> Dict:
    """Side-by-side schedule comparison of two compiled programs:
    per-kind event counts plus each side's gather_overlap_report."""
    out: Dict[str, object] = {}
    for label, text in ((label_a, compiled_a), (label_b, compiled_b)):
        counts: Dict[str, int] = {}
        for _, k in schedule_events(text):
            counts[k] = counts.get(k, 0) + 1
        out[label] = {"counts": counts,
                      "overlap": gather_overlap_report(text)}
    return out
