"""tpuprof — measured runtime kernel attribution over registry programs.

tpucost (hlo_cost.py) MODELS each registered program — FLOPs, HBM bytes
and a roofline time per kernel — but models drift from machines. This
module is the measurement half the MFU campaign's fusion loop needs
("Operator Fusion in XLA", PAPERS.md 2301.13062, prescribes an
op-TIME-weighted fusion report; MPK-style mega-kernelization, PAPERS.md
2512.22219, needs that report as its target list): run a program under
the programmatic ``jax.profiler``, parse the chrome trace it emits
(stdlib gzip+json — no TensorBoard), and JOIN the measured
per-kernel device time against ``hlo_cost.collect_kernels``' modeled
inventory by kernel name. Per program that yields:

- a time-weighted fusion-class histogram (where the *seconds* go, not
  the kernel counts);
- a measured-vs-modeled roofline ratio per kernel and for the whole
  dispatch (how far the program sits above what the chip could do);
- the top unfused chains of PR 6 re-ranked by MEASURED time — the
  bytes-ranked candidate list turned into a seconds-ranked work list.

Degrade contract (tests/test_runtime_profile.py): a CPU backend's
trace has no device plane — only ``/host:CPU`` dispatch events — so the
report keeps the measured wall-time-per-dispatch (median-of-N) and
marks the join unavailable; anchors that need kernel attribution are
SKIPPED with a recorded reason instead of silently passing.

Gate (tools/tpuprof_baseline.json, via tools/tpuprof.py):

- ``budgets``: per-program measured dispatch-time medians. This host
  jitters at seconds scale, so the ratchet is noise-tolerant: a run
  fails only past ``budget * tolerance`` (tolerance lives in the
  baseline); ``--update-baseline`` re-pins the medians (and locks wins
  in) while anchors/notes/tolerance survive.
- ``anchors``: hand-set measured invariants — ``matmul_time_share_floor``
  (train step device time must stay matmul-dominated) and
  ``measured_vs_roofline`` (the decode tick must not drift further from
  its modeled roofline) — evaluated whenever a device plane exists,
  loud-skipped when not.

Pure parsing/join/gate code here has no jax dependency (fixture-driven
tests run with ZERO compiles); the run-under-profiler helpers import
jax lazily.
"""
from __future__ import annotations

import glob
import gzip
import json
import os
import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

from .findings import (PROF_ANCHOR, PROF_BUDGET, STALE_PROF_PROGRAM,
                       Finding, Severity)
from .hlo_cost import CHIP_SPECS, DEFAULT_CHIP, ChipSpec, KernelCost

__all__ = [
    "DeviceProfile", "load_trace_events", "device_op_times",
    "category_of", "normalize_kernel_name",
    "join_measured_modeled", "time_weighted_histogram",
    "time_weighted_chains", "runtime_report",
    "hlo_op_scopes", "read_scope", "by_scope",
    "host_example_args", "measure_dispatch", "trace_dispatches",
    "profile_program",
    "load_profile_baseline", "updated_profile_baseline",
    "check_profile_baseline", "DEFAULT_TOLERANCE",
]

# dispatch-time ratchet band: measured_median > budget * tolerance
# fails. 2.5x on a shared 1-core host whose seconds-scale jitter is
# documented in every bench (PERF.md); a real regression (an extra
# compile-per-call, a dropped fusion doubling a tick) clears it easily.
DEFAULT_TOLERANCE = 2.5


# ---------------------------------------------------------------------------
# chrome-trace parsing (device + host lanes)
# ---------------------------------------------------------------------------

@dataclass
class DeviceProfile:
    """Aggregated device-lane view of one chrome trace.

    ``per_op`` maps kernel (HLO instruction) name -> total device us
    across the traced window and ``per_op_self`` the same less the time
    of operations nested inside it (a ``while`` holds its body's);
    ``op_category`` keeps the profiler's own ``hlo_category`` label
    where present. ``had_device`` False means
    the trace came from a backend with no device plane (CPU) and the
    caller must degrade to wall-time-only reporting."""
    per_op: Dict[str, float] = field(default_factory=dict)
    per_op_self: Dict[str, float] = field(default_factory=dict)
    op_category: Dict[str, str] = field(default_factory=dict)
    had_device: bool = False
    host_dispatch_events: int = 0

    @property
    def total_us(self) -> float:
        return sum(self.per_op.values())


def load_trace_events(logdir: str) -> List[dict]:
    """Every traceEvent from the ``*.trace.json[.gz]`` files a
    ``jax.profiler`` session wrote under ``logdir`` (stdlib gzip+json —
    no TensorBoard/XProf dependency)."""
    events: List[dict] = []
    for pattern in ("*.trace.json.gz", "*.trace.json"):
        for path in sorted(glob.glob(
                os.path.join(logdir, "**", pattern), recursive=True)):
            opener = gzip.open if path.endswith(".gz") else open
            with opener(path) as fh:
                doc = json.load(fh)
            events.extend(doc.get("traceEvents", []) or [])
    return events


# host events that mark one executable dispatch (per backend family):
# the CPU client's execute, the PJRT stream executor's launch, and the
# generic RunExecutable — counted so a host-only trace still reports
# how many dispatches the profiled window actually saw
_HOST_DISPATCH_MARKERS = ("ExecuteSharded", "TfrtCpuExecutable::Execute",
                          "PjRtStreamExecutorLoadedExecutable::Execute",
                          "RunExecutable")


def device_op_times(events: Sequence[dict]) -> DeviceProfile:
    """Aggregate per-op durations from the DEVICE lanes of a chrome
    trace. Only the "XLA Ops" lane holds per-op events; the "Steps" /
    "XLA Modules" lanes carry whole-step spans that would double every
    total if summed alongside. Host-only traces (CPU backend) return
    ``had_device=False`` with the dispatch-event count instead."""
    prof = DeviceProfile()
    device_pids = set()
    for e in events:
        if e.get("ph") == "M" and e.get("name") == "process_name" and \
                "/device:" in str(e.get("args", {}).get("name", "")):
            device_pids.add(e.get("pid"))
    op_tids = set()
    for e in events:
        if e.get("ph") == "M" and e.get("name") == "thread_name" and \
                e.get("pid") in device_pids and \
                "XLA Ops" in str(e.get("args", {}).get("name", "")):
            op_tids.add((e.get("pid"), e.get("tid")))
    lanes: Dict[tuple, list] = {}
    for e in events:
        if e.get("ph") != "X":
            continue
        name = str(e.get("name", "?"))
        if e.get("pid") in device_pids:
            prof.had_device = True
            if op_tids and (e.get("pid"), e.get("tid")) not in op_tids:
                continue
            prof.per_op[name] = prof.per_op.get(name, 0.0) + \
                float(e.get("dur", 0.0))
            t0 = float(e.get("ts", 0.0))
            lanes.setdefault((e.get("pid"), e.get("tid")), []).append(
                (name, t0, t0 + float(e.get("dur", 0.0))))
            args = e.get("args") or {}
            cat = args.get("hlo_category") or args.get("category")
            if cat:
                prof.op_category[name] = str(cat)
        elif any(m in name for m in _HOST_DISPATCH_MARKERS):
            prof.host_dispatch_events += 1
    for lane in lanes.values():
        for name, us in self_times(lane).items():
            prof.per_op_self[name] = prof.per_op_self.get(name, 0.0) + us
    return prof


def self_times(events: Sequence[Tuple[str, float, float]]
               ) -> Dict[str, float]:
    """name -> time not covered by an event nested inside it, for one
    lane's (name, start, end) events; nesting is by containment."""
    out: Dict[str, float] = {}
    stack: List[Tuple[str, float, float]] = []
    for name, s, e in sorted(events, key=lambda ev: (ev[1], -ev[2])):
        while stack and stack[-1][2] <= s:
            stack.pop()
        if stack:                       # take my time out of my parent's
            parent = stack[-1]
            out[parent[0]] = out.get(parent[0], 0.0) - (min(e, parent[2]) - s)
        out[name] = out.get(name, 0.0) + (e - s)
        stack.append((name, s, e))
    return out


def category_of(name: str, op_cat: Optional[Dict[str, str]] = None) -> str:
    """Display category for one kernel name: the profiler's own
    ``hlo_category`` when recorded, else a name-pattern fallback."""
    if op_cat and op_cat.get(name):
        return op_cat[name]
    n = name.lower()
    for pat, cat in (("dot", "matmul"), ("conv", "conv"),
                     ("all-reduce", "collective"),
                     ("all-gather", "collective"),
                     ("reduce-scatter", "collective"),
                     ("collective-permute", "collective"),
                     ("custom-call", "custom-call (pallas/lib)"),
                     ("fusion", "fusion"), ("copy", "copy"),
                     ("scatter", "scatter/gather"),
                     ("gather", "scatter/gather"),
                     ("reduce", "reduce"), ("sort", "sort")):
        if pat in n:
            return cat
    return "other"


def normalize_kernel_name(name: str) -> str:
    """Join key between trace event names and HLO instruction names:
    the profiler drops the ``%`` sigil and may append a ``.N`` dedup
    suffix the HLO text lacks (or vice versa) — strip the sigil and
    whitespace, keep the rest verbatim (suffixes are real identity:
    ``fusion.3`` and ``fusion.30`` are different kernels)."""
    return name.strip().lstrip("%")


# ---------------------------------------------------------------------------
# measured <-> modeled join
# ---------------------------------------------------------------------------

def _aggregate_modeled(kernels: Sequence[KernelCost],
                       chip: ChipSpec) -> Dict[str, dict]:
    """Modeled kernels keyed by normalized name. collect_kernels
    multiplies loop bodies by their trip counts already; two kernels
    sharing a name (XLA-deduplicated computations) merge — the join is
    by-name because that is all the trace carries."""
    out: Dict[str, dict] = {}
    for k in kernels:
        key = normalize_kernel_name(k.name)
        m = out.setdefault(key, {
            "name": key, "class": k.klass, "op": k.opcode,
            "flops": 0.0, "matmul_flops": 0.0, "hbm_bytes": 0,
            "roofline_us": 0.0, "trip": 0})
        m["flops"] += k.flops
        m["matmul_flops"] += k.matmul_flops
        m["hbm_bytes"] += k.hbm_bytes
        m["roofline_us"] += k.roofline_seconds(chip) * 1e6
        m["trip"] += k.trip
    return out


def join_measured_modeled(per_op_us: Dict[str, float],
                          kernels: Sequence[KernelCost],
                          chip: "str | ChipSpec" = DEFAULT_CHIP,
                          dispatches: int = 1) -> dict:
    """JOIN measured device time (``per_op_us``, totals over
    ``dispatches`` executions) with the modeled kernel inventory.

    Returns a dict with per-kernel rows (measured us per dispatch,
    modeled roofline us, measured/roofline ratio, class, bytes/flops),
    the TIME-WEIGHTED join rate (what fraction of measured device time
    found a modeled kernel — the honesty number the report leads with),
    and the measured-but-unmodeled / modeled-but-unmeasured leftovers."""
    if isinstance(chip, str):
        chip = CHIP_SPECS[chip]
    dispatches = max(1, int(dispatches))
    modeled = _aggregate_modeled(kernels, chip)
    rows: List[dict] = []
    joined_us = 0.0
    unjoined: List[Tuple[str, float]] = []
    for name, us in per_op_us.items():
        key = normalize_kernel_name(name)
        us_per = us / dispatches
        m = modeled.get(key)
        if m is None:
            unjoined.append((key, us_per))
            continue
        joined_us += us
        ratio = (us_per / m["roofline_us"]) if m["roofline_us"] else None
        rows.append({
            "name": key, "class": m["class"], "op": m["op"],
            "measured_us": round(us_per, 3),
            "roofline_us": round(m["roofline_us"], 3),
            "measured_vs_roofline":
                round(ratio, 3) if ratio is not None else None,
            "flops": m["flops"], "matmul_flops": m["matmul_flops"],
            "hbm_bytes": m["hbm_bytes"],
        })
    rows.sort(key=lambda r: r["measured_us"], reverse=True)
    unjoined.sort(key=lambda x: x[1], reverse=True)
    total_us = sum(per_op_us.values())
    measured_names = {normalize_kernel_name(n) for n in per_op_us}
    unmeasured = sorted(set(modeled) - measured_names)
    return {
        "available": True,
        "rows": rows,
        "join_rate_time_weighted":
            round(joined_us / total_us, 4) if total_us else 0.0,
        "measured_total_us": round(total_us / dispatches, 3),
        "unjoined_us": round((total_us - joined_us) / dispatches, 3),
        "unjoined_top": [{"name": n, "measured_us": round(u, 3)}
                         for n, u in unjoined[:10]],
        "modeled_unmeasured_kernels": len(unmeasured),
    }


def time_weighted_histogram(join: dict) -> Dict[str, float]:
    """Measured device us per dispatch summed by modeled kernel CLASS —
    the op-time-weighted fusion histogram (vs tpucost's count-weighted
    one). Unjoined time lands in ``unattributed`` so the histogram
    always sums to the measured total."""
    hist: Dict[str, float] = {}
    for r in join.get("rows", ()):
        hist[r["class"]] = round(
            hist.get(r["class"], 0.0) + r["measured_us"], 3)
    if join.get("unjoined_us"):
        hist["unattributed"] = join["unjoined_us"]
    return hist


def matmul_time_share(join: dict) -> Optional[float]:
    """Fraction of measured device time spent in kernels whose MODELED
    FLOPs are matmul (standalone dots + fusions containing them). None
    when the join found nothing — the anchor must skip, not pass."""
    total = join.get("measured_total_us") or 0.0
    if not join.get("available") or not total:
        return None
    mm = sum(r["measured_us"] for r in join["rows"]
             if r["matmul_flops"] > 0)
    return round(mm / total, 4)


def time_weighted_chains(join: dict, chains: Sequence[dict],
                         limit: int = 5) -> List[dict]:
    """Re-rank PR 6's bytes-ranked unfused chains by MEASURED time: a
    chain's measured_us is the summed device time of its member
    kernels. Chains none of whose kernels appeared on the device lane
    are dropped (they cost nothing where the seconds are)."""
    by_name = {r["name"]: r["measured_us"] for r in join.get("rows", ())}
    out = []
    for c in chains:
        us = sum(by_name.get(normalize_kernel_name(n), 0.0)
                 for n in c.get("kernels", ()))
        if us <= 0:
            continue
        cc = dict(c)
        cc["measured_us"] = round(us, 3)
        out.append(cc)
    out.sort(key=lambda c: c["measured_us"], reverse=True)
    return out[:limit]


# ---------------------------------------------------------------------------
# whose time it is: instruction -> scope of the program -> (region, pass)
# ---------------------------------------------------------------------------
#
# The program names itself (`nn.Layer.__call__` runs every forward under
# `jax.named_scope(<the key its parent registered it under>)`; the
# trainers add `head_loss`, `optimizer`, `grad_accumulate`), the names
# survive XLA's optimisation in each instruction's
# ``metadata={op_name="..."}``, and JAX marks the pass itself:
# ``jvp(..)`` forward, ``transpose(jvp(..))`` backward,
# ``checkpoint/rematted_computation`` the recomputed forward.

# JAX's own path tokens that are no scope of the program
_JAX_TOKENS = frozenset((
    "while", "body", "cond", "body_fun", "cond_fun", "scan", "switch",
    "closed_call", "core_call", "checkpoint", "rematted_computation",
    "remat", "remat2", "pjit", "shard_map", "custom_jvp_call",
    "custom_vjp_call", "custom_vjp_call_jaxpr", "custom_lin"))
_BRANCH_RE = re.compile(r"^branch_\d+_fun$")
_WRAPPED_RE = re.compile(r"^([\w.\-]*)\((.*)\)$")
_INDEX_RE = re.compile(r"(?:^|_)\d+$")
_NAME_RE = re.compile(r"^[\w.\-]+$")     # a scope the program could give
_UPDATE_SCOPES = ("optimizer", "grad_accumulate")
# the scopes the trainers give where no layer stands: one of these on a
# path owns it whatever layer runs inside (a loss layer, say)
_TRAINER_SCOPES = ("head_loss",) + _UPDATE_SCOPES


def _split_path(path: str) -> List[str]:
    """``a/jvp(b/c)/d`` -> [a, jvp(b/c), d]: ``/`` outside brackets."""
    out, depth, cur = [], 0, []
    for ch in path:
        if ch == "(":
            depth += 1
        elif ch == ")":
            depth = max(0, depth - 1)
        if ch == "/" and depth == 0:
            out.append("".join(cur))
            cur = []
        else:
            cur.append(ch)
    out.append("".join(cur))
    return out


def read_scope(path: str, instruction: str = "") -> dict:
    """THE rule that reads one ``op_name`` path. Pure. ``instruction``
    is the HLO instruction's own name where the caller has it: a Pallas
    kernel that was given a name is entered through a scope of that name
    (the library's wrapper may open it once more) and its instruction
    carries the name too, so on a kernel's path (one that ends in
    ``pallas_call``) a scope equal to it (less the ``.N``) names the
    kernel and no layer. Any other instruction keeps a scope of its own
    name: XLA names a ``sort`` instruction ``sort.N`` whatever made it,
    and the expert layer's ``sort`` scope holds two.

    Pass: ``update`` if ``optimizer`` or ``grad_accumulate`` is on the
    path, else ``recompute`` if ``rematted_computation``, else
    ``backward`` if ``transpose(``, else ``forward``.

    Scope: the path less JAX's own tokens (``jit(..)`` whole; the
    wrappers ``jvp(..)``/``transpose(..)``/``vmap(..)`` opened, what
    they wrap kept; ``while``, ``body``, ``cond``, ``closed_call``,
    ``checkpoint``, ``rematted_computation``; the primitive at the
    tail), a layer's index stripped (``block_7`` -> ``block``); of
    several paths joined by ``;`` the first.

    Region, for the summary: the innermost of the trainers' own scopes
    on the path (``head_loss``, ``optimizer``, ``grad_accumulate``);
    else the innermost scope, which is the name a layer was registered
    under, whatever the model calls its layers (``attn`` or
    ``self_attn``, ``ln_1`` -> ``ln`` or ``input_layernorm``; a
    block's residual adds read ``block``) -- except that where that
    scope is one that holds a loop (a ``ScannedStack``: its own
    operations stack and slice the saved activations and gradients)
    the region is ``scan_carry``; under no scope of the program at all,
    ``unscoped``. A caller that wants coarser rows (all of attention,
    every norm) groups by ``scope``, which keeps the whole path."""
    raw = _split_path(path.split(";", 1)[0])
    tail, raw = raw[-1], raw[:-1]                   # the primitive
    own = (re.sub(r"(\.\d+)+$", "", instruction.lstrip("%")) or None) \
        if tail == "pallas_call" else None
    scopes: List[str] = []      # the program's scopes, outermost first
    loops = set()               # indices into `scopes` that hold a loop
    backward = recompute = False
    for tok in raw:
        while True:             # open jvp(..), transpose(..), vmap(..)
            m = _WRAPPED_RE.match(tok)
            if m is None:
                break
            if m.group(1) == "transpose":
                backward = True
            tok = "" if m.group(1) in ("jit", "pjit") else m.group(2)
        if tok == "rematted_computation":
            recompute = True
        if tok == "while" and scopes:
            loops.add(len(scopes) - 1)
        if not tok or tok in _JAX_TOKENS or _BRANCH_RE.match(tok) \
                or not _NAME_RE.match(tok):
            continue            # JAX's own, or a library's (einsum spec)
        if tok == own:
            continue            # the kernel's name, not a layer's
        tok = _INDEX_RE.sub("", tok)
        if tok:
            scopes.append(tok)
    if tail == "while" and scopes:      # the loop's own instruction
        loops.add(len(scopes) - 1)
    if any(s in _UPDATE_SCOPES for s in scopes):
        pass_ = "update"
    elif recompute:
        pass_ = "recompute"
    else:
        pass_ = "backward" if backward else "forward"
    if not scopes:
        region = "unscoped"
    else:
        region = next((s for s in reversed(scopes)
                       if s in _TRAINER_SCOPES), None)
        if region is None:
            region = "scan_carry" if len(scopes) - 1 in loops \
                else scopes[-1]
    return {"pass": pass_, "scope": "/".join(scopes), "region": region}


def hlo_op_scopes(hlo_text: str) -> Dict[str, str]:
    """{HLO instruction name: ``op_name`` path} for EVERY instruction of
    a compiled program's text (``""`` where none is found). An
    instruction the compiler made late has no path of its own: one that
    calls a computation (a fusion) takes that computation's root's, or
    its first instruction's that has one; failing that (a copy, a
    reduction split in two) it takes its first operand's, which made
    the value it moves."""
    from .hlo_cost import parse_hlo_module
    module = parse_hlo_module(hlo_text)
    out: Dict[str, str] = {}
    for comp in module.computations.values():
        for ins in comp.instrs:     # operands come before their users
            path = ins.attrs.get("op_name", "")
            called = module.computations.get(ins.attrs.get("calls", ""))
            if not path and called is not None:
                root = called.root
                path = (root.attrs.get("op_name", "") if root else "") \
                    or next((i.attrs["op_name"] for i in called.instrs
                             if i.attrs.get("op_name")), "")
            if not path and ins.operands and ins.opcode != "parameter":
                made_by = comp.by_name.get(ins.operands[0])
                if made_by is not None and made_by.opcode != "parameter":
                    path = out.get(made_by.name, "")    # not an argument's
            out[ins.name] = path
    return out


def by_scope(per_op_self_s: Dict[str, float],
             op_scopes: Dict[str, str]) -> dict:
    """Seconds and share of device busy time per (region, pass): the
    self time of each instruction (``per_op_self_s``: name -> seconds
    not covered by an operation nested inside it, summed over the
    traced window) joined with `hlo_op_scopes`' table by name. Pure
    function of two dicts. An instruction the table does not know, or
    knows no path for, is ``unscoped``: that share is printed, not
    hidden."""
    rows: Dict[Tuple[str, str], float] = {}
    unknown = 0.0
    for name, secs in per_op_self_s.items():
        path = op_scopes.get(normalize_kernel_name(name))
        if path is None:
            unknown += secs
        got = read_scope(path, name) if path else \
            {"region": "unscoped", "pass": "forward"}
        key = (got["region"], got["pass"])
        rows[key] = rows.get(key, 0.0) + secs
    total = sum(rows.values())

    def share(s):
        return round(s / total, 6) if total else 0.0
    table = [{"region": r, "pass": p, "seconds": s, "share": share(s)}
             for (r, p), s in sorted(rows.items(), key=lambda kv: -kv[1])]
    passes: Dict[str, float] = {}
    regions: Dict[str, float] = {}
    for (r, p), s in rows.items():
        passes[p] = passes.get(p, 0.0) + s
        regions[r] = regions.get(r, 0.0) + s
    return {
        "busy_s": total,
        "rows": table,
        "by_pass": {p: {"seconds": s, "share": share(s)}
                    for p, s in sorted(passes.items(),
                                       key=lambda kv: -kv[1])},
        "by_region": {r: {"seconds": s, "share": share(s)}
                      for r, s in sorted(regions.items(),
                                         key=lambda kv: -kv[1])},
        "unscoped_share": share(regions.get("unscoped", 0.0)),
        "not_in_table_share": share(unknown),
    }


# ---------------------------------------------------------------------------
# per-program report
# ---------------------------------------------------------------------------

def _dispatch_stats(dispatch_s: Sequence[float]) -> dict:
    times = sorted(float(t) for t in dispatch_s)
    if not times:
        return {"n": 0}
    n = len(times)
    med = times[n // 2] if n % 2 else (times[n // 2 - 1]
                                       + times[n // 2]) / 2.0
    return {"n": n,
            "median_ms": round(med * 1e3, 3),
            "mean_ms": round(sum(times) / n * 1e3, 3),
            "min_ms": round(times[0] * 1e3, 3),
            "max_ms": round(times[-1] * 1e3, 3)}


def runtime_report(name: str, *, hlo_text: Optional[str] = None,
                   kernels: Optional[Sequence[KernelCost]] = None,
                   events: Optional[Sequence[dict]] = None,
                   profile: Optional[DeviceProfile] = None,
                   dispatch_s: Sequence[float] = (),
                   dispatches_profiled: int = 1,
                   chip: "str | ChipSpec" = DEFAULT_CHIP,
                   geometry: Optional[dict] = None,
                   op_scopes: Optional[Dict[str, str]] = None,
                   top: int = 15) -> dict:
    """Compose ONE program's measured-runtime record: wall dispatch
    stats + (when a device plane exists) the measured<->modeled join,
    time-weighted fusion histogram, per-kernel roofline ratios, and
    the time-ranked unfused chains. Pass either ``hlo_text`` (parsed
    here) or a pre-collected ``kernels`` list, and either raw trace
    ``events`` or a pre-parsed ``profile``. With ``op_scopes``
    (`hlo_op_scopes` of the program's text, or a trainer's
    ``op_scopes()``) the record gains ``by_scope``: device seconds per
    dispatch by (region, pass) of the program's own names."""
    from .fusion import unfused_chains
    from .hlo_cost import collect_kernels, parse_hlo_module
    if isinstance(chip, str):
        chip = CHIP_SPECS[chip]
    if kernels is None:
        kernels = collect_kernels(parse_hlo_module(hlo_text or ""))
    if profile is None:
        profile = device_op_times(events or [])

    modeled_roofline_us = sum(k.roofline_seconds(chip)
                              for k in kernels) * 1e6
    rec = {
        "program": name,
        "chip": chip.name,
        "dispatch": _dispatch_stats(dispatch_s),
        "had_device_plane": profile.had_device,
        "host_dispatch_events": profile.host_dispatch_events,
        "modeled": {
            "kernel_count": sum(1 for k in kernels
                                if k.klass != "scalar"),
            "flops": sum(k.flops for k in kernels),
            "hbm_bytes": sum(k.hbm_bytes for k in kernels),
            "matmul_flop_share": round(
                sum(k.matmul_flops for k in kernels)
                / max(sum(k.flops for k in kernels), 1e-30), 6),
            "roofline_us": round(modeled_roofline_us, 3),
            # the program's kernels by modeled roofline weight — named
            # even on the degraded (no-device-plane) path, so a report
            # always says WHAT it measured, not just how long
            "top_kernels": [
                normalize_kernel_name(k.name) for k in sorted(
                    kernels, key=lambda k: -k.roofline_seconds(chip)
                )[:10]],
        },
        "geometry": dict(geometry or {}),
    }
    if profile.had_device:
        join = join_measured_modeled(profile.per_op, kernels, chip,
                                     dispatches_profiled)
        rec["join"] = dict(join)
        rec["join"]["rows"] = join["rows"][:top]
        rec["time_weighted_fusion_histogram"] = \
            time_weighted_histogram(join)
        rec["matmul_time_share"] = matmul_time_share(join)
        rec["measured_vs_roofline"] = round(
            join["measured_total_us"] / modeled_roofline_us, 3) \
            if modeled_roofline_us else None
        rec["top_unfused_by_time"] = time_weighted_chains(
            join, unfused_chains(list(kernels), limit=max(20, top)))
        if op_scopes is not None:
            n = max(1, int(dispatches_profiled))
            rec["by_scope"] = by_scope(
                {k: us / 1e6 / n for k, us in profile.per_op_self.items()},
                op_scopes)
    else:
        rec["join"] = {
            "available": False,
            "reason": "no device plane in trace — CPU backend records "
                      "host events only; kernel attribution needs a "
                      "TPU run (wall-time-per-dispatch kept)",
        }
        rec["time_weighted_fusion_histogram"] = {}
        rec["matmul_time_share"] = None
        rec["measured_vs_roofline"] = None
        rec["top_unfused_by_time"] = []
    return rec


# ---------------------------------------------------------------------------
# run-under-profiler helpers (lazy jax)
# ---------------------------------------------------------------------------

def host_example_args(args: tuple) -> tuple:
    """Registry example args pulled back to HOST numpy. Several sites
    donate buffers (the decode tick donates its cache, TrainStep its
    state); executing the REAL site object twice over device-resident
    example args would die on the donated buffer. Host leaves re-upload
    per call, so donation only ever eats the fresh copy. Typed PRNG
    keys cannot become numpy and stay as-is — no registered site
    donates its key argument."""
    import jax
    import numpy as np

    def pull(x):
        dt = getattr(x, "dtype", None)
        if dt is not None and jax.dtypes.issubdtype(
                dt, jax.dtypes.prng_key):
            return x
        return np.asarray(x)
    return jax.tree_util.tree_map(pull, args)


def measure_dispatch(fn, args: tuple, rounds: int = 3,
                     inner: int = 3) -> List[float]:
    """Per-dispatch wall seconds, ``rounds`` samples of ``inner``
    dispatches each (block_until_ready closes every sample's clock).
    The caller interleaves programs ACROSS rounds so one background
    spike cannot land on one program only."""
    import jax
    out = []
    for _ in range(max(1, rounds)):
        t0 = _now()
        for _ in range(max(1, inner)):
            jax.block_until_ready(fn(*args))
        out.append((_now() - t0) / max(1, inner))
    return out


def _now() -> float:
    import time
    return time.perf_counter()


def trace_dispatches(fn, args: tuple, dispatches: int,
                     logdir: str) -> List[dict]:
    """Run ``dispatches`` executions under a programmatic
    ``jax.profiler`` session into ``logdir`` and return the parsed
    trace events. One session per program keeps attribution clean —
    every device event in the trace belongs to this program."""
    import jax
    os.makedirs(logdir, exist_ok=True)
    jax.profiler.start_trace(logdir)
    try:
        for _ in range(max(1, dispatches)):
            jax.block_until_ready(fn(*args))
    finally:
        jax.profiler.stop_trace()
    return load_trace_events(logdir)


def profile_program(build_result, *, rounds: int = 3, inner: int = 3,
                    profile_dispatches: int = 3,
                    logdir: Optional[str] = None,
                    chip: "str | ChipSpec" = DEFAULT_CHIP,
                    name: str = "program") -> dict:
    """End-to-end convenience over ONE BuildResult: warm, measure
    dispatch wall time, trace under the profiler, parse + join, and
    return the runtime report. Runs the builder's cleanup in a finally
    (the registry consumer contract). The CLI uses the pieces directly
    so it can interleave rounds across programs; tests and ad-hoc
    callers use this."""
    import tempfile
    r = build_result
    try:
        hlo = r.fn.lower(*r.args).compile().as_text()
        args = host_example_args(r.args)
        import jax
        jax.block_until_ready(r.fn(*args))            # warm
        dispatch_s = measure_dispatch(r.fn, args, rounds, inner)
        d = logdir or tempfile.mkdtemp(prefix="tpuprof_")
        events = trace_dispatches(r.fn, args, profile_dispatches, d)
    finally:
        if r.cleanup is not None:
            r.cleanup()
    return runtime_report(name, hlo_text=hlo, events=events,
                          dispatch_s=dispatch_s,
                          dispatches_profiled=profile_dispatches,
                          chip=chip, geometry=r.geometry,
                          op_scopes=hlo_op_scopes(hlo))


# ---------------------------------------------------------------------------
# baseline gate (tools/tpuprof_baseline.json)
# ---------------------------------------------------------------------------
#
# Baseline shape:
#   {"version": 1, "chip": "v5lite", "tolerance": 2.5,
#    "budgets": {"<program>": {"dispatch_ms": 12.3}},
#    "anchors": {"<program>": {"kind": "matmul_time_share_floor",
#                              "min_share": 0.5}
#                          | {"kind": "measured_vs_roofline",
#                             "max_ratio": 40.0}},
#    "notes": {...}}
#
# Budgets re-pin wholesale on --update-baseline (medians of this run;
# partial runs merge); the tolerance band absorbs host jitter. Anchors
# are hand-set invariants that survive updates and need a device plane
# to evaluate — where there is none they are SKIPPED loudly (the
# record's anchors_skipped), never silently passed.


def load_profile_baseline(path: str) -> dict:
    with open(path) as fh:
        base = json.load(fh)
    if not isinstance(base, dict) or "budgets" not in base:
        raise ValueError(f"malformed tpuprof baseline {path!r}: needs "
                         "a 'budgets' dict (see analysis/"
                         "runtime_profile.py)")
    return base


def updated_profile_baseline(base: Optional[dict],
                             reports: Dict[str, dict]) -> dict:
    """Re-pin per-program dispatch medians from this run; anchors,
    notes and the tolerance survive (loosening an anchor or the band
    is a hand edit — the review point)."""
    base = dict(base or {})
    budgets = {}
    for name, rep in sorted(reports.items()):
        med = rep.get("dispatch", {}).get("median_ms")
        if med is None:
            continue
        budgets[name] = {"dispatch_ms": round(float(med), 3)}
    base["budgets"] = budgets
    base.setdefault("anchors", {})
    base.setdefault("notes", {})
    base.setdefault("tolerance", DEFAULT_TOLERANCE)
    base["version"] = 1
    base.setdefault("chip", DEFAULT_CHIP)
    return base


def check_profile_baseline(reports: Dict[str, dict],
                           baseline: Optional[dict],
                           live_programs: Sequence[str],
                           require_all: bool = False
                           ) -> Tuple[List[Finding], List[dict]]:
    """Gate the measured reports. Returns ``(findings, skipped)`` —
    findings empty == gate passes; ``skipped`` lists anchors that
    could NOT be evaluated (no device plane / no join) with reasons,
    which the CLI surfaces in its record so a CPU run never reads as
    its TPU anchors holding."""
    findings: List[Finding] = []
    skipped: List[dict] = []
    baseline = baseline or {"budgets": {}}
    budgets = baseline.get("budgets", {})
    anchors = baseline.get("anchors", {})
    tol = float(baseline.get("tolerance", DEFAULT_TOLERANCE))
    live = set(live_programs)

    if require_all:
        for prog in sorted((set(budgets) | set(anchors)) & live
                           - set(reports)):
            findings.append(Finding(
                PROF_BUDGET, Severity.ERROR, prog, "not-measured",
                f"live program {prog!r} is baselined but produced no "
                "measured report this run — its budgets/anchors were "
                "NOT checked (skipped build? device count?); a full "
                "run must measure every baselined site", {}))

    for section, table in (("budgets", budgets), ("anchors", anchors)):
        for prog in sorted(table):
            if prog not in live:
                findings.append(Finding(
                    STALE_PROF_PROGRAM, Severity.ERROR, prog, section,
                    f"baseline {section} entry names {prog!r} but the "
                    "ProgramRegistry has no such program — renamed or "
                    "deleted without re-pinning "
                    "(tools/tpuprof.py --update-baseline; anchors "
                    "move by hand)", {}))

    for name, rep in sorted(reports.items()):
        med = rep.get("dispatch", {}).get("median_ms")
        b = budgets.get(name)
        if b is None:
            findings.append(Finding(
                PROF_BUDGET, Severity.WARN, name, "unbaselined",
                f"program {name!r} has no tpuprof dispatch budget — a "
                "newly registered program must be pinned (review its "
                "report, then --update-baseline)",
                {"dispatch_ms": med}))
            continue
        if med is None:
            continue
        budget = float(b.get("dispatch_ms", 0.0))
        if budget and med > budget * tol:
            findings.append(Finding(
                PROF_BUDGET, Severity.WARN, name, "dispatch_ms",
                f"measured dispatch median {med:.3f} ms exceeds the "
                f"pinned {budget:.3f} ms x tolerance {tol} — the "
                "program got structurally slower (new compile per "
                "call? dropped fusion? extra sync), or the host is "
                "drowning; re-run, then fix or --update-baseline",
                {"measured_ms": med, "budget_ms": budget,
                 "tolerance": tol}))

    for name, a in sorted(anchors.items()):
        rep = reports.get(name)
        if rep is None:
            continue    # partial runs; full runs flagged above
        kind = a.get("kind", "")
        if kind == "matmul_time_share_floor":
            share = rep.get("matmul_time_share")
            if share is None:
                skipped.append({
                    "program": name, "kind": kind,
                    "reason": rep.get("join", {}).get(
                        "reason", "no measured<->modeled join")})
                continue
            floor = float(a.get("min_share", 0.0))
            if share < floor:
                findings.append(Finding(
                    PROF_ANCHOR, Severity.ERROR, name, kind,
                    f"measured matmul time share {share:.4f} broke "
                    f"the hand-set floor {floor:.4f} — non-matmul "
                    "kernels now own the step's device time",
                    {"measured": share, "floor": floor}))
        elif kind == "measured_vs_roofline":
            ratio = rep.get("measured_vs_roofline")
            if ratio is None:
                skipped.append({
                    "program": name, "kind": kind,
                    "reason": rep.get("join", {}).get(
                        "reason", "no measured<->modeled join")})
                continue
            max_ratio = float(a.get("max_ratio", 10.0))
            if ratio > max_ratio:
                findings.append(Finding(
                    PROF_ANCHOR, Severity.ERROR, name, kind,
                    f"measured device time is {ratio:.2f}x the "
                    f"modeled roofline (max {max_ratio}x) — the "
                    "program drifted further from what the chip "
                    "could do (launch overhead? serialization? an "
                    "unmodeled pass)",
                    {"measured_ratio": ratio, "max_ratio": max_ratio}))
        else:
            findings.append(Finding(
                PROF_ANCHOR, Severity.ERROR, name, "unknown-kind",
                f"anchor for {name!r} has unknown kind {kind!r} "
                "(valid: matmul_time_share_floor, "
                "measured_vs_roofline) — the invariant was NOT "
                "evaluated; fix the baseline", {"kind": kind}))
    return findings, skipped
