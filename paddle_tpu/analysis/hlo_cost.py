"""tpucost — static fusion & HBM-traffic cost model over compiled HLO.

The MFU campaign (ROADMAP item 3) needs its measurement half before any
fusion work can land: "Operator Fusion in XLA" (PAPERS.md 2301.13062)
shows XLA's fusion decisions are analyzable — and frequently suboptimal
— from the HLO text alone, and MPK (PAPERS.md 2512.22219) motivates
knowing exactly which per-layer HBM round-trips dominate the decode
tick. This module turns the compiled HLO of any registered program into
a per-kernel inventory WITHOUT executing anything:

- every top-level instruction of the entry computation (recursing into
  while bodies with their statically-recovered trip counts, call
  targets, and the costlier conditional branch) is one KERNEL — one
  launch, one HBM round-trip boundary;
- a kernel's HBM bytes are its operand reads + result writes; values
  produced INSIDE a fusion never touch HBM (the cache-awareness that
  makes fusion worth measuring), so a fused producer is free and an
  unfused one pays write + re-read;
- FLOPs per kernel: dots count 2 * prod(result dims) * contraction
  size (batch dims included via the result), elementwise arithmetic
  counts one per output element, reductions count their input elements;
  data movement (copy/transpose/broadcast/slice/gather/...) is zero
  FLOPs but full traffic — exactly the ops a roofline says are free to
  fuse and expensive to leave standalone;
- roofline-predicted time per kernel under a configurable
  :class:`ChipSpec` = max(flops/peak, bytes/bw); the program total is
  the sum over kernels x trip counts.

The chip-spec table here is the ONE place accelerator constants live:
`tools/tpucost.py` defaults to v5-lite, the chip the benchmark's cells
run on.

`check_cost_baseline` is the gate: per-program ratcheted budgets (total
HBM bytes, kernel count, matmul-FLOP share floor) plus must-stay-true
anchors (the engine decode tick's modeled HBM bytes within 1.15x of the
analytic KV-cache + weight bound; train_step's matmul share never
drops), emitted as `analysis.findings.Finding`s so tpulint's
baseline/report idioms carry over unchanged.

Parsing is line-based over the text `Compiled.as_text()` returns —
checked-in fixtures under tests/fixtures/hlo/ exercise it with zero
compiles.
"""
from __future__ import annotations

import math
import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

from .findings import (COST_ANCHOR, COST_BUDGET, STALE_COST_PROGRAM,
                       Finding, Severity)

__all__ = [
    "ChipSpec", "CHIP_SPECS", "DEFAULT_CHIP", "HLO_DTYPE_BYTES",
    "parse_hlo_module", "program_cost", "collect_kernels", "KernelCost",
    "analytic_decode_hbm_bytes", "analytic_paged_decode_hbm_bytes",
    "analytic_verify_hbm_bytes",
    "check_cost_baseline",
    "load_cost_baseline", "updated_cost_baseline",
]

# ---------------------------------------------------------------------------
# chip specs — the one table lives in chips.py (dependency-free, so a
# tool can load it without the package import); re-exported here as
# the tpucost-facing surface
# ---------------------------------------------------------------------------

from .chips import CHIP_SPECS, DEFAULT_CHIP, ChipSpec  # noqa: E402

# HLO dtype -> bytes (shared: program_lint's collective inventory uses
# this same table)
HLO_DTYPE_BYTES = {
    "pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1,
    "f8e4m3fn": 1, "f8e5m2": 1, "f8e4m3b11fnuz": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8, "c128": 16,
    "token": 0, "opaque": 0,
}

# ---------------------------------------------------------------------------
# HLO text parsing
# ---------------------------------------------------------------------------

_SHAPE_RE = re.compile(r"(?P<dt>[a-z][a-z0-9]*)\[(?P<dims>[0-9,]*)\]")
_COMP_RE = re.compile(
    r"^(?P<entry>ENTRY\s+)?%?(?P<name>[\w.\-]+)\s*\(.*\)\s*->.*\{")
_INSTR_RE = re.compile(r"^\s*(?P<root>ROOT\s+)?%(?P<name>[\w.\-]+)\s*=\s*")
_TYPE_RE = re.compile(r"[a-z][a-z0-9]*\[[0-9,]*\](?:\{[^}]*\})?")
_OPCODE_RE = re.compile(r"\s*(?P<op>[\w\-]+)\(")
_OPND_RE = re.compile(r"%(?P<name>[\w.\-]+)")

_ATTR_RES = {
    "kind": re.compile(r"\bkind=(\w+)"),
    "calls": re.compile(r"\bcalls=%?([\w.\-]+)"),
    "condition": re.compile(r"\bcondition=%?([\w.\-]+)"),
    "body": re.compile(r"\bbody=%?([\w.\-]+)"),
    "to_apply": re.compile(r"\bto_apply=%?([\w.\-]+)"),
    "lhs_contracting_dims": re.compile(
        r"\blhs_contracting_dims=\{([0-9,]*)\}"),
    "direction": re.compile(r"\bdirection=(\w+)"),
    "custom_call_target": re.compile(r'\bcustom_call_target="([^"]+)"'),
    "branch_computations": re.compile(r"\bbranch_computations=\{([^}]*)\}"),
    "true_computation": re.compile(r"\btrue_computation=%?([\w.\-]+)"),
    "false_computation": re.compile(r"\bfalse_computation=%?([\w.\-]+)"),
    "op_name": re.compile(r'\bop_name="([^"]*)"'),
}


@dataclass
class Instr:
    name: str
    opcode: str
    shapes: List[Tuple[str, Tuple[int, ...]]]   # result shapes, flattened
    operands: List[str]                         # operand instruction names
    attrs: Dict[str, str]
    root: bool = False
    literal: str = ""                           # constant literal text


@dataclass
class Computation:
    name: str
    entry: bool
    instrs: List[Instr] = field(default_factory=list)
    by_name: Dict[str, Instr] = field(default_factory=dict)

    @property
    def root(self) -> Optional[Instr]:
        for i in self.instrs:
            if i.root:
                return i
        return self.instrs[-1] if self.instrs else None


@dataclass
class HloModule:
    computations: Dict[str, Computation]
    entry: str


def _shapes_of(text: str) -> List[Tuple[str, Tuple[int, ...]]]:
    out = []
    for m in _SHAPE_RE.finditer(text):
        dims = tuple(int(d) for d in m.group("dims").split(",") if d)
        out.append((m.group("dt"), dims))
    return out


def shape_bytes(shapes: Sequence[Tuple[str, Tuple[int, ...]]]) -> int:
    total = 0
    for dt, dims in shapes:
        n = 1
        for d in dims:
            n *= d
        total += n * HLO_DTYPE_BYTES.get(dt, 4)
    return total


def shape_elems(shapes: Sequence[Tuple[str, Tuple[int, ...]]]) -> int:
    total = 0
    for _, dims in shapes:
        n = 1
        for d in dims:
            n *= d
        total += n
    return total


def _balanced(s: str, start: int) -> int:
    """Index just past the ')' matching the '(' at `start`."""
    depth = 0
    for i in range(start, len(s)):
        if s[i] == "(":
            depth += 1
        elif s[i] == ")":
            depth -= 1
            if depth == 0:
                return i + 1
    return len(s)


def _parse_instr(line: str) -> Optional[Instr]:
    m = _INSTR_RE.match(line)
    if m is None:
        return None
    rest = line[m.end():].lstrip()
    if rest.startswith("("):            # tuple-typed result
        end = _balanced(rest, 0)
        type_str, rest = rest[:end], rest[end:].lstrip()
    else:
        tm = _TYPE_RE.match(rest)
        if tm is None:
            return None
        type_str, rest = tm.group(0), rest[tm.end():].lstrip()
    om = _OPCODE_RE.match(rest)
    if om is None:
        return None
    opcode = om.group("op")
    open_paren = om.end() - 1
    close = _balanced(rest, open_paren)
    inner = rest[open_paren + 1:close - 1]
    tail = rest[close:]
    attrs = {}
    for key, rx in _ATTR_RES.items():
        am = rx.search(tail)
        if am:
            attrs[key] = am.group(1)
    return Instr(
        name=m.group("name"), opcode=opcode, shapes=_shapes_of(type_str),
        operands=[o.group("name") for o in _OPND_RE.finditer(inner)],
        attrs=attrs, root=bool(m.group("root")),
        literal=inner if opcode == "constant" else "")


def parse_hlo_module(text: str) -> HloModule:
    """Line-based parse of `Compiled.as_text()` output into computations
    of instructions. Tolerant: unrecognized lines are skipped, so a new
    XLA attribute can never crash the pass (it only degrades detail)."""
    comps: Dict[str, Computation] = {}
    entry = ""
    cur: Optional[Computation] = None
    # an instruction's text may run over several lines (the splash
    # kernel's ``kernel_metadata`` attribute holds its JSON on a line of
    # its own, and ``metadata={op_name=...}`` follows it): a line that
    # opens no instruction continues the one before
    body: List[str] = []
    for line in text.splitlines():
        if cur is None:
            cm = _COMP_RE.match(line)
            if cm:
                cur = Computation(cm.group("name"),
                                  bool(cm.group("entry")))
            continue
        if line.strip() == "}":
            for ins in filter(None, map(_parse_instr, body)):
                cur.instrs.append(ins)
                cur.by_name[ins.name] = ins
            body = []
            comps[cur.name] = cur
            if cur.entry:
                entry = cur.name
            cur = None
        elif body and not _INSTR_RE.match(line):
            body[-1] += line
        else:
            body.append(line)
    if not entry and comps:       # single-computation fixture w/o ENTRY
        entry = next(iter(comps))
    return HloModule(comps, entry)


# ---------------------------------------------------------------------------
# per-op FLOP model
# ---------------------------------------------------------------------------

# one FLOP per output element
_ELEMWISE = {
    "add", "subtract", "multiply", "divide", "power", "maximum",
    "minimum", "abs", "negate", "exponential", "exponential-minus-one",
    "log", "log-plus-one", "tanh", "logistic", "sqrt", "rsqrt", "cbrt",
    "sine", "cosine", "tan", "atan2", "remainder", "sign", "compare",
    "select", "clamp", "and", "or", "xor", "not", "shift-left",
    "shift-right-arithmetic", "shift-right-logical", "is-finite",
    "floor", "ceil", "round-nearest-afz", "round-nearest-even",
    "popcnt", "count-leading-zeros", "erf", "map", "select-and-scatter",
}

# zero FLOPs, full HBM traffic when standalone
_DATA_MOVEMENT = {
    "copy", "copy-start", "transpose", "reshape", "broadcast", "slice",
    "dynamic-slice", "dynamic-update-slice", "concatenate", "pad",
    "reverse", "gather", "iota", "convert", "bitcast-convert", "real",
    "imag", "complex", "rng", "rng-bit-generator", "sort",
}

_COLLECTIVES = {"all-reduce", "all-gather", "reduce-scatter",
                "collective-permute", "all-to-all",
                "all-reduce-start", "all-gather-start",
                "collective-permute-start"}

# free glue: no kernel, no HBM boundary of its own
_SKIP = {
    "parameter", "constant", "get-tuple-element", "tuple", "bitcast",
    "after-all", "partition-id", "replica-id", "get-dimension-size",
    "add-dependency", "domain", "opt-barrier", "copy-done",
    "all-reduce-done", "all-gather-done", "collective-permute-done",
    "rng-get-and-update-state", "send", "send-done", "recv",
    "recv-done",
}

# kernels smaller than this (operands + results) are scalar glue —
# loop counters, predicates — excluded from the fusion histogram and
# the kernel-count budget so the ratchet tracks real HBM traffic
SCALAR_GLUE_BYTES = 4096


@dataclass
class KernelCost:
    """One launched kernel (top-level instruction or fusion), already
    multiplied by its loop trip count."""
    name: str
    opcode: str
    klass: str                 # histogram class (see fusion.py)
    flops: float
    matmul_flops: float
    bytes_read: int
    bytes_written: int
    trip: int
    path: str                  # loop/call nesting, e.g. "while.2"
    op_name: str = ""          # jax-level metadata label
    operands: Tuple[str, ...] = ()

    @property
    def hbm_bytes(self) -> int:
        return self.bytes_read + self.bytes_written

    @property
    def intensity(self) -> float:
        return self.flops / self.hbm_bytes if self.hbm_bytes else 0.0

    def roofline_seconds(self, chip: ChipSpec) -> float:
        return max(self.flops / chip.peak_flops,
                   self.hbm_bytes / chip.hbm_bandwidth)

    def to_dict(self, chip: ChipSpec) -> dict:
        return {
            "name": self.name, "op": self.opcode, "class": self.klass,
            "flops": self.flops, "matmul_flops": self.matmul_flops,
            "bytes_read": self.bytes_read,
            "bytes_written": self.bytes_written, "trip": self.trip,
            "path": self.path, "op_name": self.op_name,
            "arithmetic_intensity": round(self.intensity, 3),
            "roofline_us": round(self.roofline_seconds(chip) * 1e6, 3),
        }


def _operand_shapes(ins: Instr, comp: Computation):
    seen = set()
    for name in ins.operands:
        if name in seen:        # a kernel streams each operand once
            continue
        seen.add(name)
        src = comp.by_name.get(name)
        if src is not None:
            yield src


def _dot_flops(ins: Instr, comp: Computation) -> float:
    out_elems = shape_elems(ins.shapes)
    lhs = comp.by_name.get(ins.operands[0]) if ins.operands else None
    k = 1
    if lhs is not None and lhs.shapes:
        dims = lhs.shapes[0][1]
        cdims = [int(d) for d in
                 ins.attrs.get("lhs_contracting_dims", "").split(",")
                 if d]
        for d in cdims:
            if d < len(dims):
                k *= dims[d]
    return 2.0 * out_elems * k


def _plain_op_flops(ins: Instr, comp: Computation) -> Tuple[float, float]:
    """(flops, matmul_flops) for one non-fusion instruction."""
    op = ins.opcode
    if op == "dot":
        f = _dot_flops(ins, comp)
        return f, f
    if op in _ELEMWISE:
        return float(shape_elems(ins.shapes)), 0.0
    if op in ("reduce", "reduce-window"):
        src = comp.by_name.get(ins.operands[0]) if ins.operands else None
        elems = shape_elems(src.shapes) if src is not None \
            else shape_elems(ins.shapes)
        return float(elems), 0.0
    if op == "scatter" and len(ins.operands) >= 3:
        upd = comp.by_name.get(ins.operands[2])
        if upd is not None:
            return float(shape_elems(upd.shapes)), 0.0
    if op in ("all-reduce", "all-reduce-start"):
        return float(shape_elems(ins.shapes)), 0.0
    return 0.0, 0.0            # data movement / unknown: traffic only


def _fusion_flops(ins: Instr, module: HloModule,
                  notes: List[str]) -> Tuple[float, float]:
    called = module.computations.get(ins.attrs.get("calls", ""))
    if called is None:
        notes.append(f"fusion {ins.name}: called computation not found")
        return 0.0, 0.0
    flops = matmul = 0.0
    for sub in called.instrs:
        if sub.opcode == "fusion":      # nested fusion (rare)
            f, m = _fusion_flops(sub, module, notes)
        else:
            f, m = _plain_op_flops(sub, called)
        flops += f
        matmul += m
    return flops, matmul


# ---------------------------------------------------------------------------
# trip counts & kernel collection
# ---------------------------------------------------------------------------

def _trip_count(module: HloModule, cond_name: str) -> Optional[int]:
    """Recover a while loop's static trip count from its condition
    computation: jax's scan/fori lower to `iter < K` (or <=) against a
    constant, starting at 0 — the shape every registered program's
    loops have. None when the pattern doesn't match."""
    comp = module.computations.get(cond_name)
    if comp is None:
        return None
    root = comp.root
    if root is None or root.opcode != "compare":
        return None
    const = None
    for opn in root.operands:
        src = comp.by_name.get(opn)
        if src is not None and src.opcode == "constant":
            try:
                const = int(src.literal.strip())
            except ValueError:
                return None
    if const is None:
        return None
    direction = root.attrs.get("direction", "LT")
    if direction == "LT":
        return max(const, 1)
    if direction == "LE":
        return max(const + 1, 1)
    return None


def _kernel_class(ins: Instr, bytes_total: int) -> str:
    if ins.opcode == "fusion":
        return {"kLoop": "loop", "kInput": "input", "kOutput": "output",
                "kCustom": "custom"}.get(ins.attrs.get("kind", ""),
                                         "loop")
    if ins.opcode == "dot":
        return "dot"
    # convolution FLOPs are not modeled (no conv on any registered hot
    # path) — class it by traffic, never as a 0-FLOP "dot" that would
    # hollow out the matmul-share ratchet; collect_kernels notes it
    if ins.opcode in _COLLECTIVES:
        return "collective"
    if ins.opcode == "custom-call":
        return "custom-call"
    if bytes_total < SCALAR_GLUE_BYTES:
        return "scalar"
    return "unfused"


def collect_kernels(module: HloModule, comp_name: Optional[str] = None,
                    trip: int = 1, path: str = "",
                    notes: Optional[List[str]] = None) -> List[KernelCost]:
    """Walk a computation (default: entry) and return every kernel,
    recursing through while bodies (x trip count), call targets, and
    the costlier conditional branch."""
    if notes is None:
        notes = []
    comp = module.computations.get(comp_name or module.entry)
    if comp is None:
        return []
    out: List[KernelCost] = []
    for ins in comp.instrs:
        op = ins.opcode
        if op in _SKIP:
            continue
        if op == "while":
            body = ins.attrs.get("body", "")
            t = _trip_count(module, ins.attrs.get("condition", ""))
            if t is None:
                notes.append(
                    f"while {ins.name}: trip count not statically "
                    "recoverable — body counted once")
                t = 1
            out.extend(collect_kernels(
                module, body, trip * t,
                f"{path}/{ins.name}" if path else ins.name, notes))
            continue
        if op == "call":
            out.extend(collect_kernels(
                module, ins.attrs.get("to_apply", ""), trip,
                f"{path}/{ins.name}" if path else ins.name, notes))
            continue
        if op == "conditional":
            branches = []
            if "branch_computations" in ins.attrs:
                branches = re.findall(r"[\w.\-]+",
                                      ins.attrs["branch_computations"])
            else:
                branches = [ins.attrs.get(k) for k in
                            ("true_computation", "false_computation")
                            if ins.attrs.get(k)]
            best: List[KernelCost] = []
            for b in branches:
                cand = collect_kernels(
                    module, b, trip,
                    f"{path}/{ins.name}" if path else ins.name, notes)
                if sum(k.hbm_bytes for k in cand) >= \
                        sum(k.hbm_bytes for k in best):
                    best = cand
            out.extend(best)
            continue
        if op == "convolution":
            notes.append(f"convolution {ins.name}: FLOPs not modeled "
                         "(traffic counted; matmul share excludes it)")
        reads = sum(shape_bytes(src.shapes)
                    for src in _operand_shapes(ins, comp))
        writes = shape_bytes(ins.shapes)
        if op == "fusion":
            flops, matmul = _fusion_flops(ins, module, notes)
        else:
            flops, matmul = _plain_op_flops(ins, comp)
        out.append(KernelCost(
            name=ins.name, opcode=op,
            klass=_kernel_class(ins, reads + writes),
            flops=flops * trip, matmul_flops=matmul * trip,
            bytes_read=reads * trip, bytes_written=writes * trip,
            trip=trip, path=path, op_name=ins.attrs.get("op_name", ""),
            operands=tuple(ins.operands)))
    return out


# ---------------------------------------------------------------------------
# program inventory
# ---------------------------------------------------------------------------

def program_cost(hlo_text: str, *, name: str = "program",
                 chip: "str | ChipSpec" = DEFAULT_CHIP,
                 detail: bool = False, top_chains: int = 5) -> dict:
    """The per-program inventory record: FLOPs, HBM bytes, arithmetic
    intensity, roofline time under `chip`, fusion-kind histogram, and
    the ranked top unfused elementwise chains. `detail=True` adds the
    full per-kernel list (big; the CLI's --json report includes it)."""
    from .fusion import fusion_histogram, unfused_chains
    # lazy: program_lint imports HLO_DTYPE_BYTES from this module
    from .program_lint import collective_inventory_from_hlo
    if isinstance(chip, str):
        chip = CHIP_SPECS[chip]
    notes: List[str] = []
    module = parse_hlo_module(hlo_text)
    kernels = collect_kernels(module, notes=notes)
    coll = collective_inventory_from_hlo(hlo_text)
    flops = sum(k.flops for k in kernels)
    matmul = sum(k.matmul_flops for k in kernels)
    reads = sum(k.bytes_read for k in kernels)
    writes = sum(k.bytes_written for k in kernels)
    hbm = reads + writes
    roofline = sum(k.roofline_seconds(chip) for k in kernels)
    chains = unfused_chains(kernels, limit=top_chains)
    rec = {
        "program": name,
        "chip": chip.name,
        "flops": flops,
        "matmul_flops": matmul,
        "matmul_flop_share": round(matmul / flops, 6) if flops else 0.0,
        "bytes_read": reads,
        "bytes_written": writes,
        "hbm_bytes": hbm,
        "arithmetic_intensity": round(flops / hbm, 3) if hbm else 0.0,
        "roofline_seconds": roofline,
        "flop_time_seconds": flops / chip.peak_flops,
        "hbm_time_seconds": hbm / chip.hbm_bandwidth,
        "bound": ("compute" if flops / chip.peak_flops
                  >= hbm / chip.hbm_bandwidth else "bandwidth"),
        "kernel_count": sum(1 for k in kernels if k.klass != "scalar"),
        # per-chip transferred collective bytes (ring accounting,
        # program_lint.collective_inventory_from_hlo) — the quantity
        # the comm_bytes anchor and the collective_bytes budget ratchet
        # gate (ISSUE 17: wire-precision wins must not silently revert)
        "collectives": coll,
        "collective_bytes": sum(v["bytes"] for v in coll.values()),
        "fusion_histogram": fusion_histogram(kernels),
        "top_unfused": chains,
        "notes": notes,
    }
    if detail:
        rec["kernels"] = [k.to_dict(chip) for k in kernels]
    return rec


# ---------------------------------------------------------------------------
# analytic anchors
# ---------------------------------------------------------------------------

def analytic_decode_hbm_bytes(geometry: dict) -> int:
    """Analytic HBM bytes for one engine decode TICK under the CURRENT
    one-hot masked-write regime (the MPK per-layer round-trip
    accounting): each of the `tick_tokens` micro-steps streams every
    weight once (param_bytes) and makes SEVEN full passes over the KV
    cache — the layout/transpose fusion (read + write), the masked
    select itself (read + write), the loop-carry copy XLA materializes
    for the donated cache (read + write), and the attention read:

        tick_tokens * (param_bytes + 7 * kv_cache_bytes)

    The IDEAL regime is 3 passes (attention read + in-place
    read-modify-write) — the 7-pass accounting is what the compiled
    HLO actually does today (PERF.md PR 6 records the inventory), and
    the mega-kernelization campaign's job is to delete the other four.
    The decode_hbm anchor pins modeled/analytic <= 1.15x so an EIGHTH
    pass (an unfused activation chain, a dropped fusion) fails CI; a
    genuine fusion win shrinks modeled bytes and the ratcheted
    hbm_bytes budget is what locks it in."""
    return int(geometry["tick_tokens"]
               * (geometry["param_bytes"]
                  + 7 * geometry["kv_cache_bytes"]))


def analytic_paged_decode_hbm_bytes(geometry: dict) -> int:
    """Analytic HBM bytes for one PAGED engine decode tick (ISSUE 9).

    The paged tick swaps the dense slot rows for page pools plus a
    per-micro-step GATHER into the [N, pages_per_slot * page] view
    attention consumes, so the accounting splits in two:

    - ``kv_cache_bytes`` (the POOL — what HBM actually stores) makes
      FOUR passes: the one-hot page write's read + write and the
      donated-carry copy's read + write. Pool bytes scale with LIVE
      tokens admitted, not slots * max_len — at a pool sized below
      slots * pages_per_slot this is where paging cuts tick traffic.
    - ``kv_view_bytes`` (the gathered view, all layers, k + v) makes
      THREE passes: the gather's write, the attention read, and the
      gather's read side modeled at view size (the parser charges a
      gather's operand at result scale).

        tick_tokens * (param_bytes + 4*pool_bytes + 3*view_bytes)

    The IDEAL regime fuses the gather into attention (1 view pass) and
    writes pages in place (1 pool pass) — the same mega-kernelization
    target the dense anchor documents. The anchor pins modeled <=
    max_ratio of this bound so an extra full-view or full-pool pass
    (a dropped fusion in the gather/write chain) fails CI."""
    return int(geometry["tick_tokens"]
               * (geometry["param_bytes"]
                  + 4 * geometry["kv_cache_bytes"]
                  + 3 * geometry["kv_view_bytes"]))


def analytic_verify_hbm_bytes(geometry: dict) -> int:
    """Analytic HBM bytes for one speculative VERIFY-K dispatch
    (ISSUE 13) — the k-token bound that makes the multi-token tick a
    bandwidth win. The verify program is ONE target forward over the
    [tok, d1..dk] block for every slot: weights stream ONCE and the KV
    cache makes the 7 passes the dense decode micro-step pays (masked
    block write read+write, layout fusion read+write, donated-carry
    copy read+write, attention read) ONCE —

        param_bytes + 7 * kv_cache_bytes

    versus the plain tick's ``tick_tokens * (param_bytes + 7 *
    kv_cache_bytes)``: per EMITTED token the verify dispatch moves up
    to (k+1)x fewer bytes (acceptance decides how much of the bound is
    realized). The measured program sits ~1.27x above this bound: the
    per-row BLOCK write (take_along_axis of the k+1 incoming rows per
    cache position + dense select) materializes its gathered values at
    cache scale — roughly two extra cache passes the S=1 one-hot write
    doesn't pay; the anchor's max_ratio carries that headroom, so one
    MORE full cache pass or weight stream (re-per-tokenizing the
    block) still fails CI."""
    return int(geometry["param_bytes"] + 7 * geometry["kv_cache_bytes"])


# ---------------------------------------------------------------------------
# baseline gate (tools/tpucost_baseline.json)
# ---------------------------------------------------------------------------
#
# Baseline shape:
#   {"version": 1, "chip": "v5lite",
#    "budgets": {"<program>": {"hbm_bytes": N, "kernel_count": N,
#                              "matmul_flop_share_min": 0.x,
#                              "collective_bytes": N}},
#    "anchors": {"<program>": {"kind": "decode_hbm"|"matmul_share_floor"
#                                      |"comm_bytes"|"fusion_hbm",
#                              "max_ratio": 1.15 | "min_share": 0.x |
#                              "baseline_program": "...",
#                              "min_ratio": 3.5 |
#                              "max_kernel_delta": -3}},
#    "notes": {...}}
#
# Budgets RATCHET (hbm_bytes/kernel_count/collective_bytes may only
# stay or shrink, matmul share may only stay or grow) and are rewritten
# wholesale by --update-baseline; anchors are hand-set invariants that
# survive updates — the must_stay_clean idiom, numeric.


def load_cost_baseline(path: str) -> dict:
    import json
    with open(path) as fh:
        base = json.load(fh)
    if not isinstance(base, dict) or "budgets" not in base:
        raise ValueError(f"malformed tpucost baseline {path!r}: needs a "
                         "'budgets' dict (see analysis/hlo_cost.py)")
    return base


def updated_cost_baseline(base: Optional[dict],
                          inventories: Dict[str, dict]) -> dict:
    """Re-pin budgets from this run's measurements; anchors and notes
    survive (accepting a regression in an ANCHORED quantity requires
    editing the anchor by hand — that is the review point)."""
    base = dict(base or {})
    budgets = {}
    for name, inv in sorted(inventories.items()):
        budgets[name] = {
            "hbm_bytes": int(inv["hbm_bytes"]),
            "kernel_count": int(inv["kernel_count"]),
            "matmul_flop_share_min": math.floor(
                inv["matmul_flop_share"] * 1e4) / 1e4,
        }
        # pin what the run measured: inventories always carry
        # collective_bytes (0 for single-chip programs), but a summary
        # from an older report without the field must not grow a gate
        if "collective_bytes" in inv:
            budgets[name]["collective_bytes"] = int(
                inv["collective_bytes"])
    base["budgets"] = budgets
    base.setdefault("anchors", {})
    base.setdefault("notes", {})
    base["version"] = 1
    base.setdefault("chip", DEFAULT_CHIP)
    return base


def check_cost_baseline(inventories: Dict[str, dict],
                        baseline: Optional[dict],
                        live_programs: Sequence[str],
                        geometries: Optional[Dict[str, dict]] = None,
                        require_all: bool = False) -> List[Finding]:
    """Gate the measured inventories. Returns violation findings (empty
    == gate passes): cost-budget for ratchet breaks and unbaselined
    programs, cost-anchor for broken invariants, stale-cost-program for
    baseline entries naming a program the registry no longer has (the
    registry-rename rot check, analogous to stale-quarantine).

    `require_all=True` (a FULL run, not a --programs subset): a live
    baselined program MISSING from the inventories is itself a
    violation — a site silently skipped (device count, builder error
    swallowed upstream) must not read as its anchors passing."""
    findings: List[Finding] = []
    baseline = baseline or {"budgets": {}}
    budgets = baseline.get("budgets", {})
    anchors = baseline.get("anchors", {})
    geometries = geometries or {}
    live = set(live_programs)

    if require_all:
        for prog in sorted((set(budgets) | set(anchors)) & live
                           - set(inventories)):
            findings.append(Finding(
                COST_BUDGET, Severity.ERROR, prog, "not-measured",
                f"live program {prog!r} is baselined but produced no "
                "inventory this run — its budgets/anchors were NOT "
                "checked (skipped build? device count?); a full run "
                "must measure every registered site", {}))

    for section, table in (("budgets", budgets), ("anchors", anchors)):
        for prog in sorted(table):
            if prog not in live:
                findings.append(Finding(
                    STALE_COST_PROGRAM, Severity.ERROR, prog, section,
                    f"baseline {section} entry names {prog!r} but the "
                    "ProgramRegistry has no such program — renamed or "
                    "deleted without re-pinning "
                    "(tools/tpucost.py --update-baseline; anchors move "
                    "by hand)", {}))

    for name, inv in sorted(inventories.items()):
        b = budgets.get(name)
        if b is None:
            findings.append(Finding(
                COST_BUDGET, Severity.WARN, name, "unbaselined",
                f"program {name!r} has no tpucost budget — a newly "
                "registered program must be pinned (review its "
                "inventory, then --update-baseline)",
                {"hbm_bytes": inv["hbm_bytes"]}))
            continue
        hbm_budget = int(b.get("hbm_bytes", 0))
        if inv["hbm_bytes"] > hbm_budget:
            findings.append(Finding(
                COST_BUDGET, Severity.WARN, name, "hbm_bytes",
                f"modeled HBM traffic {inv['hbm_bytes']} exceeds the "
                f"pinned budget {hbm_budget} — a fusion regressed "
                "or new traffic appeared (review, fix, or "
                "--update-baseline)",
                {"measured": inv["hbm_bytes"], "budget": hbm_budget}))
        kern_budget = int(b.get("kernel_count", 0))
        if inv["kernel_count"] > kern_budget:
            findings.append(Finding(
                COST_BUDGET, Severity.WARN, name, "kernel_count",
                f"{inv['kernel_count']} kernels exceed the pinned "
                f"{kern_budget} — XLA split a previously fused "
                "region (more launches, more HBM round-trips)",
                {"measured": inv["kernel_count"],
                 "budget": kern_budget}))
        coll_budget = b.get("collective_bytes")
        if coll_budget is not None \
                and inv.get("collective_bytes", 0) > int(coll_budget):
            findings.append(Finding(
                COST_BUDGET, Severity.WARN, name, "collective_bytes",
                f"per-chip collective bytes "
                f"{inv.get('collective_bytes', 0)} exceed the pinned "
                f"budget {int(coll_budget)} — a collective regressed "
                "to a wider wire dtype or new cross-chip traffic "
                "appeared (review, fix, or --update-baseline)",
                {"measured": inv.get("collective_bytes", 0),
                 "budget": int(coll_budget)}))
        share_min = float(b.get("matmul_flop_share_min", 0.0))
        if inv["matmul_flop_share"] < share_min:
            findings.append(Finding(
                COST_BUDGET, Severity.WARN, name, "matmul_flop_share",
                f"matmul FLOP share {inv['matmul_flop_share']:.4f} "
                f"dropped below the pinned floor {share_min:.4f} — "
                "non-matmul work grew relative to the MXU work that "
                "pays for it",
                {"measured": inv["matmul_flop_share"],
                 "floor": share_min}))

    for name, a in sorted(anchors.items()):
        inv = inventories.get(name)
        if inv is None:
            continue    # partial runs; full runs flagged above
        kind = a.get("kind", "")
        if kind == "decode_hbm":
            geom = geometries.get(name) or {}
            try:
                bound = analytic_decode_hbm_bytes(geom)
            except KeyError:
                findings.append(Finding(
                    COST_ANCHOR, Severity.ERROR, name, "decode_hbm",
                    "decode_hbm anchor needs geometry metadata "
                    "(param_bytes, kv_cache_bytes, tick_tokens) on the "
                    "registered site's BuildResult", {}))
                continue
            ratio = inv["hbm_bytes"] / bound if bound else float("inf")
            if ratio > float(a.get("max_ratio", 1.15)):
                findings.append(Finding(
                    COST_ANCHOR, Severity.ERROR, name, "decode_hbm",
                    f"decode tick models {inv['hbm_bytes']} HBM bytes "
                    f"= {ratio:.3f}x the analytic KV+weight bound "
                    f"{bound} (max {a.get('max_ratio', 1.15)}x) — "
                    "unfused activation traffic crept into the tick",
                    {"measured": inv["hbm_bytes"], "analytic": bound,
                     "ratio": round(ratio, 4)}))
        elif kind == "decode_hbm_paged":
            geom = geometries.get(name) or {}
            try:
                bound = analytic_paged_decode_hbm_bytes(geom)
            except KeyError:
                findings.append(Finding(
                    COST_ANCHOR, Severity.ERROR, name,
                    "decode_hbm_paged",
                    "decode_hbm_paged anchor needs geometry metadata "
                    "(param_bytes, kv_cache_bytes, kv_view_bytes, "
                    "tick_tokens) on the registered site's "
                    "BuildResult", {}))
                continue
            ratio = inv["hbm_bytes"] / bound if bound else float("inf")
            if ratio > float(a.get("max_ratio", 1.15)):
                findings.append(Finding(
                    COST_ANCHOR, Severity.ERROR, name,
                    "decode_hbm_paged",
                    f"paged decode tick models {inv['hbm_bytes']} HBM "
                    f"bytes = {ratio:.3f}x the analytic pool+view "
                    f"bound {bound} (max {a.get('max_ratio', 1.15)}x) "
                    "— an extra full-pool or full-view pass crept "
                    "into the tick",
                    {"measured": inv["hbm_bytes"], "analytic": bound,
                     "ratio": round(ratio, 4)}))
        elif kind == "verify_hbm":
            geom = geometries.get(name) or {}
            try:
                bound = analytic_verify_hbm_bytes(geom)
            except KeyError:
                findings.append(Finding(
                    COST_ANCHOR, Severity.ERROR, name, "verify_hbm",
                    "verify_hbm anchor needs geometry metadata "
                    "(param_bytes, kv_cache_bytes) on the registered "
                    "site's BuildResult", {}))
                continue
            ratio = inv["hbm_bytes"] / bound if bound else float("inf")
            if ratio > float(a.get("max_ratio", 1.15)):
                findings.append(Finding(
                    COST_ANCHOR, Severity.ERROR, name, "verify_hbm",
                    f"verify-k dispatch models {inv['hbm_bytes']} HBM "
                    f"bytes = {ratio:.3f}x the analytic single-pass "
                    f"k-token bound {bound} (max "
                    f"{a.get('max_ratio', 1.15)}x) — an extra weight "
                    "stream or cache pass re-per-tokenized the verify "
                    "block",
                    {"measured": inv["hbm_bytes"], "analytic": bound,
                     "ratio": round(ratio, 4)}))
        elif kind == "comm_bytes":
            # wire-precision invariant (ISSUE 17): this program's
            # per-chip collective bytes must stay at least min_ratio
            # BELOW its full-precision twin's — int8/bf16 collectives
            # silently reverting to f32 payloads is exactly the
            # regression this anchor exists to catch
            ref_name = a.get("baseline_program", "")
            ref = inventories.get(ref_name)
            if ref is None:
                if ref_name in live:
                    continue    # partial run; full runs flag missing
                findings.append(Finding(
                    COST_ANCHOR, Severity.ERROR, name, "comm_bytes",
                    f"comm_bytes anchor references baseline_program "
                    f"{ref_name!r} which the registry does not have — "
                    "fix the baseline", {"baseline_program": ref_name}))
                continue
            mine = int(inv.get("collective_bytes", 0))
            theirs = int(ref.get("collective_bytes", 0))
            min_ratio = float(a.get("min_ratio", 1.0))
            ratio = (theirs / mine) if mine else float("inf")
            if ratio < min_ratio:
                findings.append(Finding(
                    COST_ANCHOR, Severity.ERROR, name, "comm_bytes",
                    f"collective bytes {mine} vs {ref_name}'s {theirs} "
                    f"= {ratio:.2f}x reduction, below the anchored "
                    f"{min_ratio:.2f}x — the quantized collectives "
                    "regressed toward full-precision wire bytes",
                    {"measured": mine, "reference": theirs,
                     "ratio": round(ratio, 4),
                     "min_ratio": min_ratio}))
        elif kind == "matmul_share_floor":
            floor = float(a.get("min_share", 0.0))
            if inv["matmul_flop_share"] < floor:
                findings.append(Finding(
                    COST_ANCHOR, Severity.ERROR, name,
                    "matmul_share_floor",
                    f"matmul FLOP share {inv['matmul_flop_share']:.4f} "
                    f"broke the hand-set anchor floor {floor:.4f}",
                    {"measured": inv["matmul_flop_share"],
                     "floor": floor}))
        elif kind == "fusion_hbm":
            # fused-kernel A/B invariant (ISSUE 19): this program is
            # its baseline_program with a fusion knob ON — its modeled
            # HBM bytes must stay at or below max_ratio of the unfused
            # twin's (the measured win is PINNED, not aspirational),
            # and, when max_kernel_delta is set, its kernel count must
            # not creep back up past baseline + max_kernel_delta
            ref_name = a.get("baseline_program", "")
            ref = inventories.get(ref_name)
            if ref is None:
                if ref_name in live:
                    continue    # partial run; full runs flag missing
                findings.append(Finding(
                    COST_ANCHOR, Severity.ERROR, name, "fusion_hbm",
                    f"fusion_hbm anchor references baseline_program "
                    f"{ref_name!r} which the registry does not have — "
                    "fix the baseline", {"baseline_program": ref_name}))
                continue
            max_ratio = float(a.get("max_ratio", 1.0))
            ratio = (inv["hbm_bytes"] / ref["hbm_bytes"]
                     if ref["hbm_bytes"] else float("inf"))
            if ratio > max_ratio:
                findings.append(Finding(
                    COST_ANCHOR, Severity.ERROR, name, "fusion_hbm",
                    f"fused program models {inv['hbm_bytes']} HBM "
                    f"bytes = {ratio:.4f}x its unfused twin "
                    f"{ref_name}'s {ref['hbm_bytes']} (max "
                    f"{max_ratio:.4f}x) — the fused-kernel win "
                    "regressed",
                    {"measured": inv["hbm_bytes"],
                     "reference": ref["hbm_bytes"],
                     "ratio": round(ratio, 4),
                     "max_ratio": max_ratio}))
            if "max_kernel_delta" in a:
                delta = (int(inv["kernel_count"])
                         - int(ref["kernel_count"]))
                if delta > int(a["max_kernel_delta"]):
                    findings.append(Finding(
                        COST_ANCHOR, Severity.ERROR, name,
                        "fusion_hbm",
                        f"fused program launches {inv['kernel_count']} "
                        f"kernels vs {ref_name}'s "
                        f"{ref['kernel_count']} (delta {delta:+d}, max "
                        f"{int(a['max_kernel_delta']):+d}) — the "
                        "fused chain's kernel-count shrinkage "
                        "regressed",
                        {"measured": inv["kernel_count"],
                         "reference": ref["kernel_count"],
                         "delta": delta,
                         "max_kernel_delta":
                             int(a["max_kernel_delta"])}))
        else:
            # a typo while hand-editing the baseline must not silently
            # DISABLE an invariant — unknown kinds fail loudly
            findings.append(Finding(
                COST_ANCHOR, Severity.ERROR, name, "unknown-kind",
                f"anchor for {name!r} has unknown kind {kind!r} "
                "(valid: decode_hbm, decode_hbm_paged, verify_hbm, "
                "matmul_share_floor, comm_bytes, fusion_hbm) — the "
                "invariant was NOT evaluated; fix the baseline",
                {"kind": kind}))
    return findings
