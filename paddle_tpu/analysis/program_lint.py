"""Program linter: walk a jitted program's ClosedJaxpr + lowered
StableHLO and emit structured hazard findings.

The properties checked here are all statically decidable from the
lowered program ("Operator Fusion in XLA: Analysis and Evaluation",
PAPERS.md) — no execution happens. `lint_program` only traces and
lowers (`jax.jit(...).lower()`); the optional collective inventory
additionally compiles, because GSPMD inserts collectives during SPMD
partitioning, AFTER StableHLO — they exist only in the compiled HLO.

Hazard classes (paddle_tpu.analysis.findings codes):
- dtype-promotion: widening float convert_element_type on a non-trivial
  array — silent f32 (or f64) upcasts double HBM traffic on TPU.
- scatter-op / gather-op: scatter is warn (one-hot masked writes beat
  scatter 2.5x on the decode cache hot path — PERF.md PR 2); gather is
  info (embedding lookups are legitimate gathers; the baseline pins the
  accepted count so regressions still trip the gate).
- host-callback: io_callback/pure_callback/debug_callback inside a
  compiled program forces a host round-trip per execution.
- baked-rng-key: a PRNG key captured as a trace-time constant — every
  run replays identical "randomness" (framework/random.py rng_guard
  contract exists precisely to prevent this).
- undonated-buffer: an input whose (shape, dtype) matches an output and
  is big enough to matter, not marked donated — the caller is paying a
  full HBM copy XLA could alias away (train-step params, KV caches).
- collective: inventory info finding per collective kind with count and
  byte estimate (the EQuARX-style audit: know what collectives/dtypes a
  program actually contains before it reaches hardware).
"""
from __future__ import annotations

import re
from typing import Dict, List, Optional, Tuple

import numpy as np

import jax
from jax import dtypes as _dtypes

from ._util import leaf_labels
from .findings import (BAKED_RNG_KEY, COLLECTIVE, DTYPE_PROMOTION,
                       GATHER_OP, HOST_CALLBACK, SCATTER_OP,
                       UNDONATED_BUFFER, Finding, Severity)
from .hlo_cost import HLO_DTYPE_BYTES as _HLO_DTYPE_BYTES

__all__ = ["lint_program", "collective_inventory_from_hlo"]

# widening float chains flagged by dtype-promotion (narrow -> wider set)
_WIDENS = {
    "bfloat16": ("float32", "float64"),
    "float16": ("float32", "float64"),
    "float32": ("float64",),
}

_CALLBACK_PRIMS = {"pure_callback", "io_callback", "debug_callback",
                   "callback", "outside_call"}

# explicit (shard_map/pmap-level) collective primitives visible in jaxprs
_JAXPR_COLLECTIVES = {"psum", "all_gather", "all_to_all", "ppermute",
                      "pmax", "pmin", "psum_scatter", "reduce_scatter"}

# HLO op names of post-partitioning collectives (compiled programs)
_HLO_COLLECTIVE_RE = re.compile(
    r"=\s*(?P<lhs>[^=]*?)\s*"
    r"(?P<op>all-reduce|all-gather|reduce-scatter|collective-permute|"
    r"all-to-all)(?:-start)?\(")

_HLO_SHAPE_RE = re.compile(r"(?P<dt>[a-z]+\d+|pred)\[(?P<dims>[0-9,]*)\]")

# {{0,1},{2,3}} explicit form, the iota form [groups,size]<=[n], or the
# EMPTY form {} (HLO for "all replicas in one group")
_REPLICA_GROUPS_RE = re.compile(
    r"replica_groups=(?:\{\{(?P<first>[0-9, ]*)\}"
    r"|\[(?P<ng>[0-9]+),(?P<gs>[0-9]+)\]<="
    r"|(?P<all>\{\}))")
_NUM_PARTITIONS_RE = re.compile(
    r"\b(?:num_partitions|replica_count)=(\d+)")

def _replica_group_size(line: str, all_devices: int = 1) -> int:
    """Devices per replica group on one collective's HLO line.
    `replica_groups={}` means ALL replicas form one group — the caller
    passes the module's partition/replica count for that case; no
    annotation at all reads as a degenerate single-device group."""
    m = _REPLICA_GROUPS_RE.search(line)
    if m is None:
        return 1
    if m.group("all") is not None:
        return max(all_devices, 1)
    if m.group("gs") is not None:
        return max(int(m.group("gs")), 1)
    first = [x for x in m.group("first").split(",") if x.strip()]
    return max(len(first), 1)


# per-chip transferred fraction of the RESULT bytes for a ring
# algorithm over an n-wide group:
# all-gather's result is the full gathered tensor -> (n-1)/n of it
# moves; reduce-scatter's result is the 1/n shard -> (n-1) x result;
# ring all-reduce = reduce-scatter + all-gather phases; a permute is
# one hop; all-to-all keeps (n-1)/n.
def _xfer_factor(op: str, n: int) -> float:
    if op == "collective-permute":
        return 1.0      # one hop; pairs, not replica groups
    if n <= 1:
        return 0.0      # degenerate self-group: nothing crosses ICI
    return {"all-gather": (n - 1) / n,
            "reduce-scatter": float(n - 1),
            "all-reduce": 2 * (n - 1) / n,
            "all-to-all": (n - 1) / n}.get(op, 1.0)


def _subjaxprs(params: dict):
    """Yield every Jaxpr/ClosedJaxpr nested in an eqn's params (pjit,
    scan, while, cond branches, custom_jvp/vjp, remat, shard_map...)."""
    from jax.extend.core import ClosedJaxpr, Jaxpr
    for v in params.values():
        vals = v if isinstance(v, (tuple, list)) else (v,)
        for x in vals:
            if isinstance(x, ClosedJaxpr):
                yield x.jaxpr, tuple(x.consts)
            elif isinstance(x, Jaxpr):
                yield x, ()


def _walk(jaxpr, consts, path=""):
    """Depth-first (eqn, path) over a jaxpr and all sub-jaxprs; also
    yields ('consts', consts, path) groups so key constants anywhere in
    the nesting are seen."""
    yield ("consts", consts, path)
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        yield ("eqn", eqn, path)
        for sub, sub_consts in _subjaxprs(eqn.params):
            yield from _walk(sub, sub_consts, f"{path}/{name}" if path
                             else name)


def _is_key_const(c) -> bool:
    dt = getattr(c, "dtype", None)
    if dt is not None:
        try:
            if _dtypes.issubdtype(dt, _dtypes.prng_key):
                return True
        except (TypeError, AttributeError):
            pass
    # raw-key form: uint32 vector of 2 (threefry) or 4 (rbg) words
    shape = tuple(getattr(c, "shape", ()) or ())
    return (dt is not None and np.dtype(dt) == np.uint32
            and shape in ((2,), (4,), (1, 2), (1, 4)))


def _aval_nbytes(aval) -> int:
    try:
        return int(np.prod(aval.shape)) * np.dtype(aval.dtype).itemsize
    except (TypeError, ValueError):
        return 0


def collective_inventory_from_hlo(hlo_text: str) -> Dict[str, dict]:
    """Parse compiled-HLO text into {collective-kind: {count, bytes,
    result_bytes, group_size}}. `result_bytes` sums each op's result
    shapes (tuple results of -start forms included); `bytes` is the
    PER-CHIP transferred estimate — result bytes scaled by the ring
    transfer factor for the op's replica-group size (counting groups:
    an 8-wide all-gather moves (n-1)/n of the gathered tensor per chip,
    not the whole result — the ZeRO-2 inventory was overstating every
    entry before groups were counted). `group_size` is the max group
    width seen for the kind (mixed widths keep per-op scaling)."""
    inv: Dict[str, dict] = {}
    # module-wide device count, for empty replica_groups={} (= one
    # all-replica group): max over the HloModule header line's
    # num_partitions / replica_count annotations — the whole first
    # line, since a real-size entry_computation_layout pushes the
    # attribute thousands of chars in
    header = hlo_text[:hlo_text.find("\n")] if "\n" in hlo_text \
        else hlo_text
    all_devices = max((int(n) for n in
                       _NUM_PARTITIONS_RE.findall(header)),
                      default=1)
    for line in hlo_text.splitlines():
        m = _HLO_COLLECTIVE_RE.search(line)
        if m is None:
            continue
        op = m.group("op")
        nbytes = 0
        for sm in _HLO_SHAPE_RE.finditer(line[:m.end("op")]):
            dims = sm.group("dims")
            n = 1
            for d in dims.split(","):
                if d:
                    n *= int(d)
            nbytes += n * _HLO_DTYPE_BYTES.get(sm.group("dt"), 4)
        group = _replica_group_size(line, all_devices)
        rec = inv.setdefault(op, {"count": 0, "bytes": 0,
                                  "result_bytes": 0, "group_size": 1})
        rec["count"] += 1
        rec["result_bytes"] += nbytes
        rec["bytes"] += int(nbytes * _xfer_factor(op, group))
        rec["group_size"] = max(rec["group_size"], group)
    return inv


def lint_program(name: str, fn, args: Tuple = (), kwargs: Optional[dict]
                 = None, *, compile_collectives: bool = False,
                 donation_bytes_threshold: int = 16 * 1024,
                 promotion_min_elems: int = 128) -> List[Finding]:
    """Lint one jitted program. `fn` may be a `jax.jit` wrapper or a
    plain traceable callable (then it is wrapped un-donated — donation
    findings reflect the wrapper actually passed, so pass the REAL
    program object to audit its donation).

    Only traces/lowers; compiles additionally iff compile_collectives
    (GSPMD materializes collectives post-partitioning)."""
    kwargs = dict(kwargs or {})
    jitted = fn if hasattr(fn, "lower") else jax.jit(fn)
    findings: List[Finding] = []

    closed = jax.make_jaxpr(jitted)(*args, **kwargs)

    promo: Dict[Tuple[str, str], int] = {}
    prim_hits: Dict[str, int] = {}
    jaxpr_colls: Dict[str, dict] = {}
    baked_keys: List[str] = []
    seen_key_const_ids = set()

    for kind, obj, path in _walk(closed.jaxpr, tuple(closed.consts)):
        if kind == "consts":
            for c in obj:
                if id(c) in seen_key_const_ids:
                    continue
                if _is_key_const(c):
                    seen_key_const_ids.add(id(c))
                    baked_keys.append(
                        f"const:{tuple(getattr(c, 'shape', ()) or ())}")
            continue
        eqn = obj
        pname = eqn.primitive.name
        if pname == "convert_element_type":
            src = eqn.invars[0].aval
            dst = eqn.outvars[0].aval
            if (str(src.dtype) in _WIDENS
                    and str(dst.dtype) in _WIDENS[str(src.dtype)]
                    and int(np.prod(src.shape or ()))
                    >= promotion_min_elems):
                promo[(str(src.dtype), str(dst.dtype))] = promo.get(
                    (str(src.dtype), str(dst.dtype)), 0) + 1
        elif pname.startswith("scatter"):
            prim_hits["scatter"] = prim_hits.get("scatter", 0) + 1
        elif pname == "gather":
            prim_hits["gather"] = prim_hits.get("gather", 0) + 1
        elif pname in _CALLBACK_PRIMS:
            prim_hits[pname] = prim_hits.get(pname, 0) + 1
        elif pname in _JAXPR_COLLECTIVES:
            nbytes = sum(_aval_nbytes(v.aval) for v in eqn.outvars)
            rec = jaxpr_colls.setdefault(pname, {"count": 0, "bytes": 0})
            rec["count"] += 1
            rec["bytes"] += nbytes

    for (src, dst), n in sorted(promo.items()):
        findings.append(Finding(
            DTYPE_PROMOTION, Severity.WARN, name, f"{src}->{dst}",
            f"{n} widening convert(s) {src}->{dst} on arrays >= "
            f"{promotion_min_elems} elems — check for unintended "
            f"promotion (weak-type literals, mixed-dtype math)",
            {"count": n}))
    n_scatter = prim_hits.get("scatter", 0)
    if n_scatter:
        findings.append(Finding(
            SCATTER_OP, Severity.WARN, name, "scatter",
            f"{n_scatter} scatter op(s) in compiled program — on the "
            "decode/cache hot path one-hot masked writes are 2.5x "
            "faster (PERF.md, PR 2)", {"count": n_scatter}))
    n_gather = prim_hits.get("gather", 0)
    if n_gather:
        findings.append(Finding(
            GATHER_OP, Severity.INFO, name, "gather",
            f"{n_gather} gather op(s) (embedding lookups are expected; "
            "baseline pins the accepted count)", {"count": n_gather}))
    for cb in sorted(set(prim_hits) & _CALLBACK_PRIMS):
        findings.append(Finding(
            HOST_CALLBACK, Severity.WARN, name, cb,
            f"{prim_hits[cb]} {cb}(s) inside the compiled program — "
            "each execution pays a host round-trip",
            {"count": prim_hits[cb]}))
    for site in sorted(set(baked_keys)):
        findings.append(Finding(
            BAKED_RNG_KEY, Severity.WARN, name, site,
            "PRNG key constant-folded into the program at trace time — "
            "every run replays the same stream; thread the key as an "
            "argument (framework/random.rng_guard contract)", {}))
    for pname, rec in sorted(jaxpr_colls.items()):
        findings.append(Finding(
            COLLECTIVE, Severity.INFO, name, pname,
            f"{rec['count']} {pname} op(s), ~{rec['bytes']} bytes",
            dict(rec)))

    # -- donation audit (lowered StableHLO + args_info) -------------------
    try:
        lowered = jitted.lower(*args, **kwargs)
    except Exception as e:   # pragma: no cover - lowering bugs surface loud
        findings.append(Finding(
            "lint-error", Severity.ERROR, name, "lower",
            f"lowering failed: {type(e).__name__}: {e}", {}))
        return findings
    arg_leaves = jax.tree_util.tree_leaves(lowered.args_info)
    labels = leaf_labels(args, kwargs)
    # output avals from the jaxpr already in hand — a third abstract
    # trace (eval_shape) would double-charge big programs
    out_set = {(tuple(a.shape), str(a.dtype))
               for a in closed.out_avals if hasattr(a, "shape")}
    for i, info in enumerate(arg_leaves):
        aval = getattr(info, "aval", info)
        donated = bool(getattr(info, "donated", False))
        sig = (tuple(aval.shape), str(aval.dtype))
        if (not donated and sig in out_set
                and _aval_nbytes(aval) >= donation_bytes_threshold):
            label = labels[i] if i < len(labels) else f"arg{i}"
            findings.append(Finding(
                UNDONATED_BUFFER, Severity.WARN, name,
                f"{label}:{list(aval.shape)}:{aval.dtype}",
                f"input {label} {sig} matches an output aval and is "
                f"{_aval_nbytes(aval)} bytes but is not donated — the "
                "caller pays a copy XLA could alias away "
                "(donate_argnums)", {"nbytes": _aval_nbytes(aval)}))

    if compile_collectives:
        try:
            hlo = lowered.compile().as_text()
        except Exception as e:
            findings.append(Finding(
                "lint-error", Severity.ERROR, name, "compile",
                f"compile for collective inventory failed: "
                f"{type(e).__name__}: {e}", {}))
            return findings
        for op, rec in sorted(collective_inventory_from_hlo(hlo).items()):
            findings.append(Finding(
                COLLECTIVE, Severity.INFO, name, op,
                f"{rec['count']} {op} op(s), ~{rec['bytes']} bytes "
                f"transferred per chip per step (group size "
                f"{rec['group_size']})", dict(rec)))
    return findings
