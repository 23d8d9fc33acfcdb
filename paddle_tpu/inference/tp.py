"""Tensor-parallel serving slice: replica = N-chip slice (ISSUE 20).

The training side has owned the mesh machinery since PR 4 — pjit over
named axes, Megatron TP layouts on every Column/RowParallelLinear, the
8/64-virtual-device harness — while serving stayed single-device end to
end. This module is the bridge: a :class:`TPContext` wraps ONE engine's
slice of ``tp`` devices as a dedicated ``("mp",)`` mesh and activates it
around that engine's program traces only (``distributed.mesh.use_mesh``
is thread-local — a TP engine and a single-chip engine, or a training
thread, coexist in one process without leaking "mp" constraints into
each other's traces).

What gets sharded (the Megatron serving layout):

====================  =========================  =====================
tensor                shape                      PartitionSpec
====================  =========================  =====================
Column weights        [in, out]                  (None, "mp")
Column bias           [out]                      ("mp",)
Row weights           [in, out]                  ("mp", None)
vocab embedding       [V, H]                     ("mp", None)
everything else       —                          replicated
KV data/pages         [..., nkv, hd]             nkv axis -> "mp"
int8 scale planes     [..., nkv]                 nkv axis -> "mp"
block tables / masks  host int32/bool            replicated
====================  =========================  =====================

The param specs are not decided here — they are read off each
parameter's ``sharding_axes`` annotation (mp_layers set them at model
construction; GPT and Llama both build their blocks from the parallel
layers), so the engine shards EXACTLY the layout training would. KV
pools shard on the head axis because column-parallel QKV already
computes only the local heads per chip; block tables stay replicated so
``paging.py``'s host-side allocator/trie/COW logic is untouched.

Per-block wire traffic is one all-reduce after attention out-proj and
one after the MLP down-proj (GSPMD derives them from the
replicated-output constraint in RowParallelLinear). Under
``comm_precision="int8"|"bf16"`` the engine traces its programs inside
``mp_layers.tp_comm_precision(...)``, routing those reductions through
the PR 17 EQuARX bodies (quantized wire, f32 accumulate) instead.

Correctness oracle (tests/test_tp_engine.py):
greedy token IDs from a tp>1 engine are identical to the single-chip
engine — slot and paged, f32 and int8 caches, speculative verify
included — with zero recompiles under prompt-length drift.
"""
from __future__ import annotations

import contextlib
from typing import Optional, Sequence

import numpy as np

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..distributed import mesh as mesh_mod
from ..distributed.meta_parallel.mp_layers import tp_comm_precision
from ..framework.env import int_env as _env_int

__all__ = ["TPContext", "build_tp_mesh", "resolve_tp",
           "validate_tp_model", "TP_AXIS"]

# the serving slice reuses the training mesh's innermost (fastest-ICI)
# axis name, so every mp_layers ``sharding_axes`` annotation and
# ``_constrain`` call resolves against it unchanged
TP_AXIS = "mp"


def resolve_tp(tp: Optional[int]) -> int:
    """Effective tensor-parallel degree: explicit arg wins, then
    PADDLE_TPU_SERVE_TP, default 1 (the single-chip engine)."""
    if tp is None:
        tp = _env_int("PADDLE_TPU_SERVE_TP", 1)
    tp = int(tp)
    if tp < 1:
        raise ValueError(f"tp degree must be >= 1, got {tp}")
    return tp


def build_tp_mesh(tp: int, devices: Optional[Sequence] = None) -> Mesh:
    """A dedicated ``(tp,)`` mesh over the leading ``tp`` devices with
    the single axis "mp" — the serving slice. Built directly (not via
    ``init_mesh``) so it NEVER installs itself process-globally; the
    engine activates it thread-locally around its own traces."""
    devices = list(devices if devices is not None else jax.devices())
    if tp > len(devices):
        raise ValueError(
            f"tp={tp} needs {tp} devices, have {len(devices)} "
            f"(virtual-mesh runs: XLA_FLAGS="
            f"--xla_force_host_platform_device_count={tp})")
    return Mesh(np.asarray(devices[:tp]), (TP_AXIS,))


def validate_tp_model(model, tp: int) -> None:
    """Loud divisibility gate: head counts (the KV pools shard on the
    kv-head axis) and every sharded weight dimension must divide by tp.
    An uneven split would make GSPMD pad shards — correct-looking but
    silently different layouts per chip, and the KV head/scale planes
    would no longer align with the column-parallel heads."""
    cfg = getattr(model, "cfg", None)
    nh = getattr(cfg, "num_heads", None)
    if nh is not None and nh % tp:
        raise ValueError(
            f"tp={tp} does not divide num_heads={nh}: attention heads "
            "shard per-head (Megatron convention)")
    nkv = getattr(cfg, "kv_heads", None)
    if nkv is None:
        nkv = getattr(cfg, "num_kv_heads", None) or nh
    if nkv is not None and nkv % tp:
        raise ValueError(
            f"tp={tp} does not divide kv_heads={nkv}: the KV pools "
            "shard on the kv-head axis")
    for name, p in model.named_parameters():
        axes = getattr(p, "sharding_axes", None)
        if not axes:
            continue
        for dim, ax in enumerate(axes):
            names = (ax,) if isinstance(ax, str) else tuple(ax or ())
            if TP_AXIS in names and p.shape[dim] % tp:
                raise ValueError(
                    f"tp={tp} does not divide dim {dim} "
                    f"({p.shape[dim]}) of sharded parameter {name!r}")


class TPContext:
    """One engine's tensor-parallel slice: the mesh, the trace-time
    activation scope, and the device_put helpers that land params /
    buffers / KV caches in the Megatron layout."""

    def __init__(self, tp: int, devices: Optional[Sequence] = None,
                 comm_precision: Optional[str] = None,
                 mesh: Optional[Mesh] = None):
        self.tp = int(tp)
        if mesh is not None:
            if TP_AXIS not in mesh.shape:
                raise ValueError(
                    f"engine mesh needs a {TP_AXIS!r} axis, has "
                    f"{tuple(mesh.shape)}")
            if mesh.shape[TP_AXIS] != self.tp:
                raise ValueError(
                    f"mesh {TP_AXIS} degree {mesh.shape[TP_AXIS]} != "
                    f"tp {self.tp}")
            self.mesh = mesh
        else:
            self.mesh = build_tp_mesh(self.tp, devices)
        if comm_precision not in (None, "fp32", "bf16", "int8"):
            raise ValueError(
                f"comm_precision {comm_precision!r}: "
                "expected fp32|bf16|int8")
        self.comm_precision = (None if comm_precision == "fp32"
                               else comm_precision)
        self._replicated = NamedSharding(self.mesh, P())

    # -- trace-time activation ------------------------------------------
    @contextlib.contextmanager
    def activate(self):
        """Thread-locally make this slice THE mesh (mp_layers'
        ``_constrain`` emits real "mp" constraints) and route the
        per-block all-reduce through the quantized wire bodies when
        configured. Wraps every engine trace/dispatch site; a no-op for
        the math on re-execution, but kept on the call path so lazy
        (non-warmup) first calls trace correctly too."""
        with mesh_mod.use_mesh(self.mesh):
            with tp_comm_precision(self.comm_precision):
                yield self

    # -- placement helpers ----------------------------------------------
    def replicate(self, tree):
        """device_put a pytree fully replicated over the slice."""
        return jax.tree_util.tree_map(
            lambda x: jax.device_put(x, self._replicated), tree)

    def shard_state(self, model, params: dict, buffers: dict):
        """Land ``raw_state(model)``'s params/buffers on the slice:
        each parameter by its own ``sharding_axes`` annotation (the
        layout mp_layers declared at construction), buffers (and
        un-annotated params) replicated."""
        axes = {n: getattr(p, "sharding_axes", None)
                for n, p in model.named_parameters()}
        out_p = {}
        for name, value in params.items():
            spec = axes.get(name)
            sh = (mesh_mod.named_sharding(*spec, mesh=self.mesh)
                  if spec else self._replicated)
            out_p[name] = jax.device_put(value, sh)
        out_b = {n: jax.device_put(v, self._replicated)
                 for n, v in buffers.items()}
        return out_p, out_b

    def cache_sharding(self, key: Optional[str], ndim: int):
        """The ONE rule for every KV-cache leaf shape this repo has:
        int8 scale planes ([..., nkv]) shard on their LAST axis, data
        leaves ([..., nkv, hd]) on their second-to-last — covering slot
        rows, paged pools, int8 dict halves and the scan-stacked
        (leading-L) variants of each without enumeration."""
        axes = [None] * ndim
        axes[ndim - 1 if key == "scale" else ndim - 2] = TP_AXIS
        return mesh_mod.named_sharding(*axes, mesh=self.mesh)

    def shard_caches(self, caches):
        """device_put a cache pytree (any engine form) head-sharded."""
        def put(path, leaf):
            key = None
            for entry in reversed(path):
                if isinstance(entry, jax.tree_util.DictKey):
                    key = entry.key
                    break
            return jax.device_put(
                leaf, self.cache_sharding(key, leaf.ndim))
        return jax.tree_util.tree_map_with_path(put, caches)

    # -- accounting / reporting -----------------------------------------
    def modeled_tick_comm_bytes(self, num_layers: int, hidden: int,
                                slots: int, tick_tokens: int) -> int:
        """Analytic PER-CHIP all-reduce bytes one decode tick moves:
        tick_tokens micro-steps, each forwarding [slots, 1, hidden]
        through num_layers blocks with TWO replicated-output reductions
        per block (attention out-proj + MLP down-proj), priced at the
        ring all-reduce's 2*(tp-1)/tp per-chip wire factor and the
        configured wire precision's bytes/element. The same formula the
        obs tick span reports — tpucost's comm_bytes anchor measures the
        real HLO bytes this models."""
        if self.tp == 1:
            return 0
        wire = {"int8": 1.0 + 4.0 / 256.0,   # int8 payload + f32 block
                "bf16": 2.0}.get(self.comm_precision, 4.0)  # scales
        payload = slots * hidden * wire
        ring = 2.0 * (self.tp - 1) / self.tp
        return int(tick_tokens * num_layers * 2 * payload * ring)

    def describe(self) -> dict:
        """Mesh geometry for stats()/healthz — JSON-safe."""
        return {"tp": self.tp, "mesh_axis": TP_AXIS,
                "mesh_devices": int(np.prod(self.mesh.devices.shape)),
                "comm_precision": self.comm_precision or "fp32",
                "devices": [str(d) for d in self.mesh.devices.flat]}
