"""Continuous-batching serving engine with a slot-based KV cache.

Serving north star (ROADMAP: "heavy traffic from millions of users, as
fast as the hardware allows"): `models/generation.py::generate()` decodes
ONE stream per compiled program, so chip utilization collapses to
batch=1 the moment traffic is concurrent. This engine multiplexes many
requests through a CONSTANT set of compiled programs:

- a fixed pool of N decode slots backed by one pre-allocated slot-based
  KV cache (`model.new_cache(N, max_len, dtype)` — per-layer
  [B=N, max_len, kv_heads, head_dim] arrays, bf16/f32 or the int8
  quantized dict form), donated through every step so XLA updates it in
  place in HBM;
- ONE jitted batched decode program per engine: each tick runs
  `tick_tokens` micro-steps for ALL slots (dead slots ride along under
  an active mask — fixed shapes, no recompiles, one host sync per tick
  for the emitted [N, tick_tokens] block);
- a small set of bucketed prefill programs: a queued request's prompt is
  right-padded to the nearest bucket, prefilled into a FRESH zeroed
  cache inside the program, and the whole slot row range is overwritten
  at admission (so a retired slot's stale rows — including int8
  quantization scales — can never leak into the next request);
- admission and retirement happen at tick boundaries only: queued
  requests enter free slots, finished ones (per-request EOS / token
  budget) resolve their futures. No head-of-line blocking: a long
  request never stalls short ones sharing the batch.

Why right-padded bucketed prefill is exact: causal attention means the
garbage rows a padded prompt writes at [P, bucket) are never attended
by positions < P, and decode overwrites position p before the mask can
reach it — so greedy outputs are token-identical to sequential
`generate()` per request (asserted in tests/test_engine.py).

Fusion-preserving, recompile-free regime per "Operator Fusion in XLA"
and MPK (PAPERS.md): the decode step stays one fixed-shape compiled
program; concurrency is multiplexed through it, never traced into it.

Paged mode (``paged=True`` / PADDLE_TPU_SERVE_PAGED — ISSUE 9): the
worst-case [N, max_len] slot rows above waste cache on the 99% of
requests that are short — one long ``max_len`` caps concurrency for
everyone, and tpucost's decode anchor shows the tick is KV-bandwidth
bound, so every wasted byte is wasted HBM traffic too. Paged mode
carves the cache into fixed ``page_size``-token PAGES shared by all
slots (per-layer pools [num_pages, page_size, kv_heads, hd]); each slot
holds a BLOCK TABLE of physical page indices:

- the ONE batched decode program GATHERS each slot's pages by table
  index into the contiguous view attention already understands (reads
  stay gather-based — the scatter-free decode anchor holds) and writes
  stay one-hot masked into the slot's current page, gated on the live
  mask so a dead slot can never touch a page reallocated to another
  request;
- admission appends the VARIABLE-LENGTH prefill output page-by-page
  (bucketed by suffix length, write-masked to the real rows) instead of
  rebuilding a worst-case row — a request holds exactly
  ceil((P + max_new + tick) / page_size) pages, so at equal cache bytes
  the pool admits strictly more short requests than slot rows can;
- a host-side page allocator (free list + refcounts, inference/paging)
  lets concurrent requests SHARE the read-only pages of a common prompt
  prefix: the prefix trie matches complete prompt pages at admission,
  matched pages are increffed instead of recomputed (prefill work drops
  to the un-matched suffix — for a fully-cached prompt, to ONE token),
  and the only page a fully-matched prompt would write into is
  copy-on-written first. Shared pages are read-only for life: complete
  prompt pages end strictly below every decode write position.

Why paged greedy output is token-identical to the slot engine: the
gathered view has the same length the slot row had, the causal mask
passes the same positions, and masked garbage (stale pages, bucket
padding) contributes exact zeros through softmax(-1e30) — asserted in
tests/test_paged_engine.py, including int8 pools and shared-prefix
admissions.

Speculative mode (``speculative=`` / PADDLE_TPU_SERVE_SPEC — ISSUE 13,
ROADMAP item 2): the decode tick above still pays one model forward
per emitted token. With speculative decoding on, the tick loop swaps
the plain tick for a DRAFT -> VERIFY pair (inference/speculative.py):
a proposer drafts up to k candidate tokens per slot (host-side n-gram
self-drafting, or a small draft model's own registered decode
program), and ONE jitted batched verify program scores all k+1
positions for every slot in a single target forward — per-slot
proposal vectors, draft lengths, positions and live masks ride as
int32/bool arguments, so k-drift / acceptance-pattern drift / prompt
drift never recompile. The emitted block is the TARGET's own argmax at
every position, so greedy speculative output is bitwise
token-identical to plain decode (f32 and int8, slot and paged caches —
tier-1 asserted); acceptance only decides how many tokens each tick
consumes. Rejected positions need no KV rollback: their garbage KV
sits above the row's true length behind the causal mask (and, paged,
behind the live write gate in the slot's PRIVATE pages) until the true
token overwrites it. Greedy only — ``do_sample`` rejects loudly.

Env knobs: PADDLE_TPU_SERVE_SLOTS (default 8),
PADDLE_TPU_SERVE_PREFILL_BUCKETS (comma list, default powers of two),
PADDLE_TPU_SERVE_TICK_TOKENS (default 8),
PADDLE_TPU_SERVE_MAX_QUEUE (default 32),
PADDLE_TPU_SERVE_PAGED (default 0), PADDLE_TPU_KV_PAGE (page size,
default 16), PADDLE_TPU_SERVE_NUM_PAGES (default slots *
ceil(max_len/page) — the slot engine's exact byte budget),
PADDLE_TPU_SERVE_SPEC ("ngram" to self-draft, default off),
PADDLE_TPU_SERVE_SPEC_K (draft tokens per tick, default 4),
PADDLE_TPU_SERVE_SPEC_NGRAM (max suffix n-gram, default 3).
"""
from __future__ import annotations

import contextlib
import os
import threading
import time
import uuid
from concurrent.futures import Future
from dataclasses import dataclass, field
from typing import List, Optional

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from .. import obs as _obs
from ..obs import efficiency as _eff
from ..distributed import resilience as _resil
from ..jit.functional import functional_call, raw_state
from ..models.generation import _select_token
from .paging import pages_needed as _pages_needed

__all__ = ["ContinuousBatchingEngine", "EngineOverloaded",
           "CacheExhausted", "RequestCancelled", "GenerationPredictor",
           "create_engine_predictor"]


class EngineOverloaded(RuntimeError):
    """Raised by submit() when the request queue is at capacity — the
    serving layer maps this to the 503 `overloaded` record (same
    load-shedding contract as the PR-1 predictor path). ``reason`` is
    the truthful shed record the serving layer forwards (a subclass
    narrows it)."""

    reason = "overloaded"

    def __init__(self, queue_depth: int, max_queue: int):
        super().__init__(
            f"engine queue saturated ({queue_depth}/{max_queue})")
        self.queue_depth = queue_depth
        self.max_queue = max_queue


class CacheExhausted(EngineOverloaded):
    """Queue saturated while the KV page pool — not slot count or
    request rate — is the binding constraint (paged engines only). The
    serving layer maps this to 503 `cache_exhausted` so operators can
    tell "add cache pages / shrink page footprints" from plain
    overload; retries clear when a request retires and frees pages."""

    reason = "cache_exhausted"

    def __init__(self, queue_depth: int, max_queue: int,
                 free_pages: int, num_pages: int):
        super().__init__(queue_depth, max_queue)
        self.free_pages = free_pages
        self.num_pages = num_pages


class RequestCancelled(RuntimeError):
    """The request was cancelled (``engine.cancel`` — client
    disconnect, a hedged duplicate losing its race, an operator
    ``POST /cancel``). Raised out of the request's future; the partial
    result — tokens generated before the cancel landed — rides the
    future's ``_ptpu_gen_info`` (``tokens_generated`` +
    ``partial_tokens``) so no work is silently discarded. Cancellation
    applies at the next tick boundary: the slot retires, its KV pages
    free — leak-free, counter-asserted in tests."""

    def __init__(self, request_id: str, tokens_generated: int):
        super().__init__(
            f"request {request_id or '<anonymous>'} cancelled after "
            f"{tokens_generated} generated token(s)")
        self.request_id = request_id
        self.tokens_generated = tokens_generated


def _attach_page_meta(caches, **meta):
    """Return the cache pytree with block-table / write-gate metadata
    merged into every paged dict (same traced arrays referenced
    everywhere — XLA sees one value). Scan-stacked pools (leaves with a
    leading layer axis — ``pages`` is 5-D) get the metadata broadcast
    with that same leading L, so ScannedStack's layer scan slices ONE
    host block table into identical per-layer [B, PM] views (the block
    table's "layer axis", ISSUE 20 / the PR 9 follow-up) and each scan
    step sees an ordinary per-layer paged dict."""
    if isinstance(caches, dict):
        if "pages" not in caches:
            return caches
        if caches["pages"].ndim == 5:     # scan-stacked [L, NP, PS, ...]
            L = caches["pages"].shape[0]
            meta = {k: jnp.broadcast_to(jnp.asarray(v),
                                        (L,) + tuple(jnp.shape(v)))
                    for k, v in meta.items()}
        return {**caches, **meta}
    if isinstance(caches, (list, tuple)):
        return type(caches)(_attach_page_meta(c, **meta)
                            for c in caches)
    return caches


def _strip_page_meta(caches):
    """Inverse of _attach_page_meta: reduce paged dicts back to their
    pool leaves so the engine-held pytree (and the donated program
    output) is pools only."""
    if isinstance(caches, dict):
        return {k: v for k, v in caches.items()
                if k in ("pages", "scale")}
    if isinstance(caches, (list, tuple)):
        return type(caches)(_strip_page_meta(c) for c in caches)
    return caches


# shared env-knob parser (framework/env.py), aliased to keep call sites
from ..framework.env import int_env as _env_int


def _default_buckets(max_len: int) -> tuple:
    """Powers of two up to AND INCLUDING max_len (a long prompt with a
    small token budget legitimately prefills near the full cache)."""
    out, b = [], 8
    while b < max_len:
        out.append(b)
        b *= 2
    out.append(max_len)
    return tuple(sorted(set(out)))


@dataclass
class _Request:
    prompt: np.ndarray           # [P] int64
    max_new_tokens: int
    eos_token_id: Optional[int]
    seed: int
    future: Future = field(default_factory=Future)
    rid: str = ""                # request id (obs span correlation)
    t_submit: float = 0.0        # perf_counter at submit (obs only)
    drafted: int = 0             # speculative: tokens proposed for me
    accepted: int = 0            # speculative: proposals accepted
    progress_cb: Optional[object] = None   # per-token progress hook
    cancelled: bool = False      # cancel() flagged; retired at the
    #                              next tick boundary


class _Slot:
    """Host-side mirror of one decode slot's in-program state."""

    __slots__ = ("req", "pos", "tok", "alive", "remaining", "emitted",
                 "key", "t_dec0", "pages")

    def __init__(self):
        self.req: Optional[_Request] = None
        self.pos = 0
        self.tok = 0
        self.alive = False
        self.remaining = 0
        self.emitted: List[int] = []
        self.key = np.zeros(2, np.uint32)
        self.t_dec0 = 0.0        # decode-phase start (obs only)
        self.pages: List[int] = []   # paged mode: owned page refs

    @property
    def free(self) -> bool:
        return self.req is None


class ContinuousBatchingEngine:
    """Serve arbitrary concurrent mixed-length generate requests through
    a constant set of compiled programs (see module docstring).

    `model` must expose the cache-threaded forward contract of
    models/generation.py (GPTForCausalLM, LlamaForCausalLM do). Greedy
    outputs are token-identical to sequential `generate()`; sampling is
    reproducible per request (slot-position-keyed PRNG) but draws a
    different stream than the sequential scan.
    """

    def __init__(self, model, slots: Optional[int] = None,
                 max_len: Optional[int] = None,
                 cache_dtype: str = "bfloat16",
                 prefill_buckets: Optional[tuple] = None,
                 tick_tokens: Optional[int] = None,
                 max_queue: Optional[int] = None,
                 do_sample: bool = False, temperature: float = 1.0,
                 top_k: int = 0, top_p: float = 1.0,
                 paged: Optional[bool] = None,
                 page_size: Optional[int] = None,
                 num_pages: Optional[int] = None,
                 prefix_cache: bool = True,
                 speculative=None, spec_k: Optional[int] = None,
                 spec_ngram: Optional[int] = None, draft_model=None,
                 tp: Optional[int] = None, mesh=None,
                 comm_precision: Optional[str] = None):
        self.model = model
        self.slots = int(slots if slots is not None
                         else _env_int("PADDLE_TPU_SERVE_SLOTS", 8))
        if self.slots < 2:
            raise ValueError("engine needs >= 2 slots (batch-axis "
                             "detection and batching both require it)")
        model_max = getattr(getattr(model, "cfg", None), "max_seq_len",
                            None)
        self.max_len = int(max_len if max_len is not None
                           else (model_max or 1024))
        if model_max is not None and self.max_len > model_max:
            raise ValueError(
                f"max_len {self.max_len} exceeds the model's "
                f"max_seq_len {model_max}")
        if prefill_buckets is None:
            spec = os.environ.get("PADDLE_TPU_SERVE_PREFILL_BUCKETS", "")
            prefill_buckets = (tuple(int(x) for x in spec.split(",") if
                                     x.strip())
                               if spec else _default_buckets(self.max_len))
        self.prefill_buckets = tuple(sorted(
            b for b in prefill_buckets if b <= self.max_len))
        if not self.prefill_buckets:
            raise ValueError("no prefill bucket fits max_len")
        self.tick_tokens = int(
            tick_tokens if tick_tokens is not None
            else _env_int("PADDLE_TPU_SERVE_TICK_TOKENS", 8))
        if self.tick_tokens < 1:
            raise ValueError("tick_tokens must be >= 1")
        self.max_queue = int(
            max_queue if max_queue is not None
            else _env_int("PADDLE_TPU_SERVE_MAX_QUEUE", 32))
        self.cache_dtype = cache_dtype
        self._sampling = (bool(do_sample), float(temperature),
                          int(top_k), float(top_p))

        # tensor-parallel slice (inference/tp.py, ISSUE 20): tp > 1
        # makes THIS engine an N-chip replica — params/KV head-sharded
        # per the Megatron layout, programs pjit-partitioned over the
        # slice mesh, block tables and all host-side control replicated.
        # tp= / mesh= / PADDLE_TPU_SERVE_TP; comm_precision routes the
        # per-block all-reduce through the PR 17 quantized wire bodies.
        from .tp import TPContext, resolve_tp, validate_tp_model
        if mesh is not None and tp is None:
            tp = int(mesh.shape.get("mp", 1))
        self.tp = resolve_tp(tp)
        if self.tp > 1 or mesh is not None:
            validate_tp_model(model, self.tp)
            self._tp = TPContext(self.tp, comm_precision=comm_precision,
                                 mesh=mesh)
        else:
            self._tp = None
        # fused-kernel knobs × TP (ISSUE 20 satellite): knobs that are
        # env-enabled but forced off under this engine's sharded mesh —
        # the loud fallback fires HERE (once, at construction), and
        # stats() carries the list so operators see the downgrade
        self.fused_knobs_disabled_tp: List[str] = []
        if self._tp is not None:
            from ..framework.env import bool_env as _bool_env
            from ..nn.functional.flash_attention import (
                _fused_cache_write_on, _mega_decode_on)
            with self._tp.activate():
                if _bool_env("PADDLE_TPU_FUSED_CACHE_WRITE", False) \
                        and not _fused_cache_write_on():
                    self.fused_knobs_disabled_tp.append(
                        "PADDLE_TPU_FUSED_CACHE_WRITE")
                if _bool_env("PADDLE_TPU_MEGA_DECODE", False) \
                        and not _mega_decode_on():
                    self.fused_knobs_disabled_tp.append(
                        "PADDLE_TPU_MEGA_DECODE")

        # speculative decoding (inference/speculative.py, ISSUE 13)
        from .speculative import (DraftModelProposer, NGramProposer,
                                  resolve_speculative)
        self._spec = resolve_speculative(speculative, spec_k,
                                         spec_ngram, draft_model)
        if self._spec is not None and do_sample:
            raise ValueError(
                "speculative decoding is greedy-only (acceptance is "
                "exact token equality against the target argmax); "
                "do_sample engines must run plain decode")
        # worst-case tokens a slot can overshoot its budget by in one
        # tick: tick_tokens plain, k+1 per verify dispatch — and the
        # verify block WRITES cache positions up to pos + k, so the
        # same bound sizes the cache-length check and page footprints
        self._overshoot = (max(self.tick_tokens, self._spec.k + 1)
                           if self._spec is not None
                           else self.tick_tokens)

        # paged KV cache config (module docstring, ISSUE 9)
        self.paged = bool(_env_int("PADDLE_TPU_SERVE_PAGED", 0)
                          if paged is None else paged)
        self.page_size = int(page_size if page_size is not None
                             else _env_int("PADDLE_TPU_KV_PAGE", 16))
        if self.page_size < 1:
            raise ValueError("page_size must be >= 1")
        # block-table width: enough logical pages to cover one
        # max_len-token request — the per-REQUEST cap is unchanged,
        # paging relaxes only the per-POOL sum
        self.pages_per_slot = _pages_needed(self.max_len,
                                            self.page_size)
        if num_pages is None:
            num_pages = _env_int("PADDLE_TPU_SERVE_NUM_PAGES", 0) or \
                self.slots * self.pages_per_slot
        self.num_pages = int(num_pages)
        self.prefix_cache = bool(prefix_cache)
        self._allocator = None
        self._trie = None
        self._pool_blocked = False    # last admission failed on pages
        self.prefix_hits = 0          # admissions with >= 1 trie page
        self.prefix_misses = 0
        self.prefix_tokens_saved = 0  # prompt tokens NOT re-prefilled
        self.prefill_tokens = 0       # suffix tokens actually prefilled

        was_training = model.training
        model.eval()
        self._params, self._buffers = raw_state(model)
        if was_training:
            model.train()
        if self.paged:
            if self.num_pages < self.pages_per_slot:
                raise ValueError(
                    f"num_pages {self.num_pages} cannot hold even one "
                    f"max_len request ({self.pages_per_slot} pages)")
            from .paging import PageAllocator, PrefixTrie
            self._allocator = PageAllocator(self.num_pages)
            self._trie = PrefixTrie(self._allocator)
            self._caches = model.new_paged_cache(
                self.num_pages, self.page_size, cache_dtype)
            self._block_tables = np.zeros(
                (self.slots, self.pages_per_slot), np.int32)
        else:
            self._caches = model.new_cache(self.slots, self.max_len,
                                           cache_dtype)
            self._block_tables = None
        if self._tp is not None:
            # land state in the Megatron layout BEFORE any program
            # traces: params/buffers by their sharding_axes annotations,
            # KV leaves head-sharded — pjit then propagates these input
            # shardings through every engine program (block tables stay
            # host numpy, replicated by jit's default for uncommitted
            # arguments, so paging.py never changes)
            self._params, self._buffers = self._tp.shard_state(
                model, self._params, self._buffers)
            self._caches = self._tp.shard_caches(self._caches)
        self._slots = [_Slot() for _ in range(self.slots)]
        self._queue: List[_Request] = []
        self._cv = _obs.make_condition("engine.cv")
        self._stop_flag = False
        self._broken: Optional[BaseException] = None

        # compiled-program accounting: the counters tick inside the
        # TRACED bodies, so they move only when XLA actually (re)traces
        # — tests assert they stay constant after warmup no matter how
        # many distinct (prompt-len, max-new-tokens) pairs are served
        self._trace_count = 0
        self._admit_progs = {}        # bucket -> jitted admit program
        self._decode_prog = None
        self._copy_prog = None        # paged: COW page-copy program
        self._verify_prog = None      # speculative: batched verify-k
        self._warmed = False          # warmup() completed
        # serializes warmup(): two threads tracing the same program
        # concurrently leak tracers into each other's jaxprs (found by
        # tools/race_hunt.py warmup_concurrent) — one compiles, the
        # rest wait and see AotPrograms already installed
        self._warmup_lock = _obs.make_lock("engine.warmup")
        self.ticks = 0
        self.admitted = 0
        self.completed = 0
        self.cancelled = 0            # requests cancelled (queued or
        #                               slot-retired mid-decode)
        # last tick's model efficiency (obs.efficiency): modeled HBM
        # bytes over measured tick wall time as a fraction of the
        # efficiency chip's bandwidth; 0.0 until a tick ran (or with
        # obs off — stats() stays shape-uniform either way)
        self.last_tick_model_eff = 0.0

        # speculative proposer + counters (always present so stats()
        # reads uniformly; the proposer exists only when configured)
        self._proposer = None
        self.spec_ticks = 0           # verify dispatches
        self.tokens_drafted = 0
        self.tokens_accepted = 0      # drafted tokens that matched
        self.tokens_rejected = 0
        self.spec_tokens_emitted = 0  # tokens consumed off verify ticks
        self.spec_slot_ticks = 0      # live (slot, verify-tick) pairs
        if self._spec is not None:
            if self._spec.kind == "draft":
                self._proposer = DraftModelProposer(
                    self._spec.draft_model, self.slots, self.max_len,
                    self._spec.k, cache_dtype="float32")
            else:
                self._proposer = NGramProposer(
                    self._spec.k, self._spec.ngram_max,
                    self._spec.ngram_min)

        # observability (paddle_tpu.obs): per-request phase spans into
        # the flight recorder + registry series on /metrics. The flag
        # is snapshotted ONCE so the disabled hot path is a single
        # attribute test per site — no spans, no histogram touches, no
        # allocations per tick (counter-asserted in tests/test_obs.py;
        # the enabled path's cost is not checked by any test).
        # modeled per-chip all-reduce bytes per tick / per verify
        # dispatch (inference/tp.py formula; 0 single-chip) — reported
        # on the tp_allreduce span and in stats()
        cfg = getattr(model, "cfg", None)
        if self._tp is not None and cfg is not None:
            self.tp_tick_comm_bytes = self._tp.modeled_tick_comm_bytes(
                cfg.num_layers, cfg.hidden_size, self.slots,
                self.tick_tokens)
            self.tp_verify_comm_bytes = (
                self._tp.modeled_tick_comm_bytes(
                    cfg.num_layers, cfg.hidden_size,
                    self.slots * (self._spec.k + 1), 1)
                if self._spec is not None else 0)
        else:
            self.tp_tick_comm_bytes = 0
            self.tp_verify_comm_bytes = 0

        self._obs = _obs.enabled()
        if self._obs:
            reg = _obs.metrics.registry
            self._g_mesh_devices = reg.gauge(
                "ptpu_engine_mesh_devices",
                "devices in this engine's mesh slice (1 = single-chip; "
                "the tier sum over replicas is total serving chips)")
            self._g_mesh_devices.set(self.tp)
            self._m_ticks = reg.counter(
                "ptpu_engine_ticks_total", "batched decode ticks")
            self._m_admits = reg.counter(
                "ptpu_engine_admits_total", "requests admitted to slots")
            self._m_retires = reg.counter(
                "ptpu_engine_retires_total", "requests retired")
            self._m_cancels = reg.counter(
                "ptpu_engine_cancels_total",
                "requests cancelled (queued or mid-decode; slot and "
                "pages reclaimed)")
            self._m_occupancy = reg.histogram(
                "ptpu_engine_batch_occupancy",
                "live slots per decode tick",
                buckets=(0, 1, 2, 4, 8, 16, 32, 64, 128))
            self._m_queue_wait = reg.histogram(
                "ptpu_engine_queue_wait_ms",
                "submit -> admission start")
            self._m_prefill = reg.histogram(
                "ptpu_engine_prefill_ms",
                "admission program incl. first-token sync")
            self._m_decode = reg.histogram(
                "ptpu_engine_decode_ms", "first token -> retirement")
            self._m_ttft = reg.histogram(
                "ptpu_engine_ttft_ms", "submit -> first token")
            self._m_e2e = reg.histogram(
                "ptpu_engine_e2e_ms", "submit -> retirement")
            if self.paged:
                self._g_pages_free = reg.gauge(
                    "ptpu_engine_pages_free", "KV pool pages free")
                self._g_pages_used = reg.gauge(
                    "ptpu_engine_pages_used", "KV pool pages in use")
                self._g_pages_free.set(self._allocator.free_pages)
                self._g_pages_used.set(self._allocator.used_pages)
                self._m_prefix_hits = reg.counter(
                    "ptpu_engine_prefix_hits_total",
                    "admissions reusing >=1 cached prefix page")
                self._m_prefix_misses = reg.counter(
                    "ptpu_engine_prefix_misses_total",
                    "admissions with no cached prefix page")
            if self._spec is not None:
                self._m_spec_ticks = reg.counter(
                    "ptpu_engine_spec_ticks_total",
                    "draft->verify tick dispatches")
                self._m_spec_drafted = reg.counter(
                    "ptpu_engine_spec_drafted_total",
                    "draft tokens proposed to verify")
                self._m_spec_accepted = reg.counter(
                    "ptpu_engine_spec_accepted_total",
                    "draft tokens accepted by the target")
                self._m_spec_rejected = reg.counter(
                    "ptpu_engine_spec_rejected_total",
                    "draft tokens rejected by the target")
                self._m_spec_per_tick = reg.histogram(
                    "ptpu_engine_spec_accepted_per_tick",
                    "tokens emitted per slot per verify tick "
                    "(accepted prefix + correction)",
                    buckets=tuple(range(0, self._spec.k + 2)))
            # live model efficiency (obs.efficiency — ISSUE 14): the
            # decode tick is bandwidth-bound (tpucost's anchor), so
            # each tick exports modeled HBM bytes over its measured
            # wall time as a fraction of the efficiency chip's
            # bandwidth. The modeled-bytes constants are the SAME
            # analytic bounds the tpucost anchors price (one formula,
            # no drift); they are computed once here so the per-tick
            # cost is one multiply + one gauge set.
            # PER-CHIP geometry: a tp-sharded engine streams 1/tp of
            # the (sharded) params and KV bytes per chip each tick —
            # same convention as the tpucost gpt_decode_tp anchor
            # (replicated norm scales/biases are noise at this scale)
            geom = {"tick_tokens": self.tick_tokens,
                    "param_bytes": _eff.tree_nbytes(
                        (self._params, self._buffers)) // self.tp,
                    "kv_cache_bytes":
                        _eff.tree_nbytes(self._caches) // self.tp}
            if self.paged:
                geom["kv_view_bytes"] = self._kv_view_nbytes() // self.tp
            self._tick_model_bytes = _eff.modeled_tick_bytes(
                "decode_paged" if self.paged else "decode", geom)
            self._verify_model_bytes = (
                _eff.modeled_tick_bytes("verify", geom)
                if self._spec is not None else 0)
            self._eff_chip = _eff.chip_spec()
            self._g_tick_eff = reg.gauge(
                _eff.TICK_EFF_GAUGE,
                "decode tick modeled-bytes/s over measured wall time, "
                "as a fraction of the efficiency chip's HBM bandwidth")

        self._thread = threading.Thread(target=self._loop, daemon=True,
                                        name="cb-engine")
        self._thread.start()

    # -- public API ------------------------------------------------------
    def submit(self, input_ids, max_new_tokens: int = 32,
               eos_token_id: Optional[int] = None,
               seed: int = 0, request_id: Optional[str] = None,
               progress_cb=None) -> Future:
        """Queue one request; returns a Future resolving to an int64
        [prompt_len + max_new_tokens] array, eos-padded after finish —
        the same shape/padding contract as one row of generate().
        ``request_id`` correlates this request's obs spans (the serving
        layer forwards the X-PTPU-Request-Id header here; absent, one
        is minted when tracing is on) and is the handle ``cancel``
        takes. ``progress_cb(new_tokens)`` — when given — is invoked
        from the engine thread with each newly emitted token block
        (the first token at admission, then per tick): the streaming
        side-channel the serving layer's incremental ``/generate`` and
        the router's token journal ride. It must be fast and must not
        raise; a raising callback is dropped, never the engine loop."""
        _resil.maybe_inject("serve_backend")   # dead-backend fault site
        prompt = np.asarray(input_ids).astype(np.int64).reshape(-1)
        P = prompt.shape[0]
        if P < 1:
            raise ValueError("empty prompt")
        if P > self.prefill_buckets[-1]:
            raise ValueError(
                f"prompt length {P} exceeds the largest prefill bucket "
                f"{self.prefill_buckets[-1]}")
        if max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")
        # worst-case decode overshoot is one tick past the budget (a
        # row is only retired at a tick boundary; a speculative tick
        # also WRITES cache rows up to k past the current position)
        worst = P + max_new_tokens + self._overshoot
        if worst > self.max_len:
            raise ValueError(
                f"prompt ({P}) + max_new_tokens ({max_new_tokens}) + "
                f"tick overshoot ({self._overshoot}) exceeds the "
                f"engine cache length {self.max_len}")
        # Paged engines need no extra static rejection here: worst <=
        # max_len (above) bounds a request at pages_per_slot pages, and
        # the constructor guarantees num_pages >= pages_per_slot — so
        # any request passing the view-length check CAN fit once enough
        # pages free up; transient shortage queues, and sheds as
        # cache_exhausted below when the queue is also full.
        req = _Request(prompt, int(max_new_tokens),
                       None if eos_token_id is None else int(eos_token_id),
                       int(seed))
        req.progress_cb = progress_cb
        if self._obs:
            req.rid = (str(request_id) if request_id
                       else uuid.uuid4().hex[:16])
            req.t_submit = time.perf_counter()
        elif request_id:
            req.rid = str(request_id)
        with self._cv:
            if self._broken is not None:
                raise RuntimeError("engine is broken") from self._broken
            if self._stop_flag:
                # after stop() no thread will ever drain the queue — a
                # silently-enqueued request would hang its caller forever
                raise RuntimeError("engine stopped")
            if len(self._queue) >= self.max_queue:
                if self.paged and self._pool_is_binding_locked():
                    # the queue backed up because admission is waiting
                    # on PAGES (a slot was free but the pool could not
                    # cover the head request) — shed with the truthful
                    # reason so operators size the pool, not the fleet
                    raise CacheExhausted(
                        len(self._queue), self.max_queue,
                        self._allocator.free_pages, self.num_pages)
                raise EngineOverloaded(len(self._queue), self.max_queue)
            self._queue.append(req)
            self._cv.notify()
        return req.future

    def cancel(self, request_id: Optional[str]) -> bool:
        """Cancel the in-flight request carrying ``request_id`` (the id
        given to submit). Returns True when a request was found. A
        QUEUED request resolves immediately (its future raises
        :class:`RequestCancelled`, zero tokens); an ADMITTED one is
        flagged and retired by the engine thread at the next tick
        boundary — the slot frees, its KV pages decref (leak-free),
        and the future raises :class:`RequestCancelled` with the
        partial result attached (``_ptpu_gen_info``: tokens_generated
        + partial_tokens). Idempotent: a second cancel of the same id
        returns False once the first resolved it."""
        if not request_id:
            return False
        rid = str(request_id)
        victim = None
        with self._cv:
            for i, req in enumerate(self._queue):
                if req.rid == rid:
                    victim = self._queue.pop(i)
                    break
            if victim is None:
                for s in self._slots:
                    if (s.req is not None and s.req.rid == rid
                            and not s.req.cancelled):
                        s.req.cancelled = True
                        self._cv.notify()
                        return True
                return False
            self.cancelled += 1
        # queued request: resolve outside the lock (future callbacks
        # must never run under the engine lock)
        victim.future._ptpu_gen_info = {"tokens_generated": 0,
                                        "partial_tokens": []}
        if self._obs:
            self._m_cancels.inc()
        if not victim.future.done():
            victim.future.set_exception(RequestCancelled(rid, 0))
        return True

    def _notify_progress(self, req: _Request, toks) -> None:
        """Deliver newly emitted tokens to the request's progress
        callback (streaming side-channel). Runs on the engine thread:
        a raising callback is dropped so it can never take the loop —
        and with it every other slot — down."""
        cb = req.progress_cb
        if cb is None:
            return
        try:
            cb([int(t) for t in toks])
        except Exception:   # noqa: BLE001 — a broken stream is the
            req.progress_cb = None   # caller's problem, not the loop's

    def _pool_is_binding_locked(self) -> bool:
        """Is the page pool (not slots / request rate) what is blocking
        the queue? True once an actual admission attempt failed on
        pages, or — to close the window before the engine thread gets
        to try — when a slot is free but the head request's worst-case
        pages exceed everything the pool could produce (free pages plus
        every trie-only page eviction could reclaim). Callers hold
        self._cv."""
        if self._pool_blocked:
            return True
        if not self._queue or not any(s.free for s in self._slots):
            return False
        head = self._queue[0]
        need = _pages_needed(head.prompt.shape[0] + head.max_new_tokens
                             + self._overshoot, self.page_size)
        return need > (self._allocator.free_pages
                       + self._trie.reclaimable())

    def generate(self, input_ids, max_new_tokens: int = 32,
                 eos_token_id: Optional[int] = None, seed: int = 0,
                 timeout: Optional[float] = None) -> np.ndarray:
        """Blocking convenience wrapper over submit()."""
        return self.submit(input_ids, max_new_tokens, eos_token_id,
                           seed).result(timeout)

    def _kv_view_nbytes(self) -> int:
        """Bytes of the gathered [N, pages_per_slot * page_size] KV
        view one PAGED micro-step materializes (all layers, k + v) —
        the geometry input the paged analytic HBM bound prices
        alongside the pool itself (compilation/sites.py exports the
        same number on the gpt_decode_paged registry geometry)."""
        total = 0
        if isinstance(self._caches, tuple):
            # scan-stacked (k_stack, v_stack): leaves carry a leading
            # layer axis, pages live on axis 1 — every layer gathers
            # its own view
            for half in self._caches:
                for leaf in half.values():
                    L, NP = leaf.shape[0], leaf.shape[1]
                    per_page = _eff.tree_nbytes(leaf) // (L * NP)
                    total += (per_page * self.pages_per_slot
                              * self.slots * L)
            return total
        for kc, vc in self._caches:
            for half in (kc, vc):
                for leaf in half.values():
                    per_page = _eff.tree_nbytes(leaf) // leaf.shape[0]
                    total += per_page * self.pages_per_slot * self.slots
        return total

    def stats(self) -> dict:
        with self._cv:
            active = sum(1 for s in self._slots if not s.free)
            queued = len(self._queue)
            cancelled = self.cancelled
        out = {"slots": self.slots, "active": active,
               "free": self.slots - active, "queued": queued,
               "max_queue": self.max_queue, "ticks": self.ticks,
               "admitted": self.admitted, "completed": self.completed,
               "cancelled": cancelled,
               "compiled_programs": self.compiled_program_count,
               "tick_tokens": self.tick_tokens,
               "prefill_buckets": list(self.prefill_buckets),
               "max_len": self.max_len,
               "cache_dtype": self.cache_dtype,
               "paged": self.paged,
               "speculative": (self._spec.kind if self._spec else None),
               # tensor-parallel slice geometry (ISSUE 20): tp == 1 is
               # the single-chip engine; fused_knobs_disabled_tp lists
               # env-enabled Pallas knobs forced off under the sharded
               # mesh (the loud fallback's machine-readable half)
               "tp": self.tp,
               "mesh_devices": self.tp,
               "fused_knobs_disabled_tp":
                   list(self.fused_knobs_disabled_tp),
               # obs.efficiency: last tick's modeled-bytes/s as a
               # fraction of the efficiency chip's HBM bandwidth
               # (0.0 before the first tick or with obs disabled)
               "tick_model_eff": round(self.last_tick_model_eff, 6)}
        if self._tp is not None:
            out["mesh"] = self._tp.describe()
            out["tp_comm_precision"] = (self._tp.comm_precision
                                        or "fp32")
            out["tp_tick_comm_bytes"] = self.tp_tick_comm_bytes
        if self._spec is not None:
            drafted = self.tokens_drafted
            out.update({
                "spec_k": self._spec.k,
                "spec_ticks": self.spec_ticks,
                "tokens_drafted": drafted,
                "tokens_accepted": self.tokens_accepted,
                "tokens_rejected": self.tokens_rejected,
                "acceptance_rate": round(
                    self.tokens_accepted / drafted, 4) if drafted
                else 0.0,
                # tokens emitted per SLOT per verify forward — the
                # multi-token-tick number (1.0 = no better than the
                # plain one-token-per-forward regime)
                "accepted_tokens_per_tick": round(
                    self.spec_tokens_emitted / self.spec_slot_ticks, 4)
                if self.spec_slot_ticks else 0.0,
            })
        if self.paged:
            free_p = self._allocator.free_pages
            used_p = self._allocator.used_pages
            lookups = self.prefix_hits + self.prefix_misses
            out.update({
                "page_size": self.page_size,
                "pages_total": self.num_pages,
                "pages_free": free_p,
                "pages_used": used_p,
                "pages_cached_prefix": self._trie.pages_cached,
                "page_utilization": round(used_p / self.num_pages, 4),
                "prefix_hits": self.prefix_hits,
                "prefix_misses": self.prefix_misses,
                "prefix_hit_rate": round(self.prefix_hits / lookups, 4)
                if lookups else 0.0,
                "prefix_tokens_saved": self.prefix_tokens_saved,
                "prefill_tokens": self.prefill_tokens,
            })
            # cross-process prefix identity for the router's affinity
            # scoring (ISSUE 16): chained crc32 per cached trie node,
            # bounded. The walk races the engine loop's inserts by
            # design — a torn read only costs one poll's freshness,
            # never correctness (hashes are compared, not dereferenced)
            try:
                out["prefix_fingerprints"] = self._trie.fingerprints()
            except RuntimeError:
                out["prefix_fingerprints"] = []
        return out

    @property
    def compiled_program_count(self) -> int:
        """How many times XLA traced an engine program — constant after
        warmup is the no-recompile serving guarantee. Includes the
        draft proposer's programs (a re-tracing draft would pay the
        same per-request compile tax as a re-tracing target)."""
        return self._trace_count + (
            self._proposer._trace_count
            if getattr(self._proposer, "kind", None) == "draft" else 0)

    @property
    def warm(self) -> bool:
        """True once the batched decode program is actually COMPILED —
        either warmup() finished (compiled or loaded from the
        executable store) or the first lazy tick completed. The raw jit
        wrapper existing is not enough: readiness claimed mid-compile
        would stall the first routed request, the exact lie the
        serving layer's warming->ready /healthz transition exists to
        prevent."""
        return self._warmed or self.ticks > 0

    def _tp_scope(self):
        """The trace/dispatch scope for this engine's programs: under
        tp > 1 it thread-locally activates the slice mesh (so
        mp_layers' constraints and the comm-precision routing take
        effect at trace time) — a no-op context single-chip. Wraps
        every site that may TRACE an engine program (warmup and the
        lazy first call of each dispatch path)."""
        return (self._tp.activate() if self._tp is not None
                else contextlib.nullcontext())

    # -- AOT warmup ------------------------------------------------------
    def _static_key(self) -> str:
        """Trace-time constants of this engine's programs that never
        appear in an argument aval — part of the executable-store key
        (two engines over the same weights but different sampling
        config must not collide)."""
        paged = ((self.page_size, self.num_pages, self.pages_per_slot)
                 if self.paged else None)
        spec = ((self._spec.kind, self._spec.k)
                if self._spec is not None else None)
        # kernel-fusion knobs are trace-time constants too: a cached
        # executable traced with the unfused chain must not be reused
        # when the fused kernels are toggled on (ISSUE 19)
        from ..nn.functional.flash_attention import (_fused_cache_write_on,
                                                     _mega_decode_on)
        # evaluated under the engine's mesh scope: a tp engine's knobs
        # read as OFF (the loud TP fallback), so its cache key matches
        # what its traces actually contain — a single-chip fused
        # executable can never be loaded for the sharded programs
        with self._tp_scope():
            fusion = (_fused_cache_write_on(), _mega_decode_on())
        tp_key = ((self.tp, self._tp.comm_precision or "fp32")
                  if self._tp is not None else None)
        return repr((type(self.model).__name__, self._sampling,
                     self.tick_tokens, self.max_len, self.cache_dtype,
                     paged, spec, fusion, tp_key))

    def _decode_example_args(self) -> tuple:
        N = self.slots
        if self.paged:
            return (self._params, self._buffers, self._caches,
                    np.zeros((N, self.pages_per_slot), np.int32),
                    np.zeros(N, np.int32), np.zeros(N, np.int32),
                    np.ones(N, bool), np.full(N, -1, np.int32),
                    np.zeros((N, 2), np.uint32))
        return (self._params, self._buffers, self._caches,
                np.zeros(N, np.int32), np.zeros(N, np.int32),
                np.ones(N, bool), np.full(N, -1, np.int32),
                np.zeros((N, 2), np.uint32))

    def _admit_example_args(self, bucket: int) -> tuple:
        if self.paged:
            return (self._params, self._buffers,
                    np.zeros((1, bucket), np.int64), np.int32(0),
                    np.int32(0), np.int32(bucket),
                    np.zeros(2, np.uint32), self._caches,
                    np.zeros((1, self.pages_per_slot), np.int32))
        return (self._params, self._buffers,
                np.zeros((1, bucket), np.int64), np.int32(0),
                np.zeros(2, np.uint32), self._caches, np.int32(0))

    def _copy_example_args(self) -> tuple:
        return (self._caches, np.int32(0), np.int32(0))

    def _verify_example_args(self) -> tuple:
        N, K = self.slots, self._spec.k
        head = (self._params, self._buffers, self._caches)
        if self.paged:
            head += (np.zeros((N, self.pages_per_slot), np.int32),)
        return head + (np.zeros(N, np.int32), np.zeros(N, np.int32),
                       np.ones(N, bool), np.zeros((N, K), np.int32),
                       np.zeros(N, np.int32))

    def warmup(self, buckets: Optional[tuple] = None, store=None) -> list:
        """Compile-or-load THIS engine's programs ahead of traffic: the
        batched decode tick plus one admission program per prefill
        bucket, through the persistent executable store
        (paddle_tpu.compilation) — a store-warm fresh process reaches
        its first token without XLA compiling anything. Also primes the
        tiny eager helper ops the admission path runs per request
        (PRNGKey construction). Returns the compile-log records."""
        from ..compilation import log as _clog
        from ..compilation import prime_helper_ops
        from ..compilation.store import AotProgram, aot_compile
        prime_helper_ops()
        static = self._static_key()
        with self._warmup_lock:
            return self._warmup_locked(buckets, store, static,
                                       AotProgram, aot_compile, _clog)

    def _warmup_locked(self, buckets, store, static, AotProgram,
                       aot_compile, _clog) -> list:
        recs = []
        # every TARGET program traces inside the engine's mesh scope
        # (sharded constraints + comm-precision routing are trace-time);
        # the draft proposer warms OUTSIDE it below — the draft stays a
        # single-device replicated model on purpose (its k-token
        # proposals are checked by the sharded verify, never trusted)
        with self._tp_scope():
            if not isinstance(self._decode_prog, AotProgram):
                rec: dict = {"site": "engine_decode"}
                self._decode_prog = aot_compile(
                    "engine_decode", self._get_decode_prog(),
                    self._decode_example_args(), store=store,
                    log_record=rec, static_key=static)
                recs.append(_clog.record(rec))
            for bucket in (buckets if buckets is not None
                           else self.prefill_buckets):
                bucket = self._bucket_for(int(bucket))
                if isinstance(self._admit_progs.get(bucket), AotProgram):
                    continue
                rec = {"site": f"engine_admit_b{bucket}"}
                self._admit_progs[bucket] = aot_compile(
                    f"engine_admit_b{bucket}",
                    self._get_admit_prog(bucket),
                    self._admit_example_args(bucket), store=store,
                    log_record=rec, static_key=static)
                recs.append(_clog.record(rec))
            if self.paged and not isinstance(self._copy_prog,
                                             AotProgram):
                rec = {"site": "engine_copy_page"}
                self._copy_prog = aot_compile(
                    "engine_copy_page", self._get_copy_page_prog(),
                    self._copy_example_args(), store=store,
                    log_record=rec, static_key=static)
                recs.append(_clog.record(rec))
            if self._spec is not None and not isinstance(
                    self._verify_prog, AotProgram):
                rec = {"site": "engine_verify"}
                self._verify_prog = aot_compile(
                    "engine_verify", self._get_verify_prog(),
                    self._verify_example_args(), store=store,
                    log_record=rec, static_key=static)
                recs.append(_clog.record(rec))
        if self._spec is not None and self._spec.kind == "draft":
            recs.extend(self._proposer.warmup(
                self.prefill_buckets, store=store, static_key=static))
        self._warmed = True
        return recs

    def stop(self):
        with self._cv:
            self._stop_flag = True
            self._cv.notify()
        self._thread.join(timeout=30)
        self._fail_all(RuntimeError("engine stopped"))

    def __enter__(self):
        return self

    def __exit__(self, *a):
        self.stop()
        return False

    # -- compiled programs ----------------------------------------------
    def _bucket_for(self, P: int) -> int:
        for b in self.prefill_buckets:
            if P <= b:
                return b
        raise ValueError(f"prompt length {P} exceeds largest bucket")

    def _get_admit_prog(self, bucket: int):
        prog = self._admit_progs.get(bucket)
        if prog is not None:
            return prog
        if self.paged:
            return self._get_paged_admit_prog(bucket)
        model, engine = self.model, self
        do_sample, temperature, top_k, top_p = self._sampling

        def admit(params, buffers, ids, last_idx, key, caches, slot):
            engine._trace_count += 1      # fires at trace time only
            # fresh zeroed cache built INSIDE the program: inserting its
            # full row range below is what resets a retired slot's stale
            # rows (incl. int8 scales) before re-admission
            temp = model.new_cache(1, engine.max_len, engine.cache_dtype)
            (logits, temp), _ = functional_call(
                model, params, buffers, ids, temp, jnp.int32(0),
                training=False)
            last = lax.dynamic_index_in_dim(logits, last_idx, axis=1,
                                            keepdims=False)   # [1, V]
            tok0 = _select_token(last, key, do_sample, temperature,
                                 top_k, top_p)

            def insert(slot_leaf, temp_leaf):
                # batch axis = the one where the N-slot leaf and the
                # batch-1 temp leaf disagree (works for unrolled
                # [B, L, ...] and scanned [layers, B, L, ...] layouts)
                ax = next(i for i, (a, c) in enumerate(
                    zip(slot_leaf.shape, temp_leaf.shape)) if a != c)
                start = [0] * slot_leaf.ndim
                start[ax] = slot
                return lax.dynamic_update_slice(
                    slot_leaf, temp_leaf.astype(slot_leaf.dtype),
                    tuple(start))

            caches = jax.tree_util.tree_map(insert, caches, temp)
            return tok0[0].astype(jnp.int32), caches

        prog = jax.jit(admit, donate_argnums=(5,))
        self._admit_progs[bucket] = prog
        return prog

    def _get_paged_admit_prog(self, bucket: int):
        """ONE jitted program per suffix bucket: prefill the request's
        un-cached suffix (tokens [M, M+wlen), right-padded to `bucket`)
        straight INTO its block-table pages. The suffix attends over
        the slot's gathered pages — shared prefix pages included, which
        is exactly why matched prefixes never re-prefill — and the
        write mask (wlen) keeps bucket padding out of the pool. M,
        wlen, last_idx and the table are traced values: prompt-length
        drift, prefix-hit depth and page placement never retrace."""
        model, engine = self.model, self
        do_sample, temperature, top_k, top_p = self._sampling

        def admit(params, buffers, ids, last_idx, m_pos, wlen, key,
                  caches, bt_row):
            engine._trace_count += 1      # fires at trace time only
            cm = _attach_page_meta(caches, bt=bt_row, wlen=wlen)
            (logits, cm), _ = functional_call(
                model, params, buffers, ids, cm, m_pos, training=False)
            caches = _strip_page_meta(cm)
            last = lax.dynamic_index_in_dim(logits, last_idx, axis=1,
                                            keepdims=False)   # [1, V]
            tok0 = _select_token(last, key, do_sample, temperature,
                                 top_k, top_p)
            return tok0[0].astype(jnp.int32), caches

        prog = jax.jit(admit, donate_argnums=(7,))
        self._admit_progs[bucket] = prog
        return prog

    def _get_copy_page_prog(self):
        """Copy-on-write: duplicate one physical page (every layer's
        k/v pool leaves, int8 scales included) into a freshly allocated
        page — the only write path that may target content shared with
        other requests, and it writes to the COPY. Gather + one-hot
        select, scatter-free like everything else."""
        if self._copy_prog is not None:
            return self._copy_prog
        engine = self
        # trace-time constant: scan-stacked pools put the page axis at
        # 1 (behind the layer axis), unrolled pools at 0
        stacked = isinstance(self._caches, tuple)

        def copy_page(caches, src, dst):
            engine._trace_count += 1      # fires at trace time only

            def cp(leaf):
                ax = 1 if stacked else 0
                row = jnp.take(leaf, src[None], axis=ax)  # page row
                hit = jnp.arange(leaf.shape[ax]) == dst
                shape = [1] * leaf.ndim
                shape[ax] = -1
                return jnp.where(hit.reshape(shape), row, leaf)

            return jax.tree_util.tree_map(cp, caches)

        self._copy_prog = jax.jit(copy_page, donate_argnums=(0,))
        return self._copy_prog

    def _get_decode_prog(self):
        if self._decode_prog is not None:
            return self._decode_prog
        if self.paged:
            return self._get_paged_decode_prog()
        model, engine = self.model, self
        do_sample, temperature, top_k, top_p = self._sampling
        T = self.tick_tokens

        def decode_tick(params, buffers, caches, tok, pos, live,
                        eos_ids, keys):
            engine._trace_count += 1      # fires at trace time only

            def body(carry, _):
                tok, caches, pos, live = carry
                (logits, caches), _ = functional_call(
                    model, params, buffers, tok[:, None], caches, pos,
                    training=False)
                last = logits[:, -1, :]
                if do_sample:
                    subs = jax.vmap(jax.random.fold_in)(keys, pos)
                    nxt = jax.vmap(
                        lambda lg, k: _select_token(
                            lg[None], k, True, temperature, top_k,
                            top_p)[0])(last, subs)
                else:
                    nxt = jnp.argmax(last, axis=-1)
                nxt = jnp.where(live, nxt.astype(jnp.int32),
                                jnp.int32(0))
                new_live = live & (nxt != eos_ids)
                pos = pos + live.astype(jnp.int32)
                tok = jnp.where(live, nxt, tok)
                return (tok, caches, pos, new_live), nxt

            (tok, caches, pos, live), toks = lax.scan(
                body, (tok, caches, pos, live), None, length=T)
            return toks.T, caches    # toks: [N, T]

        self._decode_prog = jax.jit(decode_tick, donate_argnums=(2,))
        return self._decode_prog

    def _get_paged_decode_prog(self):
        """The paged batched decode tick: identical token semantics to
        the slot-cache tick (same scan, same masks, same sampling) —
        the only difference is that each micro-step's cached_attention
        GATHERS the slot's pages through the block table and one-hot
        writes into the slot's current page, write-gated on the live
        mask (a dead slot's table may point at pages since reallocated
        to another request). Block tables ride as a [N, pages_per_slot]
        int32 argument, so page placement drift never retraces."""
        model, engine = self.model, self
        do_sample, temperature, top_k, top_p = self._sampling
        T = self.tick_tokens

        def decode_tick(params, buffers, caches, bt, tok, pos, live,
                        eos_ids, keys):
            engine._trace_count += 1      # fires at trace time only

            def body(carry, _):
                tok, caches, pos, live = carry
                cm = _attach_page_meta(caches, bt=bt, live=live)
                (logits, cm), _ = functional_call(
                    model, params, buffers, tok[:, None], cm, pos,
                    training=False)
                caches = _strip_page_meta(cm)
                last = logits[:, -1, :]
                if do_sample:
                    subs = jax.vmap(jax.random.fold_in)(keys, pos)
                    nxt = jax.vmap(
                        lambda lg, k: _select_token(
                            lg[None], k, True, temperature, top_k,
                            top_p)[0])(last, subs)
                else:
                    nxt = jnp.argmax(last, axis=-1)
                nxt = jnp.where(live, nxt.astype(jnp.int32),
                                jnp.int32(0))
                new_live = live & (nxt != eos_ids)
                pos = pos + live.astype(jnp.int32)
                tok = jnp.where(live, nxt, tok)
                return (tok, caches, pos, new_live), nxt

            (tok, caches, pos, live), toks = lax.scan(
                body, (tok, caches, pos, live), None, length=T)
            return toks.T, caches    # toks: [N, T]

        self._decode_prog = jax.jit(decode_tick, donate_argnums=(2,))
        return self._decode_prog

    def _get_verify_prog(self):
        """The batched verify-k program (speculative.py builds it; the
        trace hook is this engine's recompile counter, same contract as
        every other engine program)."""
        if self._verify_prog is not None:
            return self._verify_prog
        from .speculative import make_verify_program
        engine = self

        def hook():
            engine._trace_count += 1      # fires at trace time only

        self._verify_prog = make_verify_program(
            self.model, self._spec.k, self.paged, trace_hook=hook)
        return self._verify_prog

    # -- engine loop -----------------------------------------------------
    def _loop(self):
        while True:
            with self._cv:
                while (not self._stop_flag and not self._queue
                       and all(s.free for s in self._slots)):
                    self._cv.wait(timeout=1.0)
                if self._stop_flag:
                    return
            try:
                self._sweep_cancelled()
                self._admit_ready()
                if any(not s.free for s in self._slots):
                    self._tick()
                else:
                    with self._cv:
                        if self._queue and self._pool_blocked:
                            # nothing active to tick (and so nothing
                            # retiring to free pages) while the head
                            # request waits on the pool: only trie
                            # eviction can unblock, and _admit_paged
                            # already tried it — yield briefly instead
                            # of spinning the admission path hot
                            self._cv.wait(timeout=0.05)
            except BaseException as e:   # noqa: BLE001 — fail loudly
                with self._cv:
                    self._broken = e
                self._fail_all(e)
                return

    def _sweep_cancelled(self):
        """Retire every slot whose request was cancel()led since the
        last tick boundary — the slot frees and (paged) its pages
        decref before the next admission pass can want them."""
        with self._cv:
            idxs = [i for i, s in enumerate(self._slots)
                    if s.req is not None and s.req.cancelled]
        for i in idxs:
            self._retire(i)

    def _fail_all(self, exc: BaseException):
        with self._cv:
            pending = [(req, []) for req in self._queue]
            self._queue.clear()
            actives = [s for s in self._slots if not s.free]
            for s in actives:
                req, s.req = s.req, None
                s.alive = False
                pending.append((req, list(s.emitted)))
        for req, emitted in pending:
            if req is None or req.future.done():
                continue
            # surface the partial result on the error path too: the
            # router's journal reconciles against this engine truth
            # instead of silently losing whatever was generated
            req.future._ptpu_gen_info = {
                "tokens_generated": len(emitted),
                "partial_tokens": [int(t) for t in emitted]}
            req.future.set_exception(exc)

    def _admit_ready(self):
        while True:
            with self._cv:
                slot_idx = next((i for i, s in enumerate(self._slots)
                                 if s.free), None)
                if slot_idx is None or not self._queue:
                    return
                req = self._queue.pop(0)
            if not self._admit(req, slot_idx):
                # paged pool could not cover the head request right
                # now: keep FIFO order (put it back at the front) and
                # stop admitting — a retire or eviction re-opens the
                # path; admitting AROUND the head would starve large
                # requests forever under short-request pressure
                with self._cv:
                    self._queue.insert(0, req)
                return

    def _admit(self, req: _Request, b: int) -> bool:
        """Admit one request into slot ``b``; False when the paged pool
        cannot cover it right now (caller re-queues, nothing changed)."""
        P = req.prompt.shape[0]
        key = np.asarray(jax.random.PRNGKey(req.seed), np.uint32)
        t_adm = time.perf_counter() if self._obs else 0.0
        if self.paged:
            res = self._admit_paged(req, b, key)
            if res is None:
                return False
            tok0, bucket = res
        else:
            bucket = self._bucket_for(P)
            ids = np.zeros((1, bucket), np.int64)
            ids[0, :P] = req.prompt
            prog = self._get_admit_prog(bucket)
            with self._tp_scope():     # lazy path may trace here
                tok0_dev, self._caches = prog(
                    self._params, self._buffers, ids, np.int32(P - 1),
                    key, self._caches, np.int32(b))
            tok0 = int(tok0_dev)       # first-token host sync
            self.prefill_tokens += P
        if getattr(self._proposer, "kind", None) == "draft":
            # prefill the draft model's own cache row for this slot —
            # the prompt is the only context the draft ever needs ahead
            # of time (each tick's [prev, tok] sync block covers the
            # rest, speculative.py module docstring)
            self._proposer.admit(b, req.prompt, self._bucket_for(P))
        slot = self._slots[b]
        slot.req = req
        slot.pos = P
        slot.tok = tok0
        slot.key = key
        slot.emitted = [tok0]
        slot.remaining = req.max_new_tokens - 1
        slot.alive = (req.eos_token_id is None
                      or tok0 != req.eos_token_id)
        self.admitted += 1
        self._notify_progress(req, [tok0])
        if self._obs:
            # the request's contiguous phase timeline: queue-wait
            # (submit -> admission), prefill (admission program + the
            # first-token sync), then decode (below, -> retirement);
            # their sum is the engine-side end-to-end latency
            now = time.perf_counter()
            slot.t_dec0 = now
            self._m_admits.inc()
            self._m_queue_wait.observe((t_adm - req.t_submit) * 1e3)
            self._m_prefill.observe((now - t_adm) * 1e3)
            self._m_ttft.observe((now - req.t_submit) * 1e3)
            _obs.record_span("engine.queue_wait", req.t_submit, t_adm,
                             cat="engine", request_id=req.rid)
            # no separate TTFT span: its interval is exactly
            # queue_wait + prefill (a viewer derives it; the
            # histogram above carries the aggregate) — one less ring
            # event per request keeps the postmortem window long
            _obs.record_span("engine.prefill", t_adm, now, cat="engine",
                             request_id=req.rid, bucket=bucket,
                             prompt_len=P,
                             ttft_ms=round((now - req.t_submit) * 1e3,
                                           3))
        if slot.remaining <= 0 or not slot.alive:
            self._retire(b)
        return True

    def _admit_paged(self, req: _Request, b: int, key) -> Optional[tuple]:
        """Paged admission: prefix-trie match, page allocation (with
        LRU eviction under pressure), optional tail-page copy-on-write,
        then ONE suffix-prefill program that writes the un-cached
        tokens straight into the slot's pages. Returns (tok0, bucket)
        or None when the pool cannot cover the request yet (pool state
        is rolled back exactly)."""
        prompt, ps = req.prompt, self.page_size
        P = prompt.shape[0]
        n_complete = P // ps          # prompt pages shareable read-only
        page_keys = [tuple(int(t) for t in prompt[j * ps:(j + 1) * ps])
                     for j in range(n_complete)]
        matched = self._trie.match(page_keys) if self.prefix_cache \
            else []
        m = len(matched)
        cow_src = None
        if n_complete and m == n_complete and P % ps == 0:
            # every prompt page is cached: skip prefill entirely except
            # the LAST token (its logits seed decode) — copy-on-write
            # the tail page so that one recompute-write (and nothing
            # else, ever) lands in private memory
            cow_src = matched[-1]
            shared = matched[:-1]
            M = P - 1
        else:
            shared = matched
            M = m * ps
        total = _pages_needed(P + req.max_new_tokens + self._overshoot,
                              ps)
        # incref BEFORE any eviction below so matched pages are pinned
        self._allocator.incref(shared)
        need_priv = total - len(shared)
        priv = self._allocator.alloc(need_priv)
        if priv is None:
            self._trie.evict(need_priv - self._allocator.free_pages)
            priv = self._allocator.alloc(need_priv)
        if priv is None:
            self._allocator.decref(shared)   # exact rollback
            self._pool_blocked = True
            return None
        self._pool_blocked = False
        pages = list(shared) + priv          # logical page j = pages[j]
        bt_row = np.zeros(self.pages_per_slot, np.int32)
        bt_row[:len(pages)] = pages
        self._block_tables[b] = bt_row
        if cow_src is not None:
            with self._tp_scope():     # lazy path may trace here
                self._caches = self._get_copy_page_prog()(
                    self._caches, np.int32(cow_src),
                    np.int32(pages[n_complete - 1]))
        suffix = prompt[M:]
        S = suffix.shape[0]
        bucket = self._bucket_for(S)
        ids = np.zeros((1, bucket), np.int64)
        ids[0, :S] = suffix
        prog = self._get_admit_prog(bucket)
        with self._tp_scope():         # lazy path may trace here
            tok0_dev, self._caches = prog(
                self._params, self._buffers, ids, np.int32(S - 1),
                np.int32(M), np.int32(S), key, self._caches,
                bt_row[None])
        tok0 = int(tok0_dev)       # first-token host sync
        self._slots[b].pages = pages
        if self.prefix_cache:
            # freshly computed complete pages become shareable; keys
            # already cached are untouched (the COW copy never enters)
            self._trie.insert(page_keys, pages[:n_complete])
        if m:
            self.prefix_hits += 1
            self.prefix_tokens_saved += M
        else:
            self.prefix_misses += 1
        self.prefill_tokens += S
        if self._obs:
            (self._m_prefix_hits if m else self._m_prefix_misses).inc()
            self._g_pages_free.set(self._allocator.free_pages)
            self._g_pages_used.set(self._allocator.used_pages)
        return tok0, bucket

    def _tick(self):
        """One tick: plain decode, or — speculative — draft -> verify.
        The swap is per tick, not per engine: an n-gram engine whose
        contexts have nothing to match anywhere falls back to the plain
        tick (tick_tokens per dispatch) instead of paying a verify
        forward for one guaranteed token per slot."""
        # straggler fault site (latency injection, not death): wedges
        # THIS loop — the process stays alive, /healthz keeps
        # answering, only token progress stops. The router's hedged
        # decode is the recovery path under test.
        _resil.maybe_inject("replica_stall")
        if self._spec is None:
            self._tick_decode()
            return
        props, dlen = self._propose_all()
        if dlen.any():
            self._tick_verify(props, dlen)
        else:
            self._tick_decode()

    def _prev_token(self, s: "_Slot") -> int:
        """True token at index ``s.pos - 1`` (the draft sync block's
        first element). pos >= prompt_len >= 1 always, so it exists."""
        P = s.req.prompt.shape[0]
        j = s.pos - 1
        return int(s.req.prompt[j]) if j < P else s.emitted[j - P]

    def _propose_all(self):
        """(props [N, k] int32, dlen [N] int32) for every busy slot —
        ONE draft-model dispatch, or per-slot host n-gram lookups."""
        N, K = self.slots, self._spec.k
        props = np.zeros((N, K), np.int32)
        dlen = np.zeros(N, np.int32)
        if self._proposer.kind == "draft":
            prev = np.zeros(N, np.int32)
            tok = np.zeros(N, np.int32)
            pos = np.zeros(N, np.int32)
            busy = False
            for i, s in enumerate(self._slots):
                if s.free:
                    continue
                prev[i] = self._prev_token(s)
                tok[i] = s.tok
                pos[i] = s.pos
                dlen[i] = K
                busy = True
            if busy:
                props = self._proposer.propose(prev, tok, pos)
            return props, dlen
        for i, s in enumerate(self._slots):
            if s.free:
                continue
            ctx = np.concatenate([s.req.prompt,
                                  np.asarray(s.emitted, np.int64)])
            p, n = self._proposer.propose(ctx)
            props[i] = p
            dlen[i] = n
        return props, dlen

    def _tick_verify(self, props, dlen):
        """One draft->verify tick: ONE target forward scores all k+1
        positions for every slot; the host consumes the accepted prefix
        plus the correction token per row (1..k+1 tokens each — the
        multi-token tick). Every consumed token is the TARGET's argmax,
        so this path is bitwise token-identical to plain decode."""
        N = self.slots
        tok = np.zeros(N, np.int32)
        pos = np.zeros(N, np.int32)
        live = np.zeros(N, bool)
        n_live = 0
        for i, s in enumerate(self._slots):
            if s.free:
                continue
            tok[i] = s.tok
            pos[i] = s.pos
            if s.alive and s.remaining > 0:
                live[i] = True
                n_live += 1
        prog = self._get_verify_prog()
        t_tick = time.perf_counter() if self._obs else 0.0
        with self._tp_scope():         # lazy path may trace here
            if self.paged:
                toks_dev, acc_dev, self._caches = prog(
                    self._params, self._buffers, self._caches,
                    self._block_tables, tok, pos, live, props, dlen)
            else:
                toks_dev, acc_dev, self._caches = prog(
                    self._params, self._buffers, self._caches, tok, pos,
                    live, props, dlen)
        toks = np.asarray(toks_dev)       # the ONE host sync per tick
        n_acc = np.asarray(acc_dev)
        self.ticks += 1
        self.spec_ticks += 1
        if self._obs:
            now = time.perf_counter()
            self._m_ticks.inc()
            self._m_spec_ticks.inc()
            self._m_occupancy.observe(n_live)
            if now > t_tick:
                # the verify dispatch moves the single-pass k-token
                # bound's bytes, not tick_tokens passes
                self.last_tick_model_eff = _eff.model_bandwidth_eff(
                    self._verify_model_bytes, now - t_tick,
                    self._eff_chip)
                self._g_tick_eff.set(self.last_tick_model_eff)
            _obs.record_span("engine.tick", t_tick, now, cat="engine",
                             active=n_live, tick=self.ticks, spec=True)
            if self._tp is not None:
                # the per-block all-reduces run INSIDE the verify
                # program; this span brackets the dispatch that moved
                # them and carries the modeled per-chip wire bytes
                _obs.record_span(
                    "engine.tp_allreduce", t_tick, now, cat="engine",
                    tp=self.tp, tick=self.ticks,
                    modeled_comm_bytes=self.tp_verify_comm_bytes)
        for i, s in enumerate(self._slots):
            if s.free or not live[i]:
                continue
            drafted, accepted = int(dlen[i]), int(n_acc[i])
            self.tokens_drafted += drafted
            self.tokens_accepted += accepted
            self.tokens_rejected += drafted - accepted
            s.req.drafted += drafted
            s.req.accepted += accepted
            n = 0
            for t in range(accepted + 1):
                if s.remaining <= 0 or not s.alive:
                    break
                token = int(toks[i, t])
                s.emitted.append(token)
                s.remaining -= 1
                n += 1
                if (s.req.eos_token_id is not None
                        and token == s.req.eos_token_id):
                    s.alive = False
            # host mirror of the advance: rejected positions' in-cache
            # garbage sits above pos and is overwritten by the next
            # block before any query can attend it (no rollback)
            s.pos += n
            s.tok = s.emitted[-1]
            if n:
                self._notify_progress(s.req, s.emitted[-n:])
            self.spec_tokens_emitted += n
            self.spec_slot_ticks += 1
            if self._obs:
                self._m_spec_drafted.inc(drafted)
                self._m_spec_accepted.inc(accepted)
                self._m_spec_rejected.inc(drafted - accepted)
                self._m_spec_per_tick.observe(n)
            if s.remaining <= 0 or not s.alive:
                self._retire(i)

    def _tick_decode(self):
        N = self.slots
        tok = np.zeros(N, np.int32)
        pos = np.zeros(N, np.int32)
        live = np.zeros(N, bool)
        eos = np.full(N, -1, np.int32)
        keys = np.zeros((N, 2), np.uint32)
        n_live = 0
        for i, s in enumerate(self._slots):
            if s.free:
                continue
            tok[i] = s.tok
            pos[i] = s.pos
            if s.alive and s.remaining > 0:
                live[i] = True
                n_live += 1
            if s.req.eos_token_id is not None:
                eos[i] = s.req.eos_token_id
            keys[i] = s.key
        prog = self._get_decode_prog()
        t_tick = time.perf_counter() if self._obs else 0.0
        with self._tp_scope():         # lazy path may trace here
            if self.paged:
                toks_dev, self._caches = prog(
                    self._params, self._buffers, self._caches,
                    self._block_tables, tok, pos, live, eos, keys)
            else:
                toks_dev, self._caches = prog(
                    self._params, self._buffers, self._caches, tok,
                    pos, live, eos, keys)
        toks = np.asarray(toks_dev)       # the ONE host sync per tick
        self.ticks += 1
        if self._obs:
            now = time.perf_counter()
            self._m_ticks.inc()
            self._m_occupancy.observe(n_live)
            if now > t_tick:
                self.last_tick_model_eff = _eff.model_bandwidth_eff(
                    self._tick_model_bytes, now - t_tick,
                    self._eff_chip)
                self._g_tick_eff.set(self.last_tick_model_eff)
            _obs.record_span("engine.tick", t_tick, now, cat="engine",
                             active=n_live, tick=self.ticks)
            if self._tp is not None:
                # the per-block all-reduces run INSIDE the decode
                # program; this span brackets the dispatch that moved
                # them and carries the modeled per-chip wire bytes
                _obs.record_span(
                    "engine.tp_allreduce", t_tick, now, cat="engine",
                    tp=self.tp, tick=self.ticks,
                    modeled_comm_bytes=self.tp_tick_comm_bytes)
        for i, s in enumerate(self._slots):
            if s.free or not live[i]:
                continue
            n = 0
            for t in range(self.tick_tokens):
                if s.remaining <= 0 or not s.alive:
                    break
                token = int(toks[i, t])
                s.emitted.append(token)
                s.remaining -= 1
                n += 1
                if (s.req.eos_token_id is not None
                        and token == s.req.eos_token_id):
                    s.alive = False
            # host mirror of the in-program advance: continuing rows
            # consumed exactly tick_tokens live steps; retired rows'
            # in-program overshoot is irrelevant (slot is reset at the
            # next admission)
            s.pos += n
            s.tok = s.emitted[-1]
            if n:
                self._notify_progress(s.req, s.emitted[-n:])
            if s.remaining <= 0 or not s.alive:
                self._retire(i)

    def _retire(self, b: int):
        slot = self._slots[b]
        req, slot.req = slot.req, None
        slot.alive = False
        if self.paged and slot.pages:
            # drop this request's references; pages other requests (or
            # the prefix trie) still hold survive, the rest free. The
            # stale block-table row is harmless until reuse — dead
            # slots are write-masked and their reads causally masked —
            # but zero it anyway so state dumps read truthfully.
            self._allocator.decref(slot.pages)
            slot.pages = []
            self._block_tables[b] = 0
            self._pool_blocked = False    # freed pages: retry the head
            if self._obs:
                self._g_pages_free.set(self._allocator.free_pages)
                self._g_pages_used.set(self._allocator.used_pages)
        if self._obs:
            now = time.perf_counter()
            self._m_retires.inc()
            self._m_decode.observe((now - slot.t_dec0) * 1e3)
            self._m_e2e.observe((now - req.t_submit) * 1e3)
            _obs.record_span("engine.decode", slot.t_dec0, now,
                             cat="engine", request_id=req.rid,
                             tokens=len(slot.emitted))
        out = list(slot.emitted)
        # per-request generation accounting, readable off the future by
        # the serving layer AFTER result() resolves (set before
        # set_result, so publication orders correctly)
        info = {"tokens_generated": len(out)}
        if self._spec is not None:
            info["tokens_drafted"] = req.drafted
            info["tokens_accepted"] = req.accepted
        if req.cancelled:
            # cancelled mid-decode: the slot and pages above are
            # already reclaimed; publish the PARTIAL result on the
            # error path (no eos padding — these are exactly the
            # tokens generated) so the caller's journal reconciles
            # against engine truth instead of losing the work
            info["partial_tokens"] = [int(t) for t in out]
            req.future._ptpu_gen_info = info
            with self._cv:
                self.cancelled += 1
            if self._obs:
                self._m_cancels.inc()
            if not req.future.done():
                req.future.set_exception(
                    RequestCancelled(req.rid, len(out)))
            return
        req.future._ptpu_gen_info = info
        if len(out) < req.max_new_tokens:
            # finished early on eos: pad with eos — generate()'s contract
            out += [req.eos_token_id] * (req.max_new_tokens - len(out))
        result = np.concatenate(
            [req.prompt, np.asarray(out, np.int64)])
        self.completed += 1
        if not req.future.done():
            req.future.set_result(result)


# ---------------------------------------------------------------------------
# Config -> create_predictor surface (inference/predictor.py delegates
# here when Config.enable_continuous_batching was called)
# ---------------------------------------------------------------------------

class GenerationPredictor:
    """Predictor-shaped facade over a ContinuousBatchingEngine so
    serving code written against the Config -> create_predictor surface
    (reference: multi-stream AnalysisPredictor usage) drives the engine
    unchanged: one named int64 input, one named tokens output."""

    def __init__(self, engine: ContinuousBatchingEngine):
        self.engine = engine

    def generate(self, input_ids, max_new_tokens: int = 32, **kw):
        return self.engine.generate(input_ids, max_new_tokens, **kw)

    def get_input_names(self):
        return ["input_ids"]

    def get_output_names(self):
        return ["tokens"]

    def close(self):
        self.engine.stop()


def create_engine_predictor(config) -> GenerationPredictor:
    opts = dict(config._engine_opts)
    model = opts.pop("model", None)
    if model is None:
        raise ValueError(
            "Config.enable_continuous_batching needs a live model: the "
            "generation loop (cache-threaded forward + new_cache) cannot "
            "be reconstructed from an exported StableHLO program — pass "
            "enable_continuous_batching(model=the_causal_lm)")
    return GenerationPredictor(ContinuousBatchingEngine(model, **opts))
