"""Persistent executable store — compiled XLA programs as managed,
reloadable artifacts.

The jax persistent compilation cache (`jax_compilation_cache_dir`,
wired in `paddle_tpu/__init__.py`) caches at the backend-compile layer:
a fresh process still re-traces and re-lowers, and the cache is opaque
(no names, no inspection, no targeted eviction). This store operates
one level up, on whole serving/training programs: `serialize()` of the
jax AOT ``lowered.compile()`` executable, keyed by

    (store format, jax version, backend platform, program name,
     abstract-signature hash + computation hash, donation spec)

where the computation hash digests the lowered StableHLO itself — two
programs with identical argument signatures but different traced
computations (same-geometry models with different activations, a loss
with different baked label smoothing) can never alias each other's
executables, whatever their owners put in ``static_key``.

so ``tools/warmup.py --inspect`` can say "gpt_decode for THIS engine
geometry is prebuilt" and a brand-new process can reach first token
without invoking XLA's compiler at all (a deserialized executable fires
no compile event — asserted by tests/test_compilation.py::TestWarmup::
test_warmup_idempotent_second_pass_compiles_zero). Anything the
backend refuses to serialize (or a corrupt/stale entry) degrades to the
normal lazy-jit path, where the jax persistent cache — when enabled —
is the second line of defense.

Invalidation is explicit and total: any key component mismatch is a
miss, a corrupt file is deleted on first touch, and
``ExecutableStore.evict`` / the CLI remove entries by name or age.
CPU caveat (same as `paddle_tpu/__init__.py`): XLA:CPU artifacts are
machine-feature sensitive — the store directory must not be shared
across heterogeneous hosts.

Env knobs:
  PADDLE_TPU_EXEC_STORE      1|0 — enable the store (default 1)
  PADDLE_TPU_EXEC_STORE_DIR  directory (default
                             <checkout>/.cache/exec_store)
"""
from __future__ import annotations

import hashlib
import os
import pickle
import threading
import time
import warnings
from dataclasses import dataclass
from typing import Any, List, Optional, Tuple

from .. import _paths
from ..obs import locks as _locks

__all__ = ["ExecutableStore", "StoreEntry", "default_store",
           "AotProgram", "aot_compile"]

# v2: header and payload are separate pickle frames so inspection reads
# just the small header, never the serialized executable
FORMAT_VERSION = 2


def _jax_version() -> str:
    import jax
    return jax.__version__


def _backend_platform() -> str:
    import jax
    try:
        return jax.default_backend()
    except Exception:
        return "unknown"


@dataclass
class StoreEntry:
    name: str
    path: str
    size: int
    created: float
    jax_version: str
    backend: str
    signature_hash: str
    donation: Tuple[int, ...]


class ExecutableStore:
    """Directory of serialized executables, one file per
    (name, signature) key. Files are atomic-published (tmp+rename, the
    checkpoint.py idiom) so a killed warmup never leaves a torn entry.
    """

    def __init__(self, root: Optional[str] = None,
                 enabled: Optional[bool] = None):
        if root is None:
            root = os.environ.get(
                "PADDLE_TPU_EXEC_STORE_DIR",
                _paths.cache_path("exec_store"))
        self.root = root
        if enabled is None:
            from ..framework.env import bool_env
            enabled = bool_env("PADDLE_TPU_EXEC_STORE", True)
        self.enabled = enabled
        self._lock = _locks.make_lock("compilation.store")

    # -- keys -----------------------------------------------------------
    def _path(self, name: str, sig_hash: str) -> str:
        safe = "".join(c if c.isalnum() or c in "-_." else "_"
                       for c in name)
        return os.path.join(self.root, f"{safe}-{sig_hash}.pexec")

    def _header(self, name: str, sig_hash: str,
                donation: Tuple[int, ...]) -> dict:
        return {"format": FORMAT_VERSION,
                "jax_version": _jax_version(),
                "backend": _backend_platform(),
                "name": name,
                "signature_hash": sig_hash,
                "donation": tuple(donation),
                "created": time.time()}

    # -- io -------------------------------------------------------------
    def save(self, name: str, sig_hash: str, donation: Tuple[int, ...],
             compiled) -> Optional[str]:
        """Serialize ``compiled`` (a jax.stages.Compiled). Returns the
        entry path, or None when disabled or the backend refuses
        serialization (a loud-enough degrade: the caller records the
        program as uncacheable in the compile log)."""
        if not self.enabled:
            return None
        try:
            from jax.experimental.serialize_executable import (
                deserialize_and_load, serialize)
            payload, in_tree, out_tree = serialize(compiled)
            # verify the round trip BEFORE publishing: some executables
            # serialize but cannot relink (XLA:CPU multi-device pjit
            # raises "Symbols not found" at deserialize) — storing one
            # would make every future process pay a failed load + evict
            # + recompile instead of going straight to the fallback
            deserialize_and_load(payload, in_tree, out_tree)
            # two frames: a small header frame first, so entries()/
            # --inspect can read metadata without deserializing the
            # (potentially multi-MB) executable payload
            blob = (pickle.dumps(self._header(name, sig_hash, donation),
                                 protocol=pickle.HIGHEST_PROTOCOL)
                    + pickle.dumps((payload, in_tree, out_tree),
                                   protocol=pickle.HIGHEST_PROTOCOL))
        except Exception:
            return None
        path = self._path(name, sig_hash)
        try:
            os.makedirs(self.root, exist_ok=True)
            with self._lock:
                tmp = path + f".tmp{os.getpid()}"
                with open(tmp, "wb") as fh:
                    fh.write(blob)
                os.replace(tmp, path)
        except OSError:
            return None
        return path

    def load(self, name: str, sig_hash: str,
             donation: Tuple[int, ...]):
        """Deserialize the stored executable for this exact key, or
        None (any mismatch — format, jax version, backend, signature,
        donation — is a miss; corrupt entries are evicted on touch)."""
        if not self.enabled:
            return None
        path = self._path(name, sig_hash)
        want = self._header(name, sig_hash, donation)
        try:
            with open(path, "rb") as fh:
                header = pickle.load(fh)
                if not isinstance(header, dict):
                    raise ValueError("pre-v2 single-frame entry")
                for k in ("format", "jax_version", "backend", "name",
                          "signature_hash", "donation"):
                    if header.get(k) != want[k]:
                        return None      # stale, not corrupt: keep it
                payload, in_tree, out_tree = pickle.load(fh)
        except FileNotFoundError:
            return None
        except Exception:
            self._evict_path(path)     # torn/corrupt: self-heal
            return None
        try:
            from jax.experimental.serialize_executable import \
                deserialize_and_load
            return deserialize_and_load(payload, in_tree, out_tree)
        except Exception:
            # artifact predates a jaxlib/topology change the header
            # could not see — stale, not fatal
            self._evict_path(path)
            return None

    # -- inspection / eviction ------------------------------------------
    def entries(self) -> List[StoreEntry]:
        out: List[StoreEntry] = []
        try:
            files = sorted(os.listdir(self.root))
        except OSError:
            return out
        for fname in files:
            if not fname.endswith(".pexec"):
                continue
            path = os.path.join(self.root, fname)
            try:
                with open(path, "rb") as fh:
                    header = pickle.load(fh)   # header frame only
                if not isinstance(header, dict):
                    raise ValueError("pre-v2 single-frame entry")
                out.append(StoreEntry(
                    name=header["name"], path=path,
                    size=os.path.getsize(path),
                    created=header["created"],
                    jax_version=header["jax_version"],
                    backend=header["backend"],
                    signature_hash=header["signature_hash"],
                    donation=tuple(header["donation"])))
            except Exception:
                self._evict_path(path)
        return out

    def evict(self, names: Optional[List[str]] = None,
              stale_only: bool = False) -> int:
        """Remove entries by program name (None = all); with
        ``stale_only`` remove only entries whose jax version/backend no
        longer match this process. Returns the eviction count."""
        n = 0
        cur_jax, cur_backend = _jax_version(), _backend_platform()
        for e in self.entries():
            if names is not None and e.name not in names:
                continue
            if stale_only and (e.jax_version == cur_jax
                               and e.backend == cur_backend):
                continue
            n += self._evict_path(e.path)
        return n

    def _evict_path(self, path: str) -> int:
        try:
            os.remove(path)
            return 1
        except OSError:
            return 0


_default_store: Optional[ExecutableStore] = None
_default_lock = _locks.make_lock("compilation.store")


def default_store() -> ExecutableStore:
    global _default_store
    with _default_lock:
        if _default_store is None:
            _default_store = ExecutableStore()
        return _default_store


# ---------------------------------------------------------------------------
# AOT compile-or-load + the site-installable program wrapper
# ---------------------------------------------------------------------------

class AotProgram:
    """A compiled executable installed at a jit call site, with the
    original jit wrapper as fallback.

    A deserialized/AOT ``Compiled`` only accepts the exact signature it
    was built for — it raises TypeError instead of re-tracing. Program
    sites with genuinely fixed shapes (the engine's decode tick) could
    install the raw Compiled, but sites that may legally see drift (a
    trailing partial batch hitting TrainStep's per-step program) need
    the lazy wrapper behind it. The TypeError is raised by argument
    validation BEFORE execution, so donated inputs are untouched and
    the retry through the fallback is safe. After the first drift the
    site sticks to the fallback wrapper (its own jit cache now owns
    dispatch) instead of paying the raise-per-call.
    """

    __slots__ = ("compiled", "fallback", "_use_fallback")

    def __init__(self, compiled, fallback):
        self.compiled = compiled
        self.fallback = fallback
        self._use_fallback = False

    def __call__(self, *args):
        if not self._use_fallback:
            try:
                return self.compiled(*args)
            except TypeError:
                self._use_fallback = True
        return self.fallback(*args)

    def lower(self, *args, **kw):
        # analyzers (tpulint) lower the site object; delegate
        return self.fallback.lower(*args, **kw)


def _computation_hash(lowered) -> str:
    """Digest of the lowered StableHLO module text — the traced
    computation itself, trace-time constants included. Folded into the
    store key so an argument-signature collision (two different
    programs over identical avals) can never load the wrong
    executable; jax's own persistent cache keys the same way, which is
    also what makes this text stable across processes."""
    try:
        text = lowered.as_text()
    except Exception:
        return "nohlo"
    return hashlib.sha256(text.encode()).hexdigest()[:16]


def _virtual_cpu_mesh() -> bool:
    """True in a process on several XLA:CPU devices (the virtual mesh
    the tests and CPU tools run on). XLA:CPU compiles such a process's
    programs — single-device ones included — with pseudo-features the
    loader does not accept back (the cpu_aot_loader hazard
    paddle_tpu/__init__.py documents), and on the installed jax 0.9.0
    loading one does not crash, it HANGS: every test of
    tests/test_tp_engine.py whose engine matched a stored entry sat out
    its time limit, which alone kept the tier-1 run from reaching its
    end. There the process-wide DEFAULT store is bypassed — compile
    every time (a store passed explicitly is still honoured). A
    one-device CPU process (replica children) and every TPU process
    store and load as ever."""
    import jax
    return _backend_platform() == "cpu" and jax.device_count() > 1


def aot_compile(name: str, fn, args: tuple,
                store: Optional[ExecutableStore] = None,
                log_record: Optional[dict] = None,
                static_key: str = ""):
    """Compile-or-load ``fn`` for the signature of ``args``.

    Returns an :class:`AotProgram` (callable in place of ``fn``). The
    store is consulted first; a hit deserializes without entering jax's
    compile machinery at all. A miss traces+lowers+compiles through the
    jit wrapper's AOT path and publishes the executable back to the
    store. ``log_record`` (when given) is filled in place with timings
    and the source — the compile-log entry the caller is building.
    """
    from . import counters
    from .registry import donation_spec, signature_hash
    counters.install()
    # the process-wide default store is what the virtual CPU mesh
    # bypasses; a store the caller passed explicitly is honoured
    use_store = (store.enabled if store is not None
                 else not _virtual_cpu_mesh())
    store = store if store is not None else default_store()
    use_store = use_store and store.enabled
    rec = log_record if log_record is not None else {}
    sig = signature_hash(args, static_key)
    rec.setdefault("name", name)

    t0 = time.perf_counter()

    def _lower():
        # warmup lowering is not where donation hygiene is acted on
        # (tpulint audits it; the live site's own lazy path still
        # warns), so the scan-window's expected "donated buffers not
        # usable" message is noise here
        with warnings.catch_warnings():
            warnings.filterwarnings(
                "ignore", message="Some donated buffers were not usable")
            return fn.lower(*args)

    lowered = None
    donation: Tuple[int, ...] = ()
    if use_store:
        # donation is part of the key but needs args_info — one cheap
        # trace+lower (no XLA compile) recovers it; the big cost this
        # store kills is the COMPILE, not the trace
        lowered = _lower()
        donation = donation_spec(lowered)
        sig = f"{sig}-{_computation_hash(lowered)}"
        rec["signature"] = sig
        rec["trace_s"] = round(time.perf_counter() - t0, 4)
        compiled = store.load(name, sig, donation)
        if compiled is not None:
            rec["source"] = "store"
            rec["compile_s"] = 0.0
            rec["total_s"] = round(time.perf_counter() - t0, 4)
            return AotProgram(compiled, fn)
    if lowered is None:
        lowered = _lower()
        donation = donation_spec(lowered)
        sig = f"{sig}-{_computation_hash(lowered)}"
        rec["signature"] = sig
        rec["trace_s"] = round(time.perf_counter() - t0, 4)
    t1 = time.perf_counter()
    with counters.CompileTracker() as trk:
        compiled = lowered.compile()
    rec["compile_s"] = round(time.perf_counter() - t1, 4)
    rec["xla_compiles"] = trk.xla_compiles
    rec["persistent_cache_hits"] = trk.persistent_cache_hits
    saved = use_store and store.save(name, sig, donation, compiled)
    rec["source"] = "compiled" if saved else "compiled-unstored"
    rec["total_s"] = round(time.perf_counter() - t0, 4)
    return AotProgram(compiled, fn)
