"""ProgramRegistry — the single registration point for named jitted
program sites.

Before this subsystem, three consumers each hand-maintained their own
list of "the real programs": `analysis/manifest.py` rebuilt them for
tpulint, the serving/training warm paths had none (first traffic paid
the compile), and benches re-derived them ad hoc. The registry is ONE
table of (name -> builder); tpulint's manifest, `compilation.warmup`
and `tools/warmup.py` all enumerate it, so a newly registered program
is lint-covered, warmable, and store-cacheable by default.

A builder is a zero-arg callable returning a :class:`BuildResult`:
the jitted program object (a ``jax.jit`` wrapper — the REAL site
object, so donation is audited/preserved), example call args whose
abstract signature IS the program's compile key, an optional cleanup
(undo global state the build touched, e.g. a mesh swap), and tags.
Builders import lazily and build tiny fixture configs — registration
itself costs nothing.

Signatures: ``abstract_signature(args)`` maps the example args to a
canonical (treedef, leaf shape/dtype list) string; ``signature_hash``
is its sha256 prefix. The executable store keys on it (plus jax
version/backend/donation), and the checked-in warmup manifest
(tools/warmup_manifest.json) pins it so signature drift is detected
before it silently invalidates every stored executable.
"""
from __future__ import annotations

import hashlib
import threading
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Tuple

__all__ = ["BuildResult", "RegisteredProgram", "register", "unregister",
           "get", "names", "build", "abstract_signature",
           "signature_hash", "donation_spec"]


@dataclass
class BuildResult:
    """What a registered builder returns.

    ``fn``: the jitted program object (supports ``.lower(*args)``).
    ``args``: example args; their abstract signature is the compile key.
    ``cleanup``: optional zero-arg callable undoing build side effects
    (run by every consumer in a finally).
    ``install``: optional callable(compiled) installing an AOT-compiled
    executable back into the live site (None for fixture builds — the
    value of warming those is priming the persistent caches).
    """
    fn: Any
    args: tuple
    cleanup: Optional[Callable[[], None]] = None
    install: Optional[Callable[[Any], None]] = None
    # trace-time constants not visible in the arg avals (see
    # signature_hash) — fixture builders with one fixed config leave ""
    static_key: str = ""
    # per-site batch/seq/byte geometry for the tpucost pass (FLOPs per
    # token, the decode-tick HBM anchor): builders fill what applies —
    # tokens_per_exec, batch, seq, param_bytes, kv_cache_bytes,
    # tick_tokens, ... (analysis/hlo_cost.py documents the consumers)
    geometry: dict = field(default_factory=dict)


@dataclass
class RegisteredProgram:
    name: str
    builder: Callable[[], BuildResult]
    tags: Tuple[str, ...] = ()
    description: str = ""
    # tpulint: compile (not just lower) so GSPMD-inserted collectives
    # are inventoried — mirrors manifest.ProgramSpec.compile_collectives
    compile_collectives: bool = False
    # multi-device programs can't warm on a single-device process
    min_devices: int = 1


_lock = threading.Lock()
_REGISTRY: "Dict[str, RegisteredProgram]" = {}


def register(name: str, builder: Callable[[], BuildResult], *,
             tags: Tuple[str, ...] = (), description: str = "",
             compile_collectives: bool = False, min_devices: int = 1,
             replace: bool = False) -> RegisteredProgram:
    """Register a named program site. Names are the stable identity the
    tpulint baseline and the executable store key on — never reuse one
    for a different program."""
    prog = RegisteredProgram(name, builder, tuple(tags), description,
                             compile_collectives, min_devices)
    with _lock:
        if name in _REGISTRY and not replace:
            raise ValueError(f"program {name!r} already registered "
                             "(pass replace=True to override)")
        _REGISTRY[name] = prog
    return prog


def unregister(name: str) -> None:
    with _lock:
        _REGISTRY.pop(name, None)


def get(name: str) -> RegisteredProgram:
    _ensure_default_sites()
    with _lock:
        try:
            return _REGISTRY[name]
        except KeyError:
            known = list(_REGISTRY)   # NOT names(): _lock is held
            raise KeyError(
                f"no registered program {name!r}; known: {known}") \
                from None


def names(tag: Optional[str] = None) -> List[str]:
    """Registered program names, insertion-ordered; filtered by tag."""
    _ensure_default_sites()
    with _lock:
        return [n for n, p in _REGISTRY.items()
                if tag is None or tag in p.tags]


def build(name: str) -> BuildResult:
    return get(name).builder()


def _ensure_default_sites() -> None:
    # sites.py registers the canonical programs on first use; importing
    # it here (not at module import) keeps registry.py dependency-free
    from . import sites  # noqa: F401


# ---------------------------------------------------------------------------
# abstract call signatures
# ---------------------------------------------------------------------------

def _leaf_spec(x) -> str:
    import numpy as np
    shape = tuple(getattr(x, "shape", np.shape(x)))
    dtype = getattr(x, "dtype", None)
    if dtype is None:
        dtype = np.asarray(x).dtype
    try:
        name = np.dtype(dtype).name
    except TypeError:
        name = str(dtype)   # jax extended dtypes (typed PRNG keys)
    return f"{name}[{','.join(map(str, shape))}]"


def abstract_signature(args: tuple) -> str:
    """Canonical string for the abstract call signature of ``args`` —
    the pytree structure plus every leaf's shape/dtype. This is the
    same notion of identity jax's jit cache keys on (minus weak types,
    which the registered sites avoid by passing typed np/jnp scalars)."""
    import jax
    leaves, treedef = jax.tree_util.tree_flatten(args)
    return str(treedef) + "|" + ";".join(_leaf_spec(x) for x in leaves)


def signature_hash(args: tuple, static_key: str = "") -> str:
    """Hash of the abstract call signature, plus ``static_key`` — the
    program's trace-time constants that do NOT appear in any argument
    aval (an engine's sampling temperature, a generate() program's
    baked eos/max_new_tokens, a TrainStep's accumulate cadence). Two
    programs with identical arg signatures but different baked config
    MUST NOT collide in the executable store; the owner of each site
    passes its config repr here."""
    return hashlib.sha256(
        (abstract_signature(args) + "||" + static_key)
        .encode()).hexdigest()[:16]


def donation_spec(lowered) -> Tuple[int, ...]:
    """Donated flat-argument indices of a ``jax.stages.Lowered`` (via
    ``args_info`` — the jit wrapper itself doesn't expose its
    donate_argnums). Part of the store key: the same HLO with different
    aliasing is a different executable."""
    import jax
    try:
        leaves = jax.tree_util.tree_leaves(lowered.args_info)
        return tuple(i for i, a in enumerate(leaves)
                     if getattr(a, "donated", False))
    except Exception:
        return ()
