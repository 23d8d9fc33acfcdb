"""Multi-host world formation (leaf module — no package imports).

One shared implementation of the JAX_* env contract
(JAX_COORDINATOR_ADDRESS / JAX_NUM_PROCESSES / JAX_PROCESS_ID, exported
by distributed.launch) consumed from two places: package import (must
run before anything touches the XLA backend) and init_parallel_env (the
strict fallback with an actionable error). SURVEY.md §5.8: this plays
the reference's ncclUniqueId-rendezvous role.
"""
from __future__ import annotations

import os
import warnings

_formed = False


def maybe_init_jax_distributed(strict: bool = False) -> bool:
    """Form the jax.distributed world if the env declares one.

    Returns True when the world is (already) formed. Non-strict callers
    get a RuntimeWarning on failure; strict callers get RuntimeError.
    """
    global _formed
    n = int(os.environ.get("JAX_NUM_PROCESSES", "1") or 1)
    if n <= 1 or _formed:
        return _formed

    def fail(msg, cause=None):
        if strict:
            raise RuntimeError(msg) from cause
        warnings.warn(msg, RuntimeWarning)
        return False

    coord = os.environ.get("JAX_COORDINATOR_ADDRESS")
    pid = os.environ.get("JAX_PROCESS_ID")
    if not coord or pid is None:
        return fail(
            f"multi-host world declared (JAX_NUM_PROCESSES={n}) but "
            "JAX_COORDINATOR_ADDRESS/JAX_PROCESS_ID are unset — use "
            "python -m paddle_tpu.distributed.launch, or export the "
            "full JAX_* contract")
    import jax
    try:
        jax.distributed.initialize(coordinator_address=coord,
                                   num_processes=n,
                                   process_id=int(pid))
    except (RuntimeError, ValueError) as e:
        # the backend may already be up — if the world is formed (user
        # called initialize themselves), that is success, not failure
        try:
            if jax.process_count() >= n:
                _formed = True
                return True
        except Exception:
            pass
        return fail(
            "jax.distributed.initialize() failed — it must run before "
            "any computation touches the XLA backend; import paddle_tpu "
            "(or call init_parallel_env) first thing in the trainer "
            f"(underlying error: {e})", e)
    _formed = True
    return True
