"""The window's device time by the step program's own scopes, for the
readers of ``benchmark/layer_metrics`` that name a pass or a layer.

The table from HLO instruction to ``op_name`` path is the program's own
word: ``paddle_tpu.jit.last_step_program().op_scopes()``, the record a
trainer publishes of the step it compiled (it outlives the trainer, which
the drivers drop before the readers run); where the program keeps no such
record (an older one), the table a driver handed over in
``obs["op_scopes"]``; where neither, nothing to read. The join with the
window's per-instruction self times is ``benchmark/scopes.py``'s ``table``
(the one that makes ``PERF.md`` section 5's finer rows): made once a run
and kept in ``obs["step_scopes"]`` for the next reader.

This file only finds the table and takes shares; which scope means what
is the readers' business.
"""
from __future__ import annotations

PASSES = ("forward", "recompute", "backward", "update")
STALE = 0.01


def record():
    """The program's own record of the step it compiled, or ``None``
    (none published, or a program that keeps no such record)."""
    try:
        from paddle_tpu.jit import training
        return training.last_step_program()
    except (ImportError, AttributeError):
        return None


def table_of(obs: dict):
    """{instruction: path} of the step the window ran, or ``None``."""
    mine = record()
    if mine is not None:
        return mine.op_scopes()
    return obs.get("op_scopes") or None


def read(trace, obs: dict, say):
    """``benchmark/scopes.py``'s ``table`` of the traced window under the
    step's own table (``busy_s``, what every share here is of;
    ``by_scope``, whose ``by_pass`` counts an operation the table cannot
    name as forward and says how much that is; ``under``, the seconds
    under every name on a path, ``"name|pass"``), or ``None`` where
    there is no trace or no table, or the table is of another program
    than the one traced (more than `STALE` of the busy time on
    instructions it does not hold: a trainer that compiled after the
    timed one replaced the record)."""
    if "step_scopes" not in obs:
        obs["step_scopes"] = _read(trace, obs, say)
    return obs["step_scopes"]


def _read(trace, obs, say):
    table = table_of(obs) if trace is not None else None
    if not table:
        return None
    from benchmark import scopes
    got = scopes.table(trace, table)
    stale = got["by_scope"]["not_in_table_share"]
    if stale > STALE:
        say(f"step scopes: {100 * stale:.2f}% of busy time is on "
            "instructions the step's table does not hold: it is of "
            "another program than the one traced, nothing read")
        return None
    return got


def by_pass(got: dict) -> dict:
    """Seconds of each of `PASSES` in the window."""
    return {p: got["by_scope"]["by_pass"].get(p, {}).get("seconds", 0.0)
            for p in PASSES}


def under(got: dict, *names: str) -> dict:
    """Seconds by pass of the operations whose path runs through one of
    the scopes ``names`` (names that do not nest)."""
    return {p: sum(got["under"].get(f"{n}|{p}", 0.0) for n in names)
            for p in PASSES}


def _said(seconds_by_pass: dict) -> str:
    return ", ".join(f"{p} {seconds_by_pass[p]:.4f}" for p in PASSES)


def share(got: dict, say, what: str, mine: dict = None,
          seconds: float = None) -> float:
    """``seconds`` (by default all of ``mine``) as a percentage of the
    window's busy time, with one line through ``say``: ``what``'s seconds
    by pass where it has its own (``mine``), then the window's four
    passes, which sum to its busy seconds (an operation the table cannot
    name counts as forward)."""
    window = by_pass(got)
    line = f"{what}: " if mine is None else f"{what}, s: {_said(mine)}; "
    say(line + f"the window's passes, s: {_said(window)} = "
        f"{sum(window.values()):.4f} of {got['busy_s']:.4f} busy")
    if seconds is None:
        seconds = sum(mine.values())
    return 100.0 * seconds / got["busy_s"]


def pass_share(trace, obs: dict, say, what: str, pass_: str):
    """A reader's whole body: the share of busy time of one of `PASSES`,
    ``None`` where there is no table."""
    got = read(trace, obs, say)
    if got is None:
        return None
    return share(got, say, what, seconds=by_pass(got)[pass_])


def scope_share(trace, obs: dict, say, what: str, *names: str):
    """A reader's whole body: the share of busy time under one of the
    scopes ``names``, every pass; ``None`` where there is no table or
    the program has no such scope."""
    got = read(trace, obs, say)
    if got is None:
        return None
    mine = under(got, *names)
    if sum(mine.values()) <= 0:
        return None
    return share(got, say, what, mine)
