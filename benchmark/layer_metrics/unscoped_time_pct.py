"""The instrument's own health: the share of the device's busy time in
the window that the step program's table (``benchmark/step_scopes.py``)
cannot name, either because the instruction's path runs through no scope
of the program or because the table does not hold the instruction at all
(the second is said apart; above 1% the table is of another program than
the one traced, and no reader of this family reads anything). Every other
``*_time_pct`` of this family is short by at most this."""
from benchmark import step_scopes


def read(trace, obs, cell, chip, say):
    got = step_scopes.read(trace, obs, say)
    if got is None:
        return None
    by = got["by_scope"]
    record = step_scopes.record()
    source = "the driver's hand-over" if record is None else (
        f"the program's own record ({record.program} of {record.trainer}, "
        f"made in {record.publish_s:.4f} s of the compiling call; the "
        f"module's text is {len(record.hlo_text()) / 1e6:.1f} MB of host "
        "memory)")
    say(f"step scopes from {source}: {100 * by['unscoped_share']:.3f}% of "
        f"busy time under no scope, of it {100 * by['not_in_table_share']:.3f}"
        "% on instructions the table does not hold")
    return step_scopes.share(
        got, say, "unscoped counts as forward",
        seconds=by["by_region"].get("unscoped", {}).get("seconds", 0.0))
