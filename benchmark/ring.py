"""What the program recorded about itself, for the readers that want it.

``paddle_tpu.obs.recorder`` is the program's one ring of spans (4096
events by default; Chrome-trace events, ``ts`` and ``dur`` in
microseconds of ``time.perf_counter``, the clock of the driver's own
``obs["spans"]``). ``jit.TrainStep`` records ``train.step`` and its
children ``.prep`` / ``.enqueue`` / ``.post`` there, and
``compilation.counters`` every ``compile.trace`` / ``compile.lower`` /
``compile.backend`` with the function's name. A program that records
none of these (an older one) has nothing to read: the readers return
``None``.

This file only fetches and reduces; which span means what is the
readers' business (``benchmark/layer_metrics``).
"""
from __future__ import annotations


def window(obs: dict):
    """(start, end) seconds of the measured window on the host clock:
    first start to last end of the driver's own spans; ``None`` where
    the driver kept none."""
    spans = obs.get("spans") or ()
    if not spans:
        return None
    return min(s for _, s, _ in spans), max(e for _, _, e in spans)


def events():
    """(the ring's events, oldest first, as (name, start s, end s,
    args); whether the ring has wrapped, so that its oldest events, the
    set-up's, are gone)."""
    import paddle_tpu.obs as program_obs
    rec = program_obs.recorder
    out = [(e["name"], e["ts"] / 1e6, (e["ts"] + e["dur"]) / 1e6,
            e.get("args") or {}) for e in rec.events()]
    return out, rec.appended > rec.size


def longest(evs, k: int = 3) -> str:
    """``name 1.23 s, ...``: the ``k`` functions that took longest,
    summed by the name the event carries."""
    by_name: dict = {}
    for _, s, e, args in evs:
        n = str(args.get("fun_name", "?"))
        by_name[n] = by_name.get(n, 0.0) + (e - s)
    top = sorted(by_name.items(), key=lambda kv: -kv[1])[:k]
    return ", ".join(f"{n} {t:.2f} s" for n, t in top)


def before_window(obs: dict, names: tuple, say):
    """The ring's events of these names that ended before the window;
    ``None``, with a line through ``say``, where that cannot be told."""
    win = window(obs)
    if win is None:
        return None
    evs, wrapped = events()
    mine = [ev for ev in evs if ev[0] in names]
    if not mine:
        return None             # the program records no such span
    if wrapped:
        say(f"{'/'.join(names)}: the program's ring has wrapped, its "
            "oldest events (set-up's) are gone: not read")
        return None
    return [ev for ev in mine if ev[2] <= win[0]]


def step_parts(obs: dict, say):
    """{"prep" | "enqueue" | "post": the milliseconds of each of the
    window's ``train.step.<part>`` spans, oldest first}; ``None`` where
    the program records no such span, and ``None`` with a line through
    ``say`` where a part's count disagrees with the driver's steps."""
    win = window(obs)
    if win is None or not obs.get("steps"):
        return None
    evs, _ = events()
    parts = {}
    for part in ("prep", "enqueue", "post"):
        durs = [1e3 * (e - s) for n, s, e, _ in evs
                if n == "train.step." + part and win[0] <= s and e <= win[1]]
        if not durs and part == "prep":
            return None         # the program records no such span
        if len(durs) != obs["steps"]:
            say(f"train.step.{part}: {len(durs)} spans in the window for "
                f"{obs['steps']} steps: not read")
            return None
        parts[part] = durs
    return parts
