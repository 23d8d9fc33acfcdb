"""From the profiler's ``.xplane.pb`` to intervals and sums.

What a TPU trace of this JAX holds (looked at by hand, PR 27): one plane
``/device:TPU:<n>`` a chip with the lines ``XLA Modules`` (one event a
program run) and ``XLA Ops`` (one event an HLO operation, named by its
whole HLO text ``%name.12 = ...``; the body of a ``while`` is nested
inside the ``while``'s own event), and the plane ``/host:CPU`` whose
``python`` line carries ``jax.profiler.TraceAnnotation`` spans. All on
one clock, in nanoseconds.

This file only reduces; which names mean what is the readers' business
(``benchmark/layer_metrics``).
"""
from __future__ import annotations

import glob
import gzip
import os
import re
from dataclasses import dataclass, field

DEVICE_PLANE = "/device:TPU:"
HOST_PLANE = "/host:CPU"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench."
WINDOW_SPAN = SPAN_PREFIX + "window"


def op_name(hlo_text: str) -> str:
    """``%fusion.12 = bf16[...] fusion(...)`` -> ``fusion.12``."""
    return hlo_text.split(" = ", 1)[0].lstrip("%")


def op_family(name: str) -> str:
    """``fusion.12`` -> ``fusion``: the same operation of another layer
    or another compile keeps its family."""
    return re.sub(r"(\.\d+)+$", "", name)


def union(intervals) -> list:
    """Sorted, merged [start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def clip(intervals, lo, hi) -> list:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def total(intervals) -> float:
    return float(sum(e - s for s, e in intervals))


def self_times(events) -> dict:
    """name -> nanoseconds not covered by an event nested inside it.
    ``events`` are (name, start, end); nesting is by containment."""
    out: dict = {}
    stack: list = []
    for name, s, e in sorted(events, key=lambda ev: (ev[1], -ev[2])):
        while stack and stack[-1][2] <= s:
            stack.pop()
        if stack:                       # take my time out of my parent's
            parent = stack[-1][0]
            out[parent] = out.get(parent, 0.0) - (min(e, stack[-1][2]) - s)
        out[name] = out.get(name, 0.0) + (e - s)
        stack.append((name, s, e))
    return out


KERNEL_MARK = 'custom_call_target="tpu_custom_call"'   # a Pallas kernel


@dataclass
class Chip:
    ops: list = field(default_factory=list)       # (name, start, end) ns
    modules: list = field(default_factory=list)   # (name, start, end) ns
    kernels: dict = field(default_factory=dict)   # Pallas kernels: name -> HLO


@dataclass
class Trace:
    chips: list                                   # one Chip a device plane
    spans: list                                   # host (name, start, end)

    # ---- the window ------------------------------------------------
    def window(self) -> tuple:
        """[start, end) ns of the benchmark's own window span."""
        w = [(s, e) for n, s, e in self.spans if n == WINDOW_SPAN]
        if len(w) != 1:
            raise ValueError(f"want one {WINDOW_SPAN} span, found {len(w)}")
        return w[0]

    def window_s(self) -> float:
        lo, hi = self.window()
        return (hi - lo) / 1e9

    # ---- busy and idle ---------------------------------------------
    def busy(self, chip: Chip) -> list:
        lo, hi = self.window()
        return union(clip([(s, e) for _, s, e in chip.ops], lo, hi))

    def busy_s(self) -> float:
        """Seconds in which an operation ran, averaged over the chips."""
        return sum(total(self.busy(c)) for c in self.chips) \
            / len(self.chips) / 1e9

    def idle_by_cause(self) -> dict:
        """Idle nanoseconds of the first chip inside the window, by what
        covers them: ``in_program`` while one of its programs runs, else
        the benchmark's host span at that time (the spans do not
        overlap), else ``host_other``."""
        lo, hi = self.window()
        chip = self.chips[0]
        busy = self.busy(chip)
        edges = [lo] + [t for iv in busy for t in iv] + [hi]
        gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                if edges[i + 1] > edges[i]]
        programs = union((s, e) for _, s, e in chip.modules)
        spans = [(n[len(SPAN_PREFIX):], s, e) for n, s, e in self.spans
                 if n != WINDOW_SPAN]
        out: dict = {}

        def add(cause, ns):
            if ns > 0:
                out[cause] = out.get(cause, 0.0) + ns
        for s, e in gaps:
            inside = total(clip(programs, s, e))
            add("in_program", inside)
            outside = [(s, e)]
            for ps, pe in clip(programs, s, e):      # cut the programs out
                outside = [piece for a, b in outside
                           for piece in ((a, min(b, ps)), (max(a, pe), b))
                           if piece[1] > piece[0]]
            named = 0.0
            for n, ss, se in spans:
                ns = total(clip(outside, ss, se))
                add(n, ns)
                named += ns
            add("host_other", total(outside) - named)
        return out

    # ---- operations ------------------------------------------------
    def kernels(self) -> dict:
        """The operations that are Pallas kernels: name -> HLO text. The
        names are no handle: the same kernel is ``flash_attention.15``,
        ``jvp_jit_flash_attention__.3`` or plain ``tpu_custom_call.7``
        by how the step was traced and which JAX flags were set; the
        shapes in the text are."""
        out: dict = {}
        for c in self.chips:
            out.update(c.kernels)
        return out

    def op_seconds(self, match) -> float:
        """Summed device seconds, over the chips' mean, of the operations
        inside the window whose name ``match`` accepts."""
        lo, hi = self.window()
        ns = sum(min(e, hi) - max(s, lo) for c in self.chips
                 for n, s, e in c.ops
                 if match(n) and min(e, hi) > max(s, lo))
        return ns / len(self.chips) / 1e9

    def op_count(self, match) -> int:
        lo, hi = self.window()
        return sum(1 for n, s, e in self.chips[0].ops
                   if match(n) and lo <= s < hi)

    def top_ops(self, k: int = 10) -> list:
        """[[family, seconds], ...]: the first chip's operations inside
        the window by self time, families summed."""
        lo, hi = self.window()
        evs = [(n, max(s, lo), min(e, hi)) for n, s, e in self.chips[0].ops
               if min(e, hi) > max(s, lo)]
        fam: dict = {}
        for name, ns in self_times(evs).items():
            fam[op_family(name)] = fam.get(op_family(name), 0.0) + ns
        top = sorted(fam.items(), key=lambda kv: -kv[1])[:k]
        return [[n, ns / 1e9] for n, ns in top]


def _events(line, rename=lambda n: n):
    return [(rename(e.name), float(e.start_ns),
             float(e.start_ns + e.duration_ns)) for e in line.events]


def from_profile_data(pd) -> Trace:
    chips, spans = [], []
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PLANE):
            chip = Chip()
            for line in plane.lines:
                if line.name == OPS_LINE:
                    chip.ops = _events(line, op_name)
                    chip.kernels = {op_name(e.name): e.name
                                    for e in line.events
                                    if KERNEL_MARK in e.name}
                elif line.name == MODULES_LINE:
                    chip.modules = _events(line)
            if chip.ops:
                chips.append(chip)
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                spans += [ev for ev in _events(line)
                          if ev[0].startswith(SPAN_PREFIX)]
    if not chips:
        raise ValueError(f"no {DEVICE_PLANE}* plane with an {OPS_LINE!r} "
                         "line: nothing ran on a device in this trace")
    return Trace(chips, spans)


def load(path: str) -> Trace:
    """Read a ``.xplane.pb`` (or ``.xplane.pb.gz``), or the newest one
    under a profiler output directory."""
    from jax.profiler import ProfileData
    if os.path.isdir(path):
        found = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                                 recursive=True), key=os.path.getmtime)
        if not found:
            raise FileNotFoundError(f"no .xplane.pb under {path}")
        path = found[-1]
    if path.endswith(".gz"):
        with gzip.open(path, "rb") as f:
            return from_profile_data(
                ProfileData.from_serialized_xspace(f.read()))
    return from_profile_data(ProfileData.from_file(path))
