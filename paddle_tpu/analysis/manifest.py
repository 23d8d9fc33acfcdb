"""tpulint default manifest: the real programs every perf PR rides on.

The program set IS the ProgramRegistry (paddle_tpu.compilation): every
site registered with the "manifest" tag is rebuilt exactly as its owner
builds it (the builders live in compilation/sites.py) and handed to the
program linter — trace + lower only (collective-tagged programs
additionally compile for their collective inventory). One table serves
every consumer: tpulint lints it, `compilation.warmup` prebuilds it
and `tools/warmup.py` persists it to the executable store — so a newly
registered program is lint-covered, warmable, and store-cacheable BY
DEFAULT, and the
baseline keys (code::program::site) are the registry names.

Current registry population (see compilation/sites.py for each):
gpt_decode, llama_prefill, train_step, train_step_scan,
parallel_train_step (the pre-registry five, order preserved so baseline
keys stay stable), gpt_admit and llama_decode (newly covered by landing
in the registry).

Plus two static recompile-hazard reports that are not program sites:
the sequential generate() path's per-(prompt-len) program key — the
hazard the engine's prefill buckets exist to close (PR 2) — and the
fused train loop's pinned 2-program signature (scanned window +
trailing per-step, PR 4).

Everything is tiny-config and CPU-safe; no program is executed.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable, List, Optional, Tuple

import numpy as np

from ..compilation import registry as _registry
from .findings import Finding
from .program_lint import lint_program
from .recompile import recompile_report

__all__ = ["ProgramSpec", "default_manifest", "run_manifest",
           "MANIFEST_PROGRAMS", "manifest_names"]

# static analyses that are reports over abstract call specs, not
# registered program sites
STATIC_REPORTS = ("generate_prompt_drift", "train_scan_window_drift")


def manifest_names() -> Tuple[str, ...]:
    """The current program set: registry sites tagged "manifest" (in
    registration order — baseline keys depend on the names only) plus
    the static reports. Computed from the live registry so a program
    registered after import is still covered."""
    return tuple(_registry.names(tag="manifest")) + STATIC_REPORTS


# import-time snapshot for CLI help/validation messages; gate logic
# uses manifest_names() so late registrations are linted by default
MANIFEST_PROGRAMS = manifest_names()


@dataclass
class ProgramSpec:
    name: str
    build: Callable[[], Tuple[Any, tuple, Optional[Callable]]]
    compile_collectives: bool = False


def _adapt(prog: "_registry.RegisteredProgram"):
    """Registry builder (-> BuildResult) to the linter's
    (fn, args, cleanup) triple."""
    def build():
        r = prog.builder()
        return r.fn, r.args, r.cleanup
    return build


def default_manifest() -> List[ProgramSpec]:
    return [ProgramSpec(name, _adapt(_registry.get(name)),
                        _registry.get(name).compile_collectives)
            for name in _registry.names(tag="manifest")]


def _generate_prompt_drift_report() -> List[Finding]:
    """Static restatement of PR 2's recompile storm: sequential
    generate() keys one compiled program per exact prompt length, so
    drifting traffic re-traces per request. The engine's bucketed
    prefill is the fix; this report keeps the hazard visible (and the
    analyzer honest) in the baseline."""
    specs = [(np.zeros((1, p), np.int64),) for p in (7, 9, 13)]
    return recompile_report("generate_prompt_drift", specs)


def _train_scan_window_drift_report() -> List[Finding]:
    """The fused train loop's PINNED recompile signature: one drifting-
    length epoch dispatches exactly TWO abstract call shapes — the
    scanned [K, B, S] super-batch window and the trailing per-step
    [B, S] batch (Model._run_epoch_fused's fallback). The baseline pins
    this at 2 programs; a third signature appearing here means the
    fused driver started re-tracing per window length (the hazard
    tests/test_scan_train.py's trace counter also guards at runtime)."""
    specs = [(np.zeros((4, 2, 32), np.int64),
              np.zeros((4, 2, 32), np.int64)),
             (np.zeros((2, 32), np.int64), np.zeros((2, 32), np.int64))]
    return recompile_report("train_scan_window_drift", specs)


def run_manifest(programs: Optional[List[str]] = None,
                 compile_collectives: bool = True
                 ) -> Tuple[List[Finding], List[str]]:
    """Build + lint the manifest. Returns (findings, program names run).
    `programs` filters by name; `compile_collectives=False` skips the
    compile-requiring inventory (trace/lower only — faster gate)."""
    valid = manifest_names()
    wanted = set(programs) if programs else None
    if wanted is not None:
        unknown = wanted - set(valid)
        if unknown:
            raise ValueError(
                f"unknown manifest program(s) {sorted(unknown)}; "
                f"valid: {list(valid)}")
    findings: List[Finding] = []
    ran: List[str] = []
    for spec in default_manifest():
        if wanted is not None and spec.name not in wanted:
            continue
        fn, args, cleanup = spec.build()
        try:
            findings.extend(lint_program(
                spec.name, fn, args,
                compile_collectives=(spec.compile_collectives
                                     and compile_collectives)))
            ran.append(spec.name)
        finally:
            if cleanup is not None:
                cleanup()
    if wanted is None or "generate_prompt_drift" in wanted:
        findings.extend(_generate_prompt_drift_report())
        ran.append("generate_prompt_drift")
    if wanted is None or "train_scan_window_drift" in wanted:
        findings.extend(_train_scan_window_drift_report())
        ran.append("train_scan_window_drift")
    return findings, ran
