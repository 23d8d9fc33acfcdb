"""tpulint — static analysis over the programs this framework compiles.

The reference stack ships analysis/verification layers over its graph
IR (the pass framework under paddle/fluid/framework/ir/,
FLAGS_check_nan_inf, memory-reuse checkers). Our IR is the jaxpr and
lowered StableHLO of every jitted program; this package is the
systematic way to inspect it BEFORE it reaches hardware:

- program_lint:  walk a program's ClosedJaxpr + StableHLO — dtype
  promotions, scatter/gather, host callbacks, un-donated buffers,
  baked RNG keys, collective inventory.
- recompile:     statically diff abstract call signatures — which arg
  dims will force re-tracing (PR 2's recompile storms, decided without
  compiling anything).
- codebase_lint: AST pass over the tree — retrace-per-call jit idioms,
  traced attribute mutation in Layer.forward (the aux_loss.py class of
  bug), numpy on traced values, stale quarantine entries.
- concurrency:   the tpurace pass — per-class guarded-attribute
  inference over the same AST walk: guarded attrs touched outside
  their lock, blocking calls under a lock, a cross-class static
  lock-order graph with cycle detection, unlocked check-then-act,
  orphan non-daemon threads; `tools/tpurace.py` gates CI on the diff
  against tools/tpurace_baseline.json (runtime half: obs/locks.py +
  tools/race_hunt.py).
- manifest:      the real serving/training programs (engine decode,
  generate prefill, TrainStep, ParallelTrainStep on a fake 4-device
  mesh) rebuilt and linted; `tools/tpulint.py` gates CI on the diff
  against tools/tpulint_baseline.json.
- hlo_cost + fusion: the tpucost pass — compiled HLO parsed into a
  per-program FLOP/HBM/roofline inventory with fusion histogram and
  the ranked unfused-chain report; `tools/tpucost.py` gates CI on
  ratcheted budgets + anchors in tools/tpucost_baseline.json.
- runtime_profile: the tpuprof pass — measured per-kernel device time
  (programmatic jax.profiler, stdlib chrome-trace parser) JOINED with
  hlo_cost's modeled inventory: time-weighted fusion histogram,
  measured-vs-roofline ratios, time-ranked unfused chains;
  `tools/tpuprof.py` gates CI on a noise-tolerant dispatch-time
  ratchet + measured anchors in tools/tpuprof_baseline.json.
- report:        the shared --json artifact + terminal-record contract
  the CLIs emit (one terminal JSON record).

CLIs: python tools/tpulint.py [--update-baseline] [--json out.json]
      python tools/tpucost.py [--update-baseline] [--json out.json]
      python tools/tpuprof.py [--update-baseline] [--json out.json]
      python tools/tpurace.py [--update-baseline] [--json out.json]
"""
from .findings import (Finding, Severity, count_findings,
                       diff_against_baseline, findings_to_json,
                       load_baseline)
from .program_lint import collective_inventory_from_hlo, lint_program
from .recompile import abstract_signature, recompile_report
from .codebase_lint import (HOT_JIT_FILES, lint_file, lint_quarantine,
                            lint_tree)
from .concurrency import (collect_classes, lint_concurrency_file,
                          lint_concurrency_paths, lint_concurrency_tree)
from .manifest import (MANIFEST_PROGRAMS, ProgramSpec, default_manifest,
                       manifest_names, run_manifest)
from .hlo_cost import (CHIP_SPECS, DEFAULT_CHIP, ChipSpec,
                       analytic_decode_hbm_bytes,
                       analytic_verify_hbm_bytes, check_cost_baseline,
                       collect_kernels, load_cost_baseline,
                       parse_hlo_module, program_cost,
                       updated_cost_baseline)
from .fusion import fusion_histogram, unfused_chains
from .collective_schedule import (diff_schedules, gather_chain_links,
                                  gather_overlap_report,
                                  schedule_events)
from .runtime_profile import (check_profile_baseline, device_op_times,
                              join_measured_modeled,
                              load_profile_baseline, load_trace_events,
                              profile_program, runtime_report,
                              updated_profile_baseline)
from .report import terminal_record, write_report_artifact

__all__ = [
    "Finding", "Severity", "count_findings", "diff_against_baseline",
    "findings_to_json", "load_baseline",
    "lint_program", "collective_inventory_from_hlo",
    "abstract_signature", "recompile_report",
    "lint_tree", "lint_file", "lint_quarantine", "HOT_JIT_FILES",
    "lint_concurrency_tree", "lint_concurrency_file",
    "lint_concurrency_paths", "collect_classes",
    "ProgramSpec", "default_manifest", "run_manifest",
    "MANIFEST_PROGRAMS", "manifest_names",
    "ChipSpec", "CHIP_SPECS", "DEFAULT_CHIP", "parse_hlo_module",
    "program_cost", "collect_kernels", "analytic_decode_hbm_bytes",
    "analytic_verify_hbm_bytes",
    "check_cost_baseline", "load_cost_baseline",
    "updated_cost_baseline", "fusion_histogram", "unfused_chains",
    "schedule_events", "gather_overlap_report", "gather_chain_links",
    "diff_schedules",
    "load_trace_events", "device_op_times", "join_measured_modeled",
    "runtime_report", "profile_program", "check_profile_baseline",
    "load_profile_baseline", "updated_profile_baseline",
    "write_report_artifact", "terminal_record",
]
