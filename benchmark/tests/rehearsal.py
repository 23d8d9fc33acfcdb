"""Helpers for the CPU rehearsals: a copy of the benchmark in a scratch
directory, to which a test adds a tiny cell as new files only, and a
child process that steers ``run.py`` past its look for a chip.

The steering lives here, in the tests, and not in an option of the
harness: ``run.py`` itself has no way to run without an accelerator.
"""
from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

# limits of the tiny cell, set as the real cells' are: over what the
# program reads at this size on the CPU (loss gaps to 2.3e-4, gradient
# norms to 4.8e-3, changes to 4.5e-2 on the seeds tried) and under what
# the fp8 control (1.1e-3, 1.8e-2) and the planted faults read
TINY_LIMITS = {"loss_gap_step1": 6e-4, "loss_gap_step2": 6e-4,
               "loss_gap_step3": 6e-4, "grad_norm_gap": 0.012,
               "change_norm_gap": 0.3, "attention_backend_differs": 0}

# what the child runs: patch the look for a chip and the table of peaks,
# optionally break the timed path underneath, then run the harness
_STEER = r"""
import importlib.util, json, os, sys
root = sys.argv[1]; fault = sys.argv[2]
sys.path.insert(0, root)
spec = importlib.util.spec_from_file_location(
    "run", os.path.join(root, "benchmark", "run.py"))
run = importlib.util.module_from_spec(spec); spec.loader.exec_module(run)
import jax
run.require_devices = lambda chips: jax.devices()[:chips]
from benchmark import chips
chips.chip_for = lambda kind: chips.Chip("cpu-rehearsal", 1e12, 1e11,
                                         2**30, "test")
if fault != "none":
    from paddle_tpu.jit import TrainStep
    real = TrainStep.__call__
    if fault == "state_unchanged":
        def broken(self, *batch):
            keep = jax.tree_util.tree_map(
                jax.numpy.copy, (self.params, self.buffers, self.opt_state))
            loss = real(self, *batch)
            self.params, self.buffers, self.opt_state = keep
            return loss
    elif fault == "half_batch":
        def broken(self, *batch):
            return real(self, *[b[: b.shape[0] // 2] for b in batch])
    TrainStep.__call__ = broken
sys.exit(run.main(sys.argv[3:]))
"""


def make_tree(tmp: str) -> str:
    """Copy BENCHMARK.json and benchmark/ (no tests), link the program."""
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp)
    shutil.copytree(os.path.join(REPO, "benchmark"),
                    os.path.join(tmp, "benchmark"),
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    os.symlink(os.path.join(REPO, "paddle_tpu"),
               os.path.join(tmp, "paddle_tpu"))
    return tmp


def tiny_arch() -> dict:
    with open(os.path.join(REPO, "benchmark", "configs",
                           "gpt-125m.json")) as f:
        conf = json.load(f)
    conf.update(name="gpt-tiny", hidden_size=64, num_heads=4, head_dim=16,
                num_layers=2, vocab_size=256, max_seq_len=32)
    conf["job"] = dict(conf["job"], attention_backend="xla",
                       fused_loss_chunk=16)
    return conf


def add_tiny_cell(root: str, cell: str = "train-tiny",
                  driver: str = "train_steps", scan: bool = False) -> None:
    """A cell, a configuration, a traffic mix and limits: new files and
    new entries, no file that is there edited."""
    def dump(obj, *parts):
        with open(os.path.join(root, *parts), "w") as f:
            json.dump(obj, f)
    conf = tiny_arch()
    conf["job"]["scan_layers"] = scan
    conf["job"]["recompute"] = scan
    conf["job"]["master_weights"] = not scan
    dump(conf, "benchmark", "configs", "gpt-tiny.json")
    dump({"driver": driver, "batch": 4, "seq": 32, "log_every": 5,
          "warmup_steps": 3, "compare_steps": 3, "trace_seconds": 1},
         "benchmark", "traffic", "steps-tiny.json")
    dump(TINY_LIMITS, "benchmark", "limits", cell + ".json")
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append({"name": "gpt-tiny", "source": "test",
                             "file": "benchmark/configs/gpt-tiny.json",
                             "reduced": [], "why": "rehearsal"})
    bench["workloads"].append({"name": cell, "config": "gpt-tiny",
                               "traffic": "steps-tiny", "chips": 1,
                               "why": "rehearsal"})
    dump(bench, "BENCHMARK.json")


def run_cell(root: str, cell: str, seed: int, fault: str = "none",
             trace: int = 0, seconds: float = 0.3):
    """(return code, the result object or None, standard error)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "-c", _STEER, root, fault, "--workload", cell,
         "--seed", str(seed), "--seconds", str(seconds), "--trace",
         str(trace)],
        capture_output=True, text=True, env=env, timeout=600, cwd=root)
    lines = p.stdout.strip().splitlines()
    result = json.loads(lines[-1]) if lines and lines[-1].startswith("{") \
        else None
    return p.returncode, result, p.stderr
