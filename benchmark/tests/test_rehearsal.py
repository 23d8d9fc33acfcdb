"""CPU rehearsals of ``run.py``: it fails for want of a chip unless a test
steers it; a cell, a driver and a per-layer metric added as new files are
found by name; and a timed path broken underneath reads ``correct``
false."""
import json
import os
import subprocess
import sys

import pytest

from rehearsal import REPO, add_tiny_cell, make_tree, run_cell

BIG_SEED = 2**31 + 12345


def test_no_chip_no_result():
    p = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmark", "run.py"),
         "--workload", "train-gpt-125m", "--seed", "1", "--seconds", "1",
         "--trace", "0"],
        capture_output=True, text=True, timeout=300, cwd=REPO,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert p.returncode != 0
    assert "{" not in p.stdout


def test_alone_in_a_directory_no_result(tmp_path):
    """Only BENCHMARK.json and the files under paths: no program."""
    root = make_tree(str(tmp_path))
    os.unlink(os.path.join(root, "paddle_tpu"))
    add_tiny_cell(root)
    rc, result, err = run_cell(root, "train-tiny", 3)
    assert rc != 0 and result is None
    assert "paddle_tpu" in err


def test_run_py_names_nothing():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    with open(os.path.join(REPO, "benchmark", "run.py")) as f:
        text = f.read()
    names = [e["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for e in bench[k]]
    names += [w["traffic"] for w in bench["workloads"]]
    assert [n for n in names if n in text] == []


def test_new_files_are_found_by_name(tmp_path):
    """A cell, a configuration, a traffic mix, a driver and a per-layer
    metric, all added as new files and entries, run."""
    root = make_tree(str(tmp_path))
    with open(os.path.join(root, "benchmark", "drivers",
                           "train_steps_again.py"), "w") as f:
        f.write("from benchmark.drivers.train_steps import *  # noqa\n")
    add_tiny_cell(root, driver="train_steps_again", scan=True)
    with open(os.path.join(root, "benchmark", "layer_metrics",
                           "steps_done.new.py"), "w") as f:
        f.write("def read(trace, obs, cell, chip, say):\n"
                "    return obs['steps']\n")
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["per_layer"] = [
        {"name": "steps_done.new", "unit": "count", "better": "higher",
         "source": "program_counter", "layer": "trainer entry",
         "moves": "train_tokens_per_s", "workloads": ["train-tiny"]}]
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)

    rc, result, err = run_cell(root, "train-tiny", BIG_SEED)
    assert rc == 0, err
    assert result["correct"] is True, err
    assert set(result["metrics"]) == {"train_tokens_per_s", "setup_s"}
    assert result["attempted"] > 0 and result["failed"] == 0
    assert list(result)[-1] == "compared"
    last = [l for l in err.strip().splitlines()][-len(result["compared"]):]
    assert all(l.startswith("[benchmark] compared ") for l in last)
    assert result["device"]["platform"] == "cpu"   # named for what it is


@pytest.mark.parametrize("fault,number", [
    ("state_unchanged", "change_norm_gap"),
    ("half_batch", "grad_norm_gap"),
])
def test_broken_timed_path_reads_not_correct(tmp_path, fault, number):
    root = make_tree(str(tmp_path))
    add_tiny_cell(root)
    rc, result, err = run_cell(root, "train-tiny", 7, fault=fault)
    assert rc == 0, err
    assert result["correct"] is False
    got = result["compared"][number]
    assert got["value"] > got["limit"]
