"""chip_smoke.py: it must fail without a chip, and its phase functions
must keep working (rehearsed here on the CPU at tiny sizes).

The rehearsals are the on-chip-measurement guide's first two: the phase
functions called end to end with ``PLATFORM = "cpu"`` (Pallas kernels
interpreted), the four-chip phases on four of the eight virtual devices.
They are marked slow — tier-1 runs only the no-chip exit test; run the
rest with ``--runslow`` before spending chip time on chip_smoke.py.
"""
import importlib.util
import json
import os
import re
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(ROOT, "chip_smoke.py")


def test_chip_smoke_fails_without_a_chip():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, SMOKE], env=env, cwd=ROOT,
                       capture_output=True, text=True, timeout=240)
    assert r.returncode != 0
    assert '"ok": true' not in r.stdout
    assert "need 'tpu'" in r.stdout + r.stderr


def _python_files_outside_the_benchmark():
    """Every *.py git would commit outside benchmark/: the tree walked
    without hidden directories and without what .gitignore lists (the
    driver's checkout need not be a git repository)."""
    import fnmatch
    with open(os.path.join(ROOT, ".gitignore")) as f:
        ignored = [ln.strip().rstrip("/") for ln in f
                   if ln.strip() and not ln.startswith("#")]
    for here, dirs, files in os.walk(ROOT):
        dirs[:] = [d for d in dirs if not d.startswith(".")
                   and not any(fnmatch.fnmatch(d, pat) for pat in ignored)
                   and (here, d) != (ROOT, "benchmark")]
        for name in files:
            if name.endswith(".py"):
                yield os.path.relpath(os.path.join(here, name), ROOT)


def test_one_yardstick():
    """Speed is read from one place, benchmark/run.py over BENCHMARK.json:
    nothing outside benchmark/ keeps a timing script's knobs, chip_smoke.py
    retypes no cell (no train phase, no GPT depth of its own), and the
    README names only files that exist."""
    knob = "PADDLE_TPU_" + "BENCH_"
    readers = []
    for path in _python_files_outside_the_benchmark():
        with open(os.path.join(ROOT, path)) as f:
            if knob in f.read():
                readers.append(path)
    assert readers == []

    with open(SMOKE) as f:
        smoke = f.read()
    assert not re.search(r"phase_train|\btrain_\w+|TRAIN_", smoke)
    assert not re.search(r"^GPT\w*LAYERS\w* = ", smoke, re.M)

    # every word in backticks that looks like a file of this repo; the
    # component map names modules from inside the package, and what
    # starts with a dot is made at run time
    with open(os.path.join(ROOT, "README.md")) as f:
        words = {w for span in re.findall(r"`([^`\n]+)`", f.read())
                 for w in span.split()}
    paths = {w for w in words if not w.startswith(".")
             and re.fullmatch(r"[\w./-]+(\.py|\.md|\.jsonl?|/)", w)}
    assert {"benchmark/run.py", "BENCHMARK.json", "PERF_LEDGER.jsonl",
            "chip_smoke.py"} <= paths
    missing = sorted(p for p in paths if not any(
        os.path.exists(os.path.join(base, p))
        for base in (ROOT, os.path.join(ROOT, "paddle_tpu"))))
    assert missing == []


@pytest.fixture
def smoke(monkeypatch):
    spec = importlib.util.spec_from_file_location("chip_smoke", SMOKE)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    monkeypatch.setattr(mod, "PLATFORM", "cpu")
    return mod


TINY_GPT = dict(vocab_size=512, hidden_size=128, num_layers=2,
                num_heads=4, max_seq_len=256)


@pytest.mark.slow
def test_rehearse_kernels(smoke, monkeypatch):
    from paddle_tpu.kernels import cache_write, mega_decode
    monkeypatch.setattr(cache_write, "_L_BLOCK", 16)
    monkeypatch.setattr(mega_decode, "_L_BLOCK", 16)
    smoke.phase_kernels(dict(
        flash=((1, 128, 2, 64),), flash_block=((1, 2, 256, 64),),
        flash_gqa=((1, 128, 4, 2, 64, 48), (1, 128, 4, 1, 64, None)),
        cache=(3, 32, 2, 128), pool=(8, 4, 2, 128), ce=(48, 640),
        mega=((3, 32, 2, 128),)))


@pytest.mark.slow
@pytest.mark.timeout(600)
def test_rehearse_serve(smoke, tmp_path, capsys):
    spec = dict(model=dict(kind="gpt", scan_layers=True, **TINY_GPT),
                engine=dict(slots=4, max_len=256, cache_dtype="bfloat16",
                            paged=True, page_size=8),
                prompt_lens=(8, 20, 50, 100), new_tokens=16)
    handoff = str(tmp_path / "served.json")
    # the driver must not initialise a backend: run it as the script
    # does, in a process of its own
    code = ("import importlib.util, sys, json\n"
            f"spec = importlib.util.spec_from_file_location('cs', {SMOKE!r})\n"
            "m = importlib.util.module_from_spec(spec)\n"
            "spec.loader.exec_module(m)\n"
            "m.PLATFORM = 'cpu'\n"
            f"m.phase_serve({handoff!r}, json.loads({json.dumps(spec)!r}),"
            " ready_timeout=300)\n")
    env = dict(os.environ, PYTHONPATH=ROOT)
    r = subprocess.run([sys.executable, "-c", code], env=env, cwd=ROOT,
                       capture_output=True, text=True, timeout=540)
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-4000:]
    assert '"tier_stopped": true' in r.stdout
    smoke.phase_serve_ref(handoff, spec)


@pytest.mark.slow
def test_rehearse_multichip_tp(smoke):
    smoke.phase_multichip_tp(dict(
        cfg=TINY_GPT, engine=dict(slots=2, max_len=128,
                                  cache_dtype="bfloat16"),
        prompt_lens=(8, 20), new_tokens=8), tp=4)


@pytest.mark.slow
def test_rehearse_multichip_zero(smoke):
    smoke.phase_multichip_zero(dict(cfg=TINY_GPT, batch=8, seq=128,
                                    steps=3))


@pytest.mark.parametrize("placed_from_outside", [True, False])
def test_compile_cache_placement_rule(placed_from_outside, tmp_path):
    """One rule (paddle_tpu/_paths.jax_cache_dir, applied by the package
    import): with JAX_COMPILATION_CACHE_DIR set the program sets no
    directory in code (jax reads the variable itself, so the value in
    effect IS the variable's); without it the cache is the fixed,
    git-ignored ``.cache/jax`` inside the checkout. Checked in a
    subprocess on a non-CPU platform string — importing the package
    initialises no backend, so no chip is needed."""
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_COMPILATION_CACHE_DIR", "JAX_PLATFORMS")}
    env["PYTHONPATH"] = ROOT
    outside = str(tmp_path / "placed")
    if placed_from_outside:
        env["JAX_COMPILATION_CACHE_DIR"] = outside
    code = ("import jax, json, paddle_tpu\n"
            "from paddle_tpu import _paths\n"
            "print(json.dumps({'configured': "
            "jax.config.jax_compilation_cache_dir, "
            "'rule': _paths.jax_cache_dir(), "
            "'tracebacks': "
            "jax.config.jax_include_full_tracebacks_in_locations, "
            "'backends': list(jax._src.xla_bridge._backends)}))\n")
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       cwd=str(tmp_path), capture_output=True, text=True,
                       timeout=240)
    assert r.returncode == 0, r.stderr[-2000:]
    got = json.loads(r.stdout.strip().splitlines()[-1])
    assert got["backends"] == []
    want = outside if placed_from_outside else os.path.join(
        ROOT, ".cache", "jax")
    assert got["configured"] == got["rule"] == want
    # callers' line numbers stay out of Pallas programs' cache keys
    assert got["tracebacks"] is False
    if placed_from_outside:
        assert not os.path.exists(outside)   # jax makes it, lazily
    else:
        with open(os.path.join(ROOT, ".gitignore")) as f:
            assert ".cache/" in f.read().split()
