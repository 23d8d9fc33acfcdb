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


def test_bench_and_smoke_cut_gpt1p3b_to_the_same_depth():
    import re
    depth = {}
    for name, var in (("bench.py", "GPT1P3B_LAYERS_ONE_CHIP"),
                      ("chip_smoke.py", "GPT1P3B_LAYERS")):
        with open(os.path.join(ROOT, name)) as f:
            depth[name] = int(re.search(rf"^{var} = (\d+)$", f.read(),
                                        re.M).group(1))
    assert depth["bench.py"] == depth["chip_smoke.py"] < 24


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
@pytest.mark.parametrize("extra,mp", [
    (dict(scan_layers=True), True),
    (dict(scan_layers=True, recompute=True, fused_loss_chunk=64), False)])
def test_rehearse_train(smoke, extra, mp):
    smoke.phase_train(dict(name="tiny", batch=2, seq=128,
                           multi_precision=mp,
                           cfg=dict(TINY_GPT, **extra)))


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
