"""Test env bootstrap.

Tests run on 8 virtual CPU devices so sharding/collective tests exercise the
same XLA code path as real chips without hardware (SURVEY.md §4: the
reference spawns real processes per card; virtual host devices replace that).

Nothing imports jax before this file is loaded (pytest imports conftest
ahead of every test module, in the main process and in each xdist worker,
and workers inherit the main process's environment), so the platform, the
device count and the matmul precision are set here, in-process, before the
first ``import jax`` can read them.
"""
import os
import signal
import sys

_WANT = "--xla_force_host_platform_device_count=8"

if "jax" in sys.modules:
    raise RuntimeError(
        "jax was imported before tests/conftest.py could set the test "
        "environment (a plugin or sitecustomize imports it?) — run with "
        f"JAX_PLATFORMS=cpu XLA_FLAGS={_WANT} set on the command line")
os.environ["JAX_PLATFORMS"] = "cpu"
if _WANT not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "") + " " + _WANT).strip()
# Exact fp32 matmuls for numeric checks (prod keeps fast MXU default).
os.environ.setdefault("JAX_DEFAULT_MATMUL_PRECISION", "highest")
# Deliberately NO persistent XLA compile cache here: reloading a cached
# MULTI-DEVICE CPU program segfaulted the ZeRO-3 resume test (measured
# 2026-08-03 — the cpu_aot_loader hazard paddle_tpu/__init__.py
# documents). tools/ci.py opts in for its own runs; the raw pytest path
# stays cache-free and crash-free.

import pytest  # noqa: E402


def pytest_addoption(parser):
    parser.addoption(
        "--runslow", action="store_true", default=False,
        help="include tests marked slow (whole-step compiles for the "
             "described chip, chip_smoke.py rehearsals) — tools/ci.py "
             "--full sets this")


def pytest_collection_modifyitems(config, items):
    if config.getoption("--runslow") or os.environ.get(
            "PADDLE_TPU_RUN_SLOW"):
        return
    skip = pytest.mark.skip(
        reason="marked slow: run with --runslow (tools/ci.py --full)")
    for it in items:
        if "slow" in it.keywords:
            it.add_marker(skip)


def _test_limit(item) -> int:
    m = item.get_closest_marker("timeout")
    if m is None:
        return 300
    if m.args:
        return int(m.args[0])
    return int(m.kwargs.get("seconds", 300))


def _alarm_guard(item, phase):
    limit = _test_limit(item)

    def on_alarm(signum, frame):
        raise TimeoutError(
            f"{item.nodeid} {phase} exceeded the {limit}s per-test limit")

    old = signal.signal(signal.SIGALRM, on_alarm)
    signal.alarm(limit)
    return old


def _alarm_clear(old):
    signal.alarm(0)
    signal.signal(signal.SIGALRM, old)


@pytest.hookimpl(wrapper=True)
def pytest_runtest_setup(item):
    """Per-test wall-clock limits cover setup, call, AND teardown
    (reference: per-case TIMEOUT properties in the CMake test driver) —
    one hung test or fixture must not eat the CI budget. Override with
    @pytest.mark.timeout(seconds). SIGALRM-based, so a hang inside a
    non-yielding C call can still block — subprocess-heavy tests also
    carry their own communicate() timeouts."""
    old = _alarm_guard(item, "setup")
    try:
        return (yield)
    finally:
        _alarm_clear(old)


@pytest.hookimpl(wrapper=True)
def pytest_runtest_call(item):
    old = _alarm_guard(item, "call")
    try:
        return (yield)
    finally:
        _alarm_clear(old)


@pytest.hookimpl(wrapper=True)
def pytest_runtest_teardown(item):
    old = _alarm_guard(item, "teardown")
    try:
        return (yield)
    finally:
        _alarm_clear(old)


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: heavyweight test, deselected unless --runslow")
    config.addinivalue_line(
        "markers", "timeout(seconds): per-test wall-clock limit "
                   "(default 300)")


# ---------------------------------------------------------------------------
# optional line coverage (tools/ci.py --coverage): stdlib sys.monitoring,
# restricted to paddle_tpu/ — the reference's tools/coverage/ role without
# external packages.
# ---------------------------------------------------------------------------

_COV_TOOL = 3          # sys.monitoring tool id reserved for coverage
_cov_hits = {}


def _cov_enabled():
    return os.environ.get("PADDLE_TPU_COVERAGE") and _env_ok()


def pytest_sessionstart(session):
    if not _cov_enabled():
        return
    pkg = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "paddle_tpu")
    mon = sys.monitoring
    mon.use_tool_id(_COV_TOOL, "paddle_tpu_cov")

    def on_line(code, line):
        fn = code.co_filename
        if fn.startswith(pkg):
            _cov_hits.setdefault(fn, set()).add(line)
            return None
        return mon.DISABLE  # stop monitoring this location

    mon.register_callback(_COV_TOOL, mon.events.LINE, on_line)
    mon.set_events(_COV_TOOL, mon.events.LINE)


def pytest_sessionfinish(session, exitstatus):
    if not _cov_enabled() or not _cov_hits:
        return
    import ast
    sys.monitoring.set_events(_COV_TOOL, 0)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    rows = []
    tot_hit = tot_all = 0
    for fn in sorted(_cov_hits):
        try:
            tree = ast.parse(open(fn).read())
        except (OSError, SyntaxError):
            continue
        execable = {n.lineno for n in ast.walk(tree)
                    if isinstance(n, ast.stmt)}
        hit = len(_cov_hits[fn] & execable) or len(_cov_hits[fn])
        total = max(len(execable), hit)
        tot_hit += hit
        tot_all += total
        rel = os.path.relpath(fn, root)
        rows.append(f"{rel:60s} {hit:5d}/{total:<5d} "
                    f"{100.0 * hit / total:5.1f}%")
    report = os.path.join(root, "tools", "coverage_report.txt")
    with open(report, "w") as f:
        f.write("\n".join(rows))
        if tot_all:
            f.write(f"\n\nTOTAL {tot_hit}/{tot_all} "
                    f"({100.0 * tot_hit / tot_all:.1f}%)\n")
    print(f"\ncoverage report: {report} "
          f"({100.0 * tot_hit / max(tot_all, 1):.1f}% of touched files)")
