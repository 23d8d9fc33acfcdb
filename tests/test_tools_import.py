"""Scripts outside the suite are where this tree goes stale unseen: no
tier-1 test runs most of tools/, so a script can go on importing a name
the package dropped. For chip_smoke.py and every script under tools/:
each ``paddle_tpu`` module it imports, at any depth of the file, exists,
and so does every name it takes from one. The script itself is not run.
"""
import ast
import glob
import importlib
import os

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPTS = ["chip_smoke.py"] + sorted(
    os.path.relpath(p, ROOT)
    for p in glob.glob(os.path.join(ROOT, "tools", "*.py")))


def _package_imports(path):
    """(module, name or None, line) for every paddle_tpu import."""
    with open(path) as f:
        tree = ast.parse(f.read(), path)
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for a in node.names:
                if a.name.split(".")[0] == "paddle_tpu":
                    yield a.name, None, node.lineno
        elif (isinstance(node, ast.ImportFrom) and node.level == 0
              and (node.module or "").split(".")[0] == "paddle_tpu"):
            for a in node.names:
                yield node.module, a.name, node.lineno


@pytest.mark.parametrize("script", SCRIPTS)
def test_script_imports_resolve(script):
    stale = []
    for module, name, line in _package_imports(os.path.join(ROOT, script)):
        try:
            mod = importlib.import_module(module)
        except ImportError as e:
            stale.append(f"{script}:{line}: import {module}: {e}")
            continue
        if name is None or name == "*" or hasattr(mod, name):
            continue
        try:                    # `from package import submodule`
            importlib.import_module(f"{module}.{name}")
        except ImportError:
            stale.append(f"{script}:{line}: {module} has no {name!r}")
    assert stale == []
