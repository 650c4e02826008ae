"""The transform path imports nothing from the LM scaffold.

``repro.models``, ``repro.parallel``, ``repro.train`` and
``repro.configs`` are an island that no benchmark cell drives: the
transform packages, the chip benchmark, the examples and the chip smoke
run must not import them, so that deleting the island never touches
the transform path.
"""

import ast
import os

from conftest import REPO

SCAFFOLD = ("repro.models", "repro.parallel", "repro.train", "repro.configs")
TRANSFORM_PATH = [os.path.join("src", "repro", pkg) for pkg in (
    "core", "real", "grad", "kernels", "tuning", "serve", "resil", "obs",
    "solvers", "launch")] + ["bench", "examples", "chip_smoke.py"]


def _py_files(rel):
    path = os.path.join(REPO, rel)
    if os.path.isfile(path):
        yield path
        return
    for root, _, files in os.walk(path):
        yield from (os.path.join(root, f) for f in files if f.endswith(".py"))


def _imported_modules(path):
    """Absolute names of every module a file imports, relative imports
    resolved against its package under ``src/``."""
    rel = os.path.relpath(path, os.path.join(REPO, "src"))
    package = rel.replace(os.sep, ".").rsplit(".", 2)[0]
    with open(path) as f:
        tree = ast.parse(f.read(), path)
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            yield from (a.name for a in node.names)
        elif isinstance(node, ast.ImportFrom):
            base = node.module or ""
            if node.level:
                parts = package.split(".")
                parts = parts[:len(parts) + 1 - node.level]
                base = ".".join(parts + ([base] if base else []))
            yield base
            yield from (f"{base}.{a.name}" for a in node.names)


def test_transform_path_does_not_import_the_lm_scaffold():
    files = [f for rel in TRANSFORM_PATH for f in _py_files(rel)]
    assert len(files) > 50
    offenders = sorted(
        f"{os.path.relpath(f, REPO)}: {mod}" for f in files
        for mod in _imported_modules(f)
        if any(mod == s or mod.startswith(s + ".") for s in SCAFFOLD))
    assert offenders == []
