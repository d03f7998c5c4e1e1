"""Package boundaries: every module of the triangle-counting packages
imports on the CPU, and none of them imports the model-training code
that left the repo: the packages ``models``, ``optim``, ``sparse``,
``data`` and ``configs`` of ``repro``, and the ``train``, ``dryrun``
and ``mesh`` modules of ``repro.launch``.

The scan reads each module's AST, so imports inside functions and
module names passed as strings (``__import__``, ``importlib``, code run
in a subprocess) count too."""
import ast
import importlib
import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SRC = os.path.join(REPO, "src")

GONE_PACKAGES = ("models", "optim", "sparse", "data", "configs")
GONE_LAUNCH = ("train", "dryrun", "mesh")
GONE = tuple(f"repro.{p}" for p in GONE_PACKAGES) + tuple(
    f"repro.launch.{m}" for m in GONE_LAUNCH
)
GONE_RE = re.compile(
    r"\b(%s)\b" % "|".join(re.escape(name) for name in GONE)
)


def _modules(package):
    """Dotted names and paths of every module under ``repro.<package>``."""
    root = os.path.join(SRC, "repro", package)
    out = []
    for dirpath, dirnames, filenames in os.walk(root):
        dirnames[:] = sorted(d for d in dirnames if d != "__pycache__")
        for fn in sorted(filenames):
            if not fn.endswith(".py"):
                continue
            path = os.path.join(dirpath, fn)
            parts = os.path.relpath(path, SRC)[:-3].split(os.sep)
            if parts[-1] == "__init__":
                parts = parts[:-1]
            out.append((".".join(parts), path))
    return out


def _imported_names(modname, path):
    """Every module name ``path`` imports, relative imports resolved."""
    with open(path) as f:
        tree = ast.parse(f.read(), path)
    is_pkg = os.path.basename(path) == "__init__.py"
    pkg = modname.split(".") if is_pkg else modname.split(".")[:-1]
    names = []
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            names += [a.name for a in node.names]
        elif isinstance(node, ast.ImportFrom):
            if node.level:
                base = ".".join(pkg[: len(pkg) - node.level + 1])
                mod = f"{base}.{node.module}" if node.module else base
            else:
                mod = node.module
            names.append(mod)
            names += [f"{mod}.{a.name}" for a in node.names]
    return tree, names


@pytest.mark.parametrize(
    "package", ["core", "pipeline", "kernels", "runtime", "launch", "ckpt"]
)
def test_package_imports_and_reaches_no_deleted_module(package):
    mods = _modules(package)
    assert mods, package
    for modname, path in mods:
        importlib.import_module(modname)
        tree, names = _imported_names(modname, path)
        assert not [n for n in names if GONE_RE.match(n)], modname
        texts = [
            node.value for node in ast.walk(tree)
            if isinstance(node, ast.Constant) and isinstance(node.value, str)
        ]
        assert not [t for t in texts if GONE_RE.search(t)], modname
