"""Package layering, read from the source with ``ast`` (no JAX import).

One case per module of ``src/repro``:

* only ``repro.launch`` (the entry points) may import ``repro.launch`` —
  the engine's layers never reach up into it, not even lazily;
* every ``repro.*`` module a file imports, at module top or inside a
  function, exists under ``src/repro``.  A function-local import of a
  deleted module would otherwise fail only when that function runs.
"""
from __future__ import annotations

import ast
import pathlib

import pytest

_SRC = pathlib.Path(__file__).resolve().parents[1] / "src"
_FILES = sorted((_SRC / "repro").rglob("*.py"))


def _dotted(path: pathlib.Path) -> str:
    parts = path.relative_to(_SRC).with_suffix("").parts
    return ".".join(parts[:-1] if parts[-1] == "__init__" else parts)


def _imported(tree: ast.AST, module: str, is_pkg: bool) -> set[str]:
    """Every module path named by an ``import`` or ``from`` statement."""
    out = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            out.update(a.name for a in node.names)
        elif isinstance(node, ast.ImportFrom):
            if node.level:
                base = module.split(".")
                base = base[:len(base) - node.level + (1 if is_pkg else 0)]
                name = ".".join(base + ([node.module] if node.module else []))
            else:
                name = node.module
            out.add(name)
            if not (_SRC.joinpath(*name.split(".")) / "__init__.py").is_file():
                # a namespace package has no names of its own: ``from
                # repro.serve import query`` names the module repro.serve.query
                out.update(f"{name}.{a.name}" for a in node.names
                           if _SRC.joinpath(*name.split(".")).is_dir())
    return out


def _exists(name: str) -> bool:
    base = _SRC.joinpath(*name.split("."))
    return base.with_suffix(".py").is_file() or base.is_dir()


@pytest.mark.parametrize("path", _FILES, ids=[_dotted(p) for p in _FILES])
def test_module_imports_respect_layers(path):
    module = _dotted(path)
    tree = ast.parse(path.read_text(), filename=str(path))
    repro = sorted(n for n in _imported(tree, module,
                                        path.name == "__init__.py")
                   if n == "repro" or n.startswith("repro."))
    if not module.startswith("repro.launch"):
        up = [n for n in repro if n.startswith("repro.launch")]
        assert not up, f"{module} imports the entry points: {up}"
    missing = [n for n in repro if not _exists(n)]
    assert not missing, f"{module} imports modules that do not exist: " \
                        f"{missing}"
