"""Import layering of the tree, checked statically with ``ast``.

One case per Python file of the package, the benchmarks, the examples and
``chip_smoke.py``.  Each file is parsed, never imported, so imports inside
function bodies are checked too: an import smoke test never reaches them.

(a) Every ``repro.*`` / ``benchmarks.*`` import resolves to a file in the
    tree, and every name taken ``from`` a module is a submodule of it or is
    bound at its top level (or listed in its ``__all__``).
(b) The compiler (``repro.core``) imports nothing of ``repro`` outside
    ``repro.core``: the arrow points from everything else to the compiler.
"""
from __future__ import annotations

import ast
import functools
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parent.parent
SRC = ROOT / "src"
#: top-level packages whose imports must resolve inside the tree
LOCAL_ROOTS = {"repro": SRC, "benchmarks": ROOT}

FILES = sorted(
    [p for d in (SRC / "repro", ROOT / "benchmarks", ROOT / "examples")
     for p in d.rglob("*.py")] + [ROOT / "chip_smoke.py"])


def _module_name(path: Path) -> str:
    base = SRC if path.is_relative_to(SRC) else ROOT
    parts = list(path.relative_to(base).with_suffix("").parts)
    if parts[-1] == "__init__":
        parts.pop()
    return ".".join(parts)


def _resolve(module: str) -> Path | None:
    """The file (or namespace-package directory) of a local module."""
    top = module.split(".")[0]
    base = LOCAL_ROOTS[top] / Path(*module.split("."))
    for cand in (base.with_suffix(".py"), base / "__init__.py"):
        if cand.is_file():
            return cand
    return base if base.is_dir() else None


@functools.lru_cache(maxsize=None)
def _top_level_names(path: Path) -> frozenset[str]:
    """Names a module binds at its top level, plus its ``__all__``."""
    tree = ast.parse(path.read_text(), str(path))
    names: set[str] = set()
    for node in tree.body:
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                             ast.ClassDef)):
            names.add(node.name)
        elif isinstance(node, (ast.Import, ast.ImportFrom)):
            names.update((a.asname or a.name).split(".")[0]
                         for a in node.names)
        elif isinstance(node, (ast.Assign, ast.AnnAssign)):
            targets = node.targets if isinstance(node, ast.Assign) \
                else [node.target]
            for t in targets:
                names.update(n.id for n in ast.walk(t)
                             if isinstance(n, ast.Name))
                if isinstance(t, ast.Name) and t.id == "__all__" \
                        and isinstance(node.value, (ast.List, ast.Tuple)):
                    names.update(e.value for e in node.value.elts
                                 if isinstance(e, ast.Constant))
    return frozenset(names)


def _imports(path: Path) -> list[tuple[int, str, list[str]]]:
    """(line, absolute module, names taken from it) for every import in the
    file, at any depth; relative imports are made absolute."""
    here = _module_name(path)
    package = here if path.name == "__init__.py" else here.rpartition(".")[0]
    out = []
    for node in ast.walk(ast.parse(path.read_text(), str(path))):
        if isinstance(node, ast.Import):
            out += [(node.lineno, a.name, []) for a in node.names]
        elif isinstance(node, ast.ImportFrom):
            module = node.module or ""
            if node.level:
                anchor = package.split(".")
                anchor = anchor[:len(anchor) - (node.level - 1)]
                module = ".".join(anchor + ([module] if module else []))
            out.append((node.lineno, module,
                        [a.name for a in node.names if a.name != "*"]))
    return out


def test_every_file_is_covered():
    assert len(FILES) > 40
    assert {p.name for p in FILES} >= {"api.py", "paper.py", "quickstart.py",
                                       "chip_smoke.py", "layers.py"}


@pytest.mark.parametrize("path", FILES,
                         ids=[str(p.relative_to(ROOT)) for p in FILES])
def test_imports_resolve_and_core_stays_below(path):
    unresolved, upward = [], []
    in_core = (_module_name(path) + ".").startswith("repro.core.")
    for line, module, names in _imports(path):
        if module.split(".")[0] not in LOCAL_ROOTS:
            continue
        target = _resolve(module)
        if target is None:
            unresolved.append(f"{line}: {module}")
            continue
        for name in names:
            if _resolve(f"{module}.{name}") is not None:
                continue
            if target.is_dir() or name not in _top_level_names(target):
                unresolved.append(f"{line}: {module}.{name}")
        if in_core and module.split(".")[0] == "repro" \
                and not (module + ".").startswith("repro.core."):
            upward.append(f"{line}: {module}")
    assert not unresolved, f"imports with no file in the tree: {unresolved}"
    assert not upward, f"repro.core imports outside repro.core: {upward}"
