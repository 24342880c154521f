"""Package layering of ``src/repro``, read from the source with ``ast``.

Each module under ``src/repro`` is one case. Every ``repro.*`` import in it,
at the top of the file or inside a function, must (a) name a module or
package that exists, and a name that module defines, and (b) go to the same
package or to a lower layer of ``LAYERS``. Nothing is imported, so a broken
module fails here by name rather than by an import error somewhere else.
"""
from __future__ import annotations

import ast
import pathlib

import pytest

SRC = pathlib.Path(__file__).resolve().parents[1] / "src"

# Lower rank = lower layer. A module may import its own package or a
# package of lower rank.
LAYERS = {
    "kernels": 0, "obs": 0, "dist": 0, "models": 0,
    "core": 1,
    "index": 2,
    "data": 3,
    "retrieval": 4,
    "serve": 5,
    "eval": 6, "launch": 6, "train": 6,
}
# The offline encoder-training stack: only ``train`` (and the example
# outside the package) may import it.
TRAINING = {"models", "train"}
TRAINING_USERS = {"train"}

# Imports against the layering that the tree still has, as
# (importing module, imported module). Each is a debt in ROADMAP.md,
# Queue 3, "Package back-edges"; its entry goes when the debt is repaid.
BACK_EDGES = {
    ("repro.core.index", "repro.index.compressed"):
        "ROADMAP.md Queue 3 'Package back-edges': the index format decided "
        "in one module",
    ("repro.core.traversal", "repro.index.compressed"):
        "ROADMAP.md Queue 3 'Package back-edges': the index format decided "
        "in one module",
    ("repro.retrieval.engines", "repro.serve.sharded"):
        "ROADMAP.md Queue 3 'Package back-edges': the sharded engine "
        "registered from retrieval",
    ("repro.obs.trace_exec", "repro.core.traversal"):
        "ROADMAP.md Queue 3 'Package back-edges': trace_exec reads the "
        "traversal's stat keys",
}


def _module_name(path: pathlib.Path) -> str:
    parts = path.relative_to(SRC).with_suffix("").parts
    if parts[-1] == "__init__":
        parts = parts[:-1]
    return ".".join(parts)


MODULES = {_module_name(p): p for p in sorted((SRC / "repro").rglob("*.py"))}


def _exists(name: str) -> bool:
    """A module, a package, or a namespace package (no ``__init__``)."""
    return name in MODULES or SRC.joinpath(*name.split(".")).is_dir()


def _top_level_names(tree: ast.Module) -> set[str] | None:
    """Names a module binds at its top level; None if it star-imports."""
    names: set[str] = set()

    def visit(stmts):
        for s in stmts:
            if isinstance(s, (ast.FunctionDef, ast.AsyncFunctionDef,
                              ast.ClassDef)):
                names.add(s.name)
            elif isinstance(s, (ast.Assign, ast.AnnAssign, ast.AugAssign)):
                targets = s.targets if isinstance(s, ast.Assign) else [s.target]
                for t in targets:
                    names.update(n.id for n in ast.walk(t)
                                 if isinstance(n, ast.Name))
            elif isinstance(s, (ast.Import, ast.ImportFrom)):
                for a in s.names:
                    if a.name == "*":
                        return False
                    names.add(a.asname or a.name.split(".")[0])
            elif isinstance(s, (ast.If, ast.Try, ast.With)):
                for block in (s.body, getattr(s, "orelse", []),
                              getattr(s, "finalbody", []),
                              *[h.body for h in getattr(s, "handlers", [])]):
                    if visit(block) is False:
                        return False
        return True

    return names if visit(tree.body) else None


def _imports(name: str, path: pathlib.Path):
    """(line, imported module, names taken from it) for every ``repro``
    import in the module, relative imports resolved."""
    package = name.split(".") if path.name == "__init__.py" \
        else name.split(".")[:-1]
    for node in ast.walk(ast.parse(path.read_text(), str(path))):
        if isinstance(node, ast.Import):
            for a in node.names:
                if a.name.split(".")[0] == "repro":
                    yield node.lineno, a.name, []
        elif isinstance(node, ast.ImportFrom):
            if node.level:
                base = package[:len(package) - (node.level - 1)]
                target = ".".join(base + ([node.module] if node.module
                                          else []))
            else:
                target = node.module or ""
            if target.split(".")[0] == "repro":
                yield node.lineno, target, [a.name for a in node.names]


def _check_names(target: str, names: list[str]) -> list[str]:
    """Names of ``from target import ...`` that are neither a submodule
    nor bound at the top of the target's source."""
    src = MODULES.get(target)  # None for a namespace package
    bound = _top_level_names(ast.parse(src.read_text())) if src else set()
    if bound is None:
        return []
    return [n for n in names
            if n != "*" and n not in bound and not _exists(f"{target}.{n}")]


@pytest.mark.parametrize("module", sorted(MODULES))
def test_module_imports_follow_layers(module):
    path = MODULES[module]
    pkg = module.split(".")[1]
    assert pkg in LAYERS, f"{module}: package {pkg!r} is not in LAYERS"
    faults = []
    for line, target, names in _imports(module, path):
        where = f"{module}:{line} imports {target}"
        if not _exists(target):
            faults.append(f"{where}: no such module")
            continue
        missing = _check_names(target, names)
        if missing:
            faults.append(f"{where}: it defines no {missing}")
        parts = target.split(".")
        if len(parts) < 2 or parts[1] not in LAYERS:
            faults.append(f"{where}: not a package of LAYERS")
            continue
        dst = parts[1]
        if dst == pkg:
            continue
        if dst in TRAINING and pkg not in TRAINING_USERS:
            faults.append(f"{where}: only {sorted(TRAINING_USERS)} may "
                          f"import the training stack")
        elif LAYERS[dst] > LAYERS[pkg] and (module, target) not in BACK_EDGES:
            faults.append(f"{where}: {dst} (layer {LAYERS[dst]}) is above "
                          f"{pkg} (layer {LAYERS[pkg]})")
    assert not faults, "\n".join(faults)


@pytest.mark.parametrize("edge", sorted(BACK_EDGES), ids="->".join)
def test_back_edge_exception_still_needed(edge):
    """An exception that no import uses any more is removed with its
    debt, so the list never hides a new back-edge."""
    module, target = edge
    assert module in MODULES, f"{module} no longer exists"
    targets = {t for _, t, _ in _imports(module, MODULES[module])}
    assert target in targets, f"{module} no longer imports {target}"
