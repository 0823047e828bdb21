"""R7 — static import-graph reachability (dead-code report).

Builds the module map for everything under ``src/`` and walks the static
import edges from the entry-point surfaces: ``repro.launch.*`` (the CLI),
plus every script/module under ``benchmarks/``, ``examples/``,
``tools/`` and ``tests/``.  Anything under ``src/`` not reached is an
orphan finding keyed by *module name* (the allowlist records known
orphans, such as modules loaded only via ``importlib`` strings, with a
justification each).

Conservative choices: ``from pkg import name`` marks ``pkg`` and, when
``pkg.name`` is a known module, that module too; importing any module
marks its ancestor packages (their ``__init__`` executes on import);
dynamic ``importlib`` loads are *not* followed — that is the point of
the tracked baseline.
"""
from __future__ import annotations

import ast
from pathlib import Path

from repro.analysis.astlint import Finding, iter_source_files

ENTRY_PACKAGES = ("repro.launch",)
ENTRY_DIRS = ("benchmarks", "examples", "tools", "tests")


def module_name(rel: str) -> str | None:
    """'src/repro/core/engine.py' → 'repro.core.engine' (None if not src)."""
    if not rel.startswith("src/") or not rel.endswith(".py"):
        return None
    parts = rel[len("src/") : -len(".py")].split("/")
    if parts[-1] == "__init__":
        parts = parts[:-1]
    return ".".join(parts)


def _imports_of(tree: ast.AST, self_pkg: str) -> set[str]:
    """Absolute dotted names a module's import statements mention.

    ``self_pkg`` is the importing module's package (``a.b`` for module
    ``a.b.c`` or package ``a.b`` itself) — the anchor for relative
    imports: level N strips N-1 trailing components from it.
    """
    out: set[str] = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for a in node.names:
                out.add(a.name)
        elif isinstance(node, ast.ImportFrom):
            if node.level == 0:
                base = node.module or ""
            else:
                parts = self_pkg.split(".") if self_pkg else []
                keep = max(0, len(parts) - (node.level - 1))
                base = ".".join(parts[:keep])
                if node.module:
                    base = f"{base}.{node.module}" if base else node.module
            if base:
                out.add(base)
                for a in node.names:
                    out.add(f"{base}.{a.name}")
    return out


def _ancestors(mod: str) -> list[str]:
    parts = mod.split(".")
    return [".".join(parts[:i]) for i in range(1, len(parts) + 1)]


def _is_entry_module(mod: str) -> bool:
    return any(mod == pkg or mod.startswith(pkg + ".") for pkg in ENTRY_PACKAGES)


def run_import_graph(root: Path) -> list[Finding]:
    """Return one R7 finding per orphan module under ``src/``."""
    files = iter_source_files(root)
    modules: dict[str, str] = {}  # module name → relpath
    trees: dict[str, ast.AST] = {}  # relpath → parsed tree
    for p in files:
        rel = p.relative_to(root).as_posix()
        try:
            trees[rel] = ast.parse(p.read_text(), filename=rel)
        except SyntaxError:
            continue  # reported by the AST layer
        mod = module_name(rel)
        if mod:
            modules[mod] = rel

    entry_rels = [rel for rel in trees if rel.split("/")[0] in ENTRY_DIRS]
    entry_mods = [m for m in modules if _is_entry_module(m)]

    reachable: set[str] = set()
    queue: list[str] = []

    def mark(dotted: str) -> None:
        for anc in _ancestors(dotted):
            if anc in modules and anc not in reachable:
                reachable.add(anc)
                queue.append(anc)

    def pkg_of(mod: str) -> str:
        if modules[mod].endswith("__init__.py"):
            return mod
        return mod.rpartition(".")[0]

    for m in entry_mods:
        mark(m)
    for rel in entry_rels:
        for d in _imports_of(trees[rel], ""):
            mark(d)
    while queue:
        mod = queue.pop()
        tree = trees.get(modules[mod])
        if tree is None:
            continue
        for d in _imports_of(tree, pkg_of(mod)):
            mark(d)

    out = []
    for mod in sorted(set(modules) - reachable):
        msg = f"module `{mod}` unreachable from any entry point (see --explain R7)"
        out.append(Finding("R7", modules[mod], 1, msg, mod))
    return sorted(out)
