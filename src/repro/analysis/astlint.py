"""AST lint rules R1–R6 + R8: per-file checkers over parsed source, no imports.

Each rule is a pure function ``(tree, relpath) → [Finding]`` plus a path
predicate saying where it applies; :func:`run_ast_rules` walks a source
tree (the repo, or a fixture tree mirroring its layout — the predicates
only look at *relative* paths, so the checker is testable against
``tests/fixtures/lint/``) and concatenates the findings.

The rules encode the paper's hardware contracts as code invariants — see
``RULE_EXPLAIN`` (surfaced by ``python -m tools.check --explain <rule>``)
for the rationale of each.
"""
from __future__ import annotations

import ast
import dataclasses
from pathlib import Path
from typing import Callable, Iterable

# directories never scanned, wherever they appear
_SKIP_DIRS = {"__pycache__", ".git", "experiments", "fixtures"}

# top-level directories that make up the scanned source tree
SCAN_ROOTS = ("src", "benchmarks", "tools", "examples", "tests")


@dataclasses.dataclass(frozen=True, order=True)
class Finding:
    """One rule violation.

    ``obj`` is the allowlist match key — the relative file path for the
    AST rules, the module name for R7.
    """

    rule: str
    path: str  # posix-style path relative to the scanned root
    line: int
    message: str
    obj: str = ""

    def key(self) -> str:
        return self.obj or self.path

    def render(self) -> str:
        return f"{self.rule} {self.path}:{self.line} {self.message}"


RULE_EXPLAIN = {
    "R1": """\
R1: `shard_map` may only be touched inside repro/distributed/sharding.py.
Its keywords have changed across jax releases (`auto=`/`check_rep=` on
the old `jax.experimental.shard_map`, `axis_names=`/`check_vma=` on
`jax.shard_map`).  `shard_map_compat` in repro/distributed/sharding.py
is the single call site that knows them — every other reference to the
raw name re-opens that break (train_step.py:129 shipped exactly that
bug).""",
    "R2": """\
R2: `repro.kernels.itp_*` packages are importable only by the plasticity
rules and by kernel packages themselves.
The learning rules own their datapaths: engines, models and launchers
select a kernel through the rule hooks (`fused_update_from_readout`,
`sparse_update_from_readout`, ...) and `kernels.dispatch`, never by
reaching into a kernel package.  A direct import hard-wires one rule
family's layout into a consumer and breaks the rule × backend matrix.
Rule-neutral helpers (event lists, im2col) re-export from
`repro.kernels.dispatch` — import them from there.""",
    "R3": """\
R3: no literal `interpret=True/False` defaults in kernel ops wrappers.
`interpret` must default to None and resolve via
`dispatch.default_interpret()`: the Pallas interpreter is a CPU-only
fallback, and a baked-in `True` silently runs the interpreter on real
accelerators (a silent orders-of-magnitude slowdown), while a baked-in
`False` crashes CPU CI.  Applies to `src/repro/kernels/**/ops.py` — the
public wrappers; `kernel.py` internals receive the resolved flag.""",
    "R4": """\
R4: one-argument `jnp.where(mask)` requires a static `size=`.
Without `size`, the result shape depends on runtime data, which fails
under jit and contradicts the paper's fixed-capacity event queues — the
hardware has a static number of event slots per step.  Use
`jnp.where(mask, size=cap, fill_value=n)` (the itp_sparse.events
pattern) so event extraction stays a static-shape operation.""",
    "R5": """\
R5: test modules import `_hypothesis_compat`, never `hypothesis` directly.
CI runs the suite both with and without hypothesis installed; the compat
shim degrades property tests to single-example runs when the package is
absent.  A direct `import hypothesis` makes the whole module un-collectable
in the minimal environment.""",
    "R6": """\
R6: benchmarks write tracked BENCH_*.json via `bench_io.update_bench_json`.
The tracked BENCH files are merged read-modify-write artifacts shared by
every benchmark module and diffed by CI; a raw `json.dump`/`open(...,"w")`
of a BENCH_ path clobbers the other modules' sections and races parallel
writers.  Per-run outputs under the experiment out-dir are fine — the
rule only fires on BENCH_-prefixed paths.""",
    "R7": """\
R7: every module under src/repro must be statically reachable from an
entry point (repro.launch.*, examples/, benchmarks/, tools/, tests/).
Unreachable modules are dead code that still bit-rots against the moving
APIs and silently escapes every test tier.  The tracked baseline lists
the known orphans (e.g. the dynamically-imported LM arch configs) with a
justification each; the list may only shrink.""",
    "R8": """\
R8: rule datapath hooks are called only inside repro/plasticity/.
`kernel_readout` / `kernel_readout_axes` / `magnitudes_from_readout`,
the `*_from_readout` hooks, and the session word-serialization pair
(`serve_words` / `state_from_words`) are the LearningRule ↔ kernel/store
seam; engines, models, launchers, the serving layer, benchmarks and
tests dispatch through the `plasticity.apply` layer (`make_plan` /
`UpdatePlan` / `apply_update`), which owns backend resolution,
packed-vs-unpacked readout selection and the dense / conv / sharded /
session shape variants exactly once.  A direct hook call re-creates the
per-consumer branch sprawl the dispatch layer collapsed and silently
skips plan-level invariants (the silent-step skip, event-list capping,
readout layout selection).""",
}


def _dotted(node: ast.AST) -> str | None:
    """'jnp.where' for Attribute(Name) chains; None for anything else."""
    parts: list[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


# ---------------------------------------------------------------------------
# R1 — shard_map only inside the compat shim
# ---------------------------------------------------------------------------


def _applies_r1(relpath: str) -> bool:
    return relpath != "src/repro/distributed/sharding.py"


def _check_r1(tree: ast.AST, relpath: str) -> list[Finding]:
    out = []
    for node in ast.walk(tree):
        hit = None
        if isinstance(node, ast.Attribute) and node.attr == "shard_map":
            hit = f"`{_dotted(node) or '...shard_map'}`"
        elif isinstance(node, ast.Name) and node.id == "shard_map":
            hit = "`shard_map`"
        elif isinstance(node, ast.ImportFrom):
            names = [a.name for a in node.names]
            if (node.module or "").split(".")[-1] == "shard_map" or "shard_map" in names:
                hit = f"import from `{node.module}`"
        elif isinstance(node, ast.Import):
            for a in node.names:
                if "shard_map" in a.name.split("."):
                    hit = f"`import {a.name}`"
        if hit:
            msg = f"{hit} outside repro/distributed/sharding.py — use shard_map_compat"
            out.append(Finding("R1", relpath, node.lineno, msg, relpath))
    return out


# ---------------------------------------------------------------------------
# R2 — kernel packages only via rule hooks / dispatch re-exports
# ---------------------------------------------------------------------------


def _applies_r2(relpath: str) -> bool:
    if not relpath.startswith("src/repro/"):
        return False
    return not relpath.startswith(("src/repro/kernels/", "src/repro/plasticity/"))


def _is_itp_import(module: str, names: Iterable[str] = ()) -> bool:
    if module.startswith("repro.kernels.itp_"):
        return True
    return module == "repro.kernels" and any(n.startswith("itp_") for n in names)


def _check_r2(tree: ast.AST, relpath: str) -> list[Finding]:
    out = []
    for node in ast.walk(tree):
        bad = None
        if isinstance(node, ast.Import):
            for a in node.names:
                if _is_itp_import(a.name):
                    bad = a.name
        elif isinstance(node, ast.ImportFrom) and node.level == 0:
            mod = node.module or ""
            if _is_itp_import(mod, [a.name for a in node.names]):
                bad = mod
        if bad:
            msg = f"direct kernel-package import `{bad}` — use rule hooks or kernels.dispatch"
            out.append(Finding("R2", relpath, node.lineno, msg, relpath))
    return out


# ---------------------------------------------------------------------------
# R3 — no literal interpret defaults in ops wrappers
# ---------------------------------------------------------------------------


def _applies_r3(relpath: str) -> bool:
    return relpath.startswith("src/repro/kernels/") and relpath.endswith("/ops.py")


def _check_r3(tree: ast.AST, relpath: str) -> list[Finding]:
    out = []
    for node in ast.walk(tree):
        if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        a = node.args
        pairs = list(zip(a.kwonlyargs, a.kw_defaults))
        pos = a.posonlyargs + a.args
        n_no_default = len(pos) - len(a.defaults)
        pairs += list(zip(pos[n_no_default:], a.defaults))
        for arg, default in pairs:
            if arg.arg != "interpret":
                continue
            if not (isinstance(default, ast.Constant) and isinstance(default.value, bool)):
                continue
            msg = f"`{node.name}` defaults interpret={default.value} — default to None instead"
            out.append(Finding("R3", relpath, default.lineno, msg, relpath))
    return out


# ---------------------------------------------------------------------------
# R4 — one-arg jnp.where needs a static size
# ---------------------------------------------------------------------------


def _applies_r4(relpath: str) -> bool:
    return relpath.startswith("src/repro/")


def _check_r4(tree: ast.AST, relpath: str) -> list[Finding]:
    out = []
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        if _dotted(node.func) not in ("jnp.where", "jax.numpy.where"):
            continue
        if len(node.args) != 1:
            continue  # 3-arg select form: static shape
        if any(kw.arg == "size" for kw in node.keywords):
            continue
        msg = "one-arg jnp.where without size= — pass size=cap, fill_value=n"
        out.append(Finding("R4", relpath, node.lineno, msg, relpath))
    return out


# ---------------------------------------------------------------------------
# R5 — tests go through the hypothesis compat shim
# ---------------------------------------------------------------------------


def _applies_r5(relpath: str) -> bool:
    return relpath.startswith("tests/") and not relpath.endswith("_hypothesis_compat.py")


def _check_r5(tree: ast.AST, relpath: str) -> list[Finding]:
    out = []
    for node in ast.walk(tree):
        bad = None
        if isinstance(node, ast.Import):
            for a in node.names:
                if a.name == "hypothesis" or a.name.startswith("hypothesis."):
                    bad = a.name
        elif isinstance(node, ast.ImportFrom) and node.level == 0:
            mod = node.module or ""
            if mod == "hypothesis" or mod.startswith("hypothesis."):
                bad = mod
        if bad:
            msg = f"direct `{bad}` import — go through _hypothesis_compat"
            out.append(Finding("R5", relpath, node.lineno, msg, relpath))
    return out


# ---------------------------------------------------------------------------
# R6 — tracked BENCH files only via bench_io
# ---------------------------------------------------------------------------


def _applies_r6(relpath: str) -> bool:
    return relpath.startswith("benchmarks/") and not relpath.endswith("bench_io.py")


def _bench_literal(node: ast.AST) -> bool:
    for n in ast.walk(node):
        is_str = isinstance(n, ast.Constant) and isinstance(n.value, str)
        if is_str and n.value.startswith("BENCH_"):
            return True
    return False


def _opens_for_write(node: ast.Call) -> bool:
    modes = list(node.args[1:2]) + [kw.value for kw in node.keywords if kw.arg == "mode"]
    for m in modes:
        if isinstance(m, ast.Constant) and isinstance(m.value, str) and set(m.value) & set("wax"):
            return True
    return False


def _check_r6(tree: ast.AST, relpath: str) -> list[Finding]:
    out = []
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        name = _dotted(node.func)
        if name in ("json.dump", "json.dumps") and _bench_literal(node):
            msg = f"`{name}` targeting a BENCH_ file — use bench_io.update_bench_json"
            out.append(Finding("R6", relpath, node.lineno, msg, relpath))
        elif name == "open" and _bench_literal(node) and _opens_for_write(node):
            msg = "`open` of a BENCH_ file for writing — use bench_io.update_bench_json"
            out.append(Finding("R6", relpath, node.lineno, msg, relpath))
    return out


# ---------------------------------------------------------------------------
# R8 — rule datapath hooks only inside the plasticity dispatch layer
# ---------------------------------------------------------------------------

# the LearningRule ↔ kernel seam: the readout views, every
# *_from_readout datapath hook, and the session word-serialization pair
# the serving layer's per-user state rides on (see repro/plasticity/base.py)
_R8_HOOKS = frozenset({
    "kernel_readout",
    "kernel_readout_axes",
    "magnitudes_from_readout",
    "fused_update_from_readout",
    "fused_delta_from_readout",
    "conv_delta_from_readout",
    "sparse_update_from_readout",
    "sparse_delta_from_readout",
    "sparse_conv_delta_from_readout",
    "serve_words",
    "state_from_words",
})


def _applies_r8(relpath: str) -> bool:
    return not relpath.startswith("src/repro/plasticity/")


def _check_r8(tree: ast.AST, relpath: str) -> list[Finding]:
    # syntactic and receiver-agnostic (like R4): any `<expr>.<hook>(...)`
    # call site counts — defining a hook *method* on a rule class is fine,
    # calling one outside the dispatch layer is not
    out = []
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        func = node.func
        if isinstance(func, ast.Attribute) and func.attr in _R8_HOOKS:
            msg = (f"rule hook `.{func.attr}(...)` outside repro/plasticity/ "
                   f"— dispatch through plasticity.apply (make_plan/UpdatePlan)")
            out.append(Finding("R8", relpath, node.lineno, msg, relpath))
    return out


# ---------------------------------------------------------------------------
# driver
# ---------------------------------------------------------------------------


AST_RULES: dict[str, tuple[Callable[[str], bool], Callable[[ast.AST, str], list[Finding]]]] = {
    "R1": (_applies_r1, _check_r1),
    "R2": (_applies_r2, _check_r2),
    "R3": (_applies_r3, _check_r3),
    "R4": (_applies_r4, _check_r4),
    "R5": (_applies_r5, _check_r5),
    "R6": (_applies_r6, _check_r6),
    "R8": (_applies_r8, _check_r8),
}


def iter_source_files(root: Path) -> list[Path]:
    files = []
    for top in SCAN_ROOTS:
        base = root / top
        if not base.is_dir():
            continue
        for p in sorted(base.rglob("*.py")):
            rel = p.relative_to(root)
            if any(part in _SKIP_DIRS or part.startswith(".") for part in rel.parts):
                continue
            files.append(p)
    return files


def run_ast_rules(root: Path, rules: Iterable[str] | None = None) -> list[Finding]:
    """Run the AST rules (None = all of R1–R6 + R8) over the tree at ``root``."""
    selected = {r: AST_RULES[r] for r in (AST_RULES if rules is None else rules)}
    findings: list[Finding] = []
    for path in iter_source_files(root):
        rel = path.relative_to(root).as_posix()
        applicable = {r: chk for r, (pred, chk) in selected.items() if pred(rel)}
        if not applicable:
            continue
        try:
            tree = ast.parse(path.read_text(), filename=rel)
        except SyntaxError as e:
            findings.append(Finding("PARSE", rel, e.lineno or 0, f"syntax error: {e.msg}", rel))
            continue
        for check in applicable.values():
            findings.extend(check(tree, rel))
    return sorted(findings)
