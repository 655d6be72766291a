"""Repo-specific AST lint (rule namespace ``RPR``).

Source-level companions to the jaxpr/HLO passes — these catch the bug
classes *before* anything is traced:

``RPR001``  raw ``lax.psum`` inside a sharded-loss function (third
            positional arg named ``ctx`` or name containing
            ``sharded_loss``). Inside the pipeline's
            ``shard_map(check_vma=False)`` region its transpose scales
            gradients by the model-axis size; use ``ctx.psum`` /
            ``psum_replicated`` instead.
``RPR002``  host synchronization (``.item()``, ``np.asarray``,
            ``device_get``) inside a function that is jit-compiled in the
            same module — a silent device->host round-trip per step.
``RPR003``  ``pl.pallas_call`` without an ``interpret=`` argument: the
            kernel cannot run on CPU CI and the call site has no
            plumb-through for it.
``RPR004``  non-static math (float constants, true division, jnp/np calls)
            in a ``BlockSpec`` index map — index maps must stay integer
            grid arithmetic (``//``/``%``) or the lowering silently
            misindexes blocks.

Suppression: ``# noqa: RPR001`` (or bare ``# noqa``) on the flagged line;
the rule-ID namespace is registered with ruff via ``external`` in
pyproject.toml so suppressions stay greppable.

CLI: ``python -m repro.analysis.astlint src/ [--summary]`` — exits 1 on
findings and prints per-rule counts.
"""
from __future__ import annotations

import ast
import dataclasses
import re
import sys
from pathlib import Path
from typing import Dict, Iterable, List, Optional, Sequence

RULES = {
    "RPR001": "raw lax.psum in a sharded loss (use ctx.psum/psum_replicated)",
    "RPR002": "host sync (.item()/np.asarray/device_get) in a jitted function",
    "RPR003": "pl.pallas_call without an interpret= plumb-through",
    "RPR004": "non-static indexing math in a BlockSpec index map",
}

_NOQA_RE = re.compile(r"#\s*noqa(?::\s*(?P<codes>[A-Z]+\d+(?:[,\s]+[A-Z]+\d+)*))?",
                      re.IGNORECASE)


@dataclasses.dataclass(frozen=True)
class AstFinding:
    rule: str
    path: str
    line: int
    col: int
    message: str

    def __str__(self) -> str:
        return f"{self.path}:{self.line}:{self.col}: {self.rule} {self.message}"


def _dotted(node: ast.AST) -> str:
    """'jax.lax.psum' for Attribute/Name chains, '' otherwise."""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return ""


def _is_jit_expr(node: ast.AST) -> bool:
    """jax.jit / jit, possibly wrapped in functools.partial(jax.jit, ...)."""
    d = _dotted(node)
    if d in ("jax.jit", "jit"):
        return True
    if isinstance(node, ast.Call):
        fd = _dotted(node.func)
        if fd in ("functools.partial", "partial") and node.args:
            return _is_jit_expr(node.args[0])
        return _is_jit_expr(node.func)
    return False


def _is_sharded_loss(fn: ast.AST) -> bool:
    if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
        return False
    args = fn.args.posonlyargs + fn.args.args
    if len(args) >= 3 and args[2].arg == "ctx":
        return True
    return "sharded_loss" in fn.name


# float()/int()/bool() on traced values are sync points too, but flagging
# every builtin call would drown real findings — restrict to the explicit
# device->host APIs plus .item()
_HOST_SYNC_EXPLICIT = {"np.asarray", "numpy.asarray", "jax.device_get",
                       "device_get", "np.array", "numpy.array"}


class _Linter(ast.NodeVisitor):
    def __init__(self, path: str, source: str):
        self.path = path
        self.lines = source.splitlines()
        self.findings: List[AstFinding] = []
        self.jit_names: set = set()
        self._fn_stack: List[ast.AST] = []

    # -- pass 1 collected jit-ed function names (module-scoped) --

    def _suppressed(self, rule: str, lineno: int) -> bool:
        if 1 <= lineno <= len(self.lines):
            m = _NOQA_RE.search(self.lines[lineno - 1])
            if m:
                codes = m.group("codes")
                if not codes:
                    return True
                return rule in re.split(r"[,\s]+", codes.upper())
        return False

    def _add(self, rule: str, node: ast.AST, message: str) -> None:
        lineno = getattr(node, "lineno", 1)
        if self._suppressed(rule, lineno):
            return
        self.findings.append(AstFinding(
            rule, self.path, lineno, getattr(node, "col_offset", 0), message))

    # ------------------------------ visitors ------------------------------

    def visit_FunctionDef(self, node: ast.FunctionDef) -> None:
        self._visit_fn(node)

    def visit_AsyncFunctionDef(self, node: ast.AsyncFunctionDef) -> None:
        self._visit_fn(node)

    def _visit_fn(self, node) -> None:
        for dec in node.decorator_list:
            if _is_jit_expr(dec):
                self.jit_names.add(node.name)
        in_jit = node.name in self.jit_names or any(
            getattr(f, "name", None) in self.jit_names
            for f in self._fn_stack)
        self._fn_stack.append(node)
        try:
            if _is_sharded_loss(node):
                self._check_sharded_loss(node)
            if in_jit or node.name in self.jit_names:
                self._check_host_sync(node)
            self.generic_visit(node)
        finally:
            self._fn_stack.pop()

    def visit_Call(self, node: ast.Call) -> None:
        d = _dotted(node.func)
        if d == "pallas_call" or d.endswith(".pallas_call"):
            self._check_pallas_call(node)
        elif d == "BlockSpec" or d.endswith(".BlockSpec"):
            self._check_blockspec(node)
        self.generic_visit(node)

    # ------------------------------- rules --------------------------------

    def _check_sharded_loss(self, fn) -> None:
        for sub in ast.walk(fn):
            if isinstance(sub, ast.Call):
                d = _dotted(sub.func)
                if d in ("jax.lax.psum", "lax.psum"):
                    self._add("RPR001", sub,
                              "raw lax.psum in sharded loss "
                              f"`{fn.name}`; its transpose under "
                              "check_vma=False scales gradients — use "
                              "ctx.psum / psum_replicated")

    def _check_host_sync(self, fn) -> None:
        for sub in ast.walk(fn):
            if not isinstance(sub, ast.Call):
                continue
            d = _dotted(sub.func)
            if d in _HOST_SYNC_EXPLICIT:
                self._add("RPR002", sub,
                          f"`{d}` inside jitted `{fn.name}` forces a "
                          "device->host sync per step")
            elif (isinstance(sub.func, ast.Attribute)
                  and sub.func.attr == "item" and not sub.args):
                self._add("RPR002", sub,
                          f"`.item()` inside jitted `{fn.name}` forces a "
                          "device->host sync per step")

    def _check_pallas_call(self, node: ast.Call) -> None:
        kw_names = {k.arg for k in node.keywords}
        if "interpret" in kw_names or None in kw_names:  # None = **kwargs
            return
        self._add("RPR003", node,
                  "pl.pallas_call without interpret=: plumb an "
                  "`interpret` flag through so the kernel runs on CPU CI")

    def _check_blockspec(self, node: ast.Call) -> None:
        index_map: Optional[ast.AST] = None
        for k in node.keywords:
            if k.arg == "index_map":
                index_map = k.value
        if index_map is None and len(node.args) >= 2:
            index_map = node.args[1]
        if not isinstance(index_map, ast.Lambda):
            return
        for sub in ast.walk(index_map.body):
            bad = None
            if isinstance(sub, ast.BinOp) and isinstance(sub.op, ast.Div):
                bad = "true division (use //)"
            elif isinstance(sub, ast.Constant) and isinstance(sub.value,
                                                             float):
                bad = f"float constant {sub.value!r}"
            elif isinstance(sub, ast.Call):
                d = _dotted(sub.func)
                root = d.split(".")[0]
                if root in ("jnp", "np", "numpy", "jax", "math"):
                    bad = f"`{d}(...)` call"
            if bad is not None:
                self._add("RPR004", sub,
                          f"non-static math in BlockSpec index map: {bad}; "
                          "index maps must stay integer grid arithmetic")


class _JitCollector(ast.NodeVisitor):
    """Names bound via `x = jax.jit(fn)` / decorated defs, module-scoped."""

    def __init__(self):
        self.jit_names: set = set()

    def visit_Assign(self, node: ast.Assign) -> None:
        if isinstance(node.value, ast.Call) and _is_jit_expr(node.value.func):
            if node.value.args and isinstance(node.value.args[0], ast.Name):
                self.jit_names.add(node.value.args[0].id)
        self.generic_visit(node)

    def visit_FunctionDef(self, node: ast.FunctionDef) -> None:
        for dec in node.decorator_list:
            if _is_jit_expr(dec):
                self.jit_names.add(node.name)
        self.generic_visit(node)

    visit_AsyncFunctionDef = visit_FunctionDef


def lint_source(source: str, path: str = "<memory>") -> List[AstFinding]:
    tree = ast.parse(source, filename=path)
    collector = _JitCollector()
    collector.visit(tree)
    linter = _Linter(path, source)
    linter.jit_names = collector.jit_names
    linter.visit(tree)
    return sorted(linter.findings, key=lambda f: (f.path, f.line, f.rule))


def lint_paths(paths: Iterable[str]) -> List[AstFinding]:
    findings: List[AstFinding] = []
    for p in paths:
        root = Path(p)
        files = sorted(root.rglob("*.py")) if root.is_dir() else [root]
        for f in files:
            findings.extend(lint_source(f.read_text(), str(f)))
    return findings


def rule_counts(findings: Sequence[AstFinding]) -> Dict[str, int]:
    counts = {rule: 0 for rule in RULES}
    for f in findings:
        counts[f.rule] = counts.get(f.rule, 0) + 1
    return counts


def main(argv: Optional[Sequence[str]] = None) -> int:
    import argparse
    parser = argparse.ArgumentParser(
        description="repo AST lint (RPR001-RPR004)")
    parser.add_argument("paths", nargs="+")
    parser.add_argument("--summary", action="store_true",
                        help="print per-rule counts (markdown)")
    ns = parser.parse_args(argv)
    findings = lint_paths(ns.paths)
    for f in findings:
        print(f)
    if ns.summary:
        print("| rule | description | findings |")
        print("| --- | --- | --- |")
        for rule, n in rule_counts(findings).items():
            print(f"| {rule} | {RULES[rule]} | {n} |")
    return 1 if findings else 0


if __name__ == "__main__":
    sys.exit(main())
