"""d4mlint — AST lint for host/device anti-patterns.

The HLO contract checker (:mod:`~repro.analysis.hlo_contracts`) catches
what a *compiled* program does; this pass catches what never reaches the
compiler: host-side Python that silently materializes traced values or
serializes over nnz.  Rules, each an ``ast`` walk over device scopes —
functions decorated with ``jax.jit``/``shard_map`` (or passed to
``shard_map(...)``/``pallas_call(...)``), including their nested defs:

* **D4M101** — host materialization of a traced value inside a device
  scope: ``np.asarray`` / ``np.array`` / ``np.<anything>`` calls on
  names bound inside the scope.  NumPy on a tracer either fails or
  silently constant-folds a transfer; device code uses ``jnp``.
* **D4M102** — explicit host round-trips in device scope:
  ``jax.device_get`` / ``.block_until_ready()`` / ``.item()`` /
  ``float()`` / ``int()`` on expressions.  These synchronize the stream
  the contract checker proves we never need.
* **D4M103** — a Python ``for``/``while`` loop over nnz-like bounds
  (``range(... nnz ...)`` / ``range(len(rows))`` …) in a device scope:
  serializes a vectorizable sweep into O(nnz) dispatches/trace length.
* **D4M104** — a kernel ``ops.py`` (``src/repro/kernels/*/ops.py``)
  missing the ref/interpret/pallas dispatch triple: every kernel entry
  must be runnable on CPU (``ref``), debuggable (``interpret``), and
  fast (``pallas``).

Suppressions::

    # d4mlint: disable=D4M101,D4M103     (file-level, any line)
    some_call()  # d4mlint: ignore[D4M102]   (this line only)

Run it: ``python -m repro.analysis.lint [paths...]`` (defaults to
``src/repro``); exits 1 on findings.  ``tools/d4mcheck`` runs it after
the contract sweep, and CI fails on any new finding.
"""
from __future__ import annotations

import ast
import dataclasses
import re
import sys
from pathlib import Path
from typing import List, Optional, Sequence, Set

RULES = {
    "D4M101": "numpy host materialization inside a device scope",
    "D4M102": "host round-trip (device_get/block_until_ready/item) "
              "inside a device scope",
    "D4M103": "Python loop over nnz inside a device scope",
    "D4M104": "kernel ops.py missing the ref/interpret/pallas "
              "dispatch triple",
}

_DISABLE_RE = re.compile(r"#\s*d4mlint:\s*disable=([\w,\s]+)")
_IGNORE_RE = re.compile(r"#\s*d4mlint:\s*ignore\[([\w,\s]+)\]")
_NNZ_NAME = re.compile(r"nnz|n_nz|num_nonzero", re.I)


@dataclasses.dataclass(frozen=True)
class Finding:
    path: str
    line: int
    rule: str
    message: str

    def __str__(self) -> str:
        return f"{self.path}:{self.line}: {self.rule} {self.message}"


# --------------------------------------------------------------------------
# Device-scope discovery
# --------------------------------------------------------------------------

def _dotted(node: ast.AST) -> str:
    """Best-effort dotted name of an expression (``jax.jit`` -> "jax.jit")."""
    if isinstance(node, ast.Name):
        return node.id
    if isinstance(node, ast.Attribute):
        base = _dotted(node.value)
        return f"{base}.{node.attr}" if base else node.attr
    if isinstance(node, ast.Call):
        return _dotted(node.func)
    return ""


_DEVICE_DECOS = ("jit", "shard_map", "pmap", "vmap_of_jit", "kernel")


def _is_device_decorator(deco: ast.AST) -> bool:
    name = _dotted(deco)
    last = name.rsplit(".", 1)[-1]
    if last in ("jit", "shard_map", "pmap"):
        return True
    # functools.partial(shard_map, ...) / partial(jax.jit, ...)
    if isinstance(deco, ast.Call) and _dotted(deco.func).endswith("partial"):
        for arg in deco.args[:1]:
            if _dotted(arg).rsplit(".", 1)[-1] in ("jit", "shard_map",
                                                   "pmap"):
                return True
    return False


def _collect_device_scopes(tree: ast.Module) -> Set[ast.AST]:
    """Function defs whose body traces on device: decorated with
    jit/shard_map (incl. via partial) or passed to shard_map()/
    pallas_call(); nested defs inherit the scope."""
    scopes: Set[ast.AST] = set()
    defs_by_name = {}

    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            defs_by_name.setdefault(node.name, node)
            if any(_is_device_decorator(d) for d in node.decorator_list):
                scopes.add(node)
        elif isinstance(node, ast.Call):
            callee = _dotted(node.func).rsplit(".", 1)[-1]
            if callee in ("shard_map", "pallas_call"):
                for arg in node.args[:1]:
                    target = defs_by_name.get(_dotted(arg))
                    if target is not None:
                        scopes.add(target)
                    elif isinstance(arg, ast.Lambda):
                        scopes.add(arg)

    # close over nested function defs
    out: Set[ast.AST] = set()
    for scope in scopes:
        for node in ast.walk(scope):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                                 ast.Lambda)):
                out.add(node)
    return out


# --------------------------------------------------------------------------
# Rules
# --------------------------------------------------------------------------

def _scope_findings(scope: ast.AST, path: str) -> List[Finding]:
    out: List[Finding] = []
    for node in ast.walk(scope):
        if isinstance(node, ast.Call):
            name = _dotted(node.func)
            parts = name.split(".")
            if parts[0] in ("np", "numpy") and len(parts) > 1:
                out.append(Finding(
                    path, node.lineno, "D4M101",
                    f"`{name}(...)` on (potentially traced) values — "
                    f"use jnp inside jit/shard_map bodies"))
            last = parts[-1]
            if last in ("device_get", "block_until_ready", "item"):
                out.append(Finding(
                    path, node.lineno, "D4M102",
                    f"`{name}(...)` forces a host round-trip inside a "
                    f"device scope"))
        elif isinstance(node, (ast.For, ast.While)):
            bound = ""
            if isinstance(node, ast.For) and isinstance(node.iter, ast.Call):
                if _dotted(node.iter.func).rsplit(".", 1)[-1] == "range":
                    bound = ast.dump(node.iter)
            elif isinstance(node, ast.While):
                bound = ast.dump(node.test)
            if bound and _NNZ_NAME.search(bound):
                out.append(Finding(
                    path, node.lineno, "D4M103",
                    "Python loop bounded by nnz in a device scope — "
                    "O(nnz) trace length; vectorize or lax.scan"))
    return out


def _kernel_triple_findings(tree: ast.Module, text: str,
                            path: str) -> List[Finding]:
    """D4M104: kernels/*/ops.py must dispatch ref AND interpret AND
    pallas (string-literal impl names in the module; a call of a
    ``*_pallas`` kernel counts as the pallas path)."""
    p = Path(path)
    if p.name != "ops.py" or "kernels" not in p.parts:
        return []
    impls = set(re.findall(r'"(ref|interpret|pallas)"', text))
    if re.search(r"\w_pallas\(", text):
        impls.add("pallas")
    missing = {"ref", "interpret", "pallas"} - impls
    if missing:
        return [Finding(
            path, 1, "D4M104",
            f"kernel dispatch triple incomplete: no "
            f"{'/'.join(sorted(missing))} path (every kernel needs "
            f"ref + interpret + pallas)")]
    return []


# --------------------------------------------------------------------------
# Driver
# --------------------------------------------------------------------------

def _suppressions(text: str):
    disabled: Set[str] = set()
    line_ignores = {}
    for i, line in enumerate(text.splitlines(), start=1):
        m = _DISABLE_RE.search(line)
        if m:
            disabled.update(r.strip() for r in m.group(1).split(",")
                            if r.strip())
        m = _IGNORE_RE.search(line)
        if m:
            line_ignores[i] = {r.strip() for r in m.group(1).split(",")
                               if r.strip()}
    return disabled, line_ignores


def lint_file(path: str, text: Optional[str] = None) -> List[Finding]:
    if text is None:
        text = Path(path).read_text()
    try:
        tree = ast.parse(text)
    except SyntaxError as e:
        return [Finding(path, e.lineno or 1, "D4M000",
                        f"syntax error: {e.msg}")]
    disabled, line_ignores = _suppressions(text)

    findings: List[Finding] = []
    seen = set()
    for scope in _collect_device_scopes(tree):
        for f in _scope_findings(scope, path):
            key = (f.line, f.rule, f.message)
            if key not in seen:          # nested scopes overlap
                seen.add(key)
                findings.append(f)
    findings.extend(_kernel_triple_findings(tree, text, path))

    return sorted(
        (f for f in findings
         if f.rule not in disabled
         and f.rule not in line_ignores.get(f.line, ())),
        key=lambda f: (f.line, f.rule))


def lint_paths(paths: Sequence[str]) -> List[Finding]:
    """Lint files / directory trees (``*.py``, recursively)."""
    out: List[Finding] = []
    for p in paths:
        path = Path(p)
        files = sorted(path.rglob("*.py")) if path.is_dir() else [path]
        for f in files:
            out.extend(lint_file(str(f)))
    return out


def main(argv: Optional[Sequence[str]] = None) -> int:
    args = list(argv if argv is not None else sys.argv[1:])
    paths = args or ["src/repro"]
    findings = lint_paths(paths)
    for f in findings:
        print(f)
    print(f"d4mlint: {len(findings)} finding(s) in "
          f"{', '.join(paths)}")
    return 1 if findings else 0


if __name__ == "__main__":
    raise SystemExit(main())
