"""Rule family ``config``: configuration and fault-taxonomy hygiene.

``config.unread`` — every ``Var("name", ...)`` registration must have at
least one literal read site (``config.get("name")`` / ``.set("name", ..)``)
outside its own registration; a knob nothing reads is dead weight that
will silently diverge from the code.

``config.undocumented`` — every registered var must be mentioned in
README.md or deploy/README.md so operators can discover it.

``config.errno-taxonomy`` — every errno named in a ``*_ERRNOS`` frozenset
must exist in the :mod:`errno` module, and the set's class token must be
a member of the ``ErrorClass`` enum (so classification and taxonomy can
never drift apart).

``config.bounds`` (the config-bounds rule, ISSUE 18) — every numeric
(int/size/float) Var read by the online autotuner (a literal
``config.get`` site in ``autotune.py``) must declare BOTH ``minval``
and ``maxval``: the controller takes its hard clamp range from the
Var's declared bounds, so an unbounded controlled knob is a knob the
hill-climb may walk to absurdity.  bool/str vars are exempt (they gate
behavior; the climber never steps them).
"""

from __future__ import annotations

import ast
import errno as _errno
from typing import List, Optional, Set, Tuple

from .core import Finding, Project, SourceFile

__all__ = ["run"]


def _str_const(node: ast.AST) -> Optional[str]:
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return node.value
    return None


def _registrations(project: Project) -> List[Tuple[SourceFile, int, str]]:
    out = []
    for src, tree in project.iter_trees():
        for node in ast.walk(tree):
            if (isinstance(node, ast.Call) and isinstance(node.func, ast.Name)
                    and node.func.id == "Var" and node.args):
                name = _str_const(node.args[0])
                if name is not None:
                    out.append((src, node.lineno, name))
    return out


def _literal_accesses(project: Project) -> Set[str]:
    """Names passed as the literal first argument of any ``.get``/``.set``/
    ``.peek`` call — the read/write sites the unread check accepts."""
    got: Set[str] = set()
    for _src, tree in project.iter_trees():
        for node in ast.walk(tree):
            if (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)
                    and node.func.attr in ("get", "set", "peek")
                    and node.args):
                name = _str_const(node.args[0])
                if name is not None:
                    got.add(name)
    return got


def _check_vars(project: Project, findings: List[Finding]) -> None:
    regs = _registrations(project)
    if not regs:
        return
    accessed = _literal_accesses(project)
    docs = " ".join(project.doc_texts.values())
    for src, line, name in regs:
        if name not in accessed:
            findings.append(Finding(
                src.relpath, line, "config.unread",
                f"config var '{name}' is registered but never read "
                f"(no literal config.get/set site in the package)"))
        if docs and name not in docs:
            findings.append(Finding(
                src.relpath, line, "config.undocumented",
                f"config var '{name}' is not documented in "
                f"{'/'.join(sorted(project.doc_texts))}"))


def _autotune_reads(project: Project) -> Set[str]:
    """Var names read via a literal ``config.get("...")`` inside the
    controller module — the knobs whose declared bounds are load-bearing."""
    got: Set[str] = set()
    for src, tree in project.iter_trees():
        if not src.relpath.endswith("autotune.py"):
            continue
        for node in ast.walk(tree):
            if (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)
                    and node.func.attr == "get" and node.args):
                name = _str_const(node.args[0])
                if name is not None:
                    got.add(name)
    return got


def _check_bounds(project: Project, findings: List[Finding]) -> None:
    controlled = _autotune_reads(project)
    if not controlled:
        return
    for src, tree in project.iter_trees():
        for node in ast.walk(tree):
            if not (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Name)
                    and node.func.id == "Var" and node.args):
                continue
            name = _str_const(node.args[0])
            if name is None or name not in controlled:
                continue
            kind = _str_const(node.args[2]) if len(node.args) > 2 else None
            if kind not in ("int", "size", "float"):
                continue
            declared = {kw.arg for kw in node.keywords
                        if not (isinstance(kw.value, ast.Constant)
                                and kw.value.value is None)}
            missing = [b for b in ("minval", "maxval")
                       if b not in declared]
            if missing:
                findings.append(Finding(
                    src.relpath, node.lineno, "config.bounds",
                    f"config var '{name}' is read by the autotune "
                    f"controller but declares no {'/'.join(missing)} — "
                    f"the climber clamps to declared bounds, so this "
                    f"knob is unbounded"))


def _error_class_members(project: Project) -> Set[str]:
    for _src, tree in project.iter_trees():
        for node in ast.walk(tree):
            if isinstance(node, ast.ClassDef) and node.name == "ErrorClass":
                members: Set[str] = set()
                for stmt in node.body:
                    if isinstance(stmt, ast.Assign):
                        for t in stmt.targets:
                            if isinstance(t, ast.Name):
                                members.add(t.id)
                return members
    return set()


def _check_errnos(project: Project, findings: List[Finding]) -> None:
    classes = _error_class_members(project)
    for src, tree in project.iter_trees():
        for node in ast.walk(tree):
            if not (isinstance(node, ast.Assign) and node.targets
                    and isinstance(node.targets[0], ast.Name)
                    and node.targets[0].id.endswith("_ERRNOS")):
                continue
            set_name = node.targets[0].id
            token = set_name[:-len("_ERRNOS")].lstrip("_")
            if classes and token not in classes:
                findings.append(Finding(
                    src.relpath, node.lineno, "config.errno-taxonomy",
                    f"errno set '{set_name}' names class '{token}' which "
                    f"is not an ErrorClass member"))
            for sub in ast.walk(node.value):
                if isinstance(sub, ast.Attribute) \
                        and sub.attr.startswith("E"):
                    if not hasattr(_errno, sub.attr):
                        findings.append(Finding(
                            src.relpath, sub.lineno, "config.errno-taxonomy",
                            f"'{sub.attr}' in {set_name} is not a known "
                            f"errno name"))


def run(project: Project) -> List[Finding]:
    findings: List[Finding] = []
    _check_vars(project, findings)
    _check_bounds(project, findings)
    _check_errnos(project, findings)
    return findings
