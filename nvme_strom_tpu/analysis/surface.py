"""Rule family ``surface``: the stats/trace export surface is complete.

Every counter the runtime bumps must be declared in ``STAT_FIELDS``
(``surface.undeclared``); every declared ``nr_*``/``bytes_*`` counter must
be renderable by ``tpu_stat`` and the Prometheus surface
(``surface.stat-render``, ``surface.prom-render``); every trace event kind
emitted anywhere must appear in the recorder schema with the right kind,
schema entries must not go stale, and ``*_begin``/``*_end`` span kinds
must pair (``surface.trace-*``); every ``NSTPU_BACKEND_*`` rung in the
native header must appear in both backend legends (``surface.backend``).

Anchors are discovered by content: the file assigning ``STAT_FIELDS`` is
the stats contract, the file defining ``render_prometheus`` is the prom
surface, the file assigning ``EVENT_SCHEMA`` is the recorder schema, and
the file named ``tpu_stat.py`` is the human renderer.  A generic
``for k in sorted(...)`` dump covers every counter; only counters a
renderer special-cases (skips in its generic loop) need explicit
literal coverage.
"""

from __future__ import annotations

import ast
import re
from typing import Dict, List, Optional, Set, Tuple

from .core import Finding, Project, SourceFile

__all__ = ["run"]

#: stats-object methods whose first (literal) argument is a counter name
_STATS_MUTATORS = {"add", "gauge_set", "gauge_add", "gauge_max"}


def _str_const(node: ast.AST) -> Optional[str]:
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return node.value
    return None


def _collect_stat_fields(project: Project
                         ) -> Tuple[Optional[SourceFile], int, Set[str]]:
    for src, tree in project.iter_trees():
        for node in ast.walk(tree):
            targets = []
            if isinstance(node, ast.Assign):
                targets, value = node.targets, node.value
            elif isinstance(node, ast.AnnAssign) and node.value is not None:
                targets, value = [node.target], node.value
            else:
                continue
            for t in targets:
                if isinstance(t, ast.Name) and t.id == "STAT_FIELDS":
                    names = set()
                    if isinstance(value, (ast.Tuple, ast.List)):
                        for el in value.elts:
                            s = _str_const(el)
                            if s:
                                names.add(s)
                    return src, node.lineno, names
    return None, 0, set()


def _string_constants(node: ast.AST) -> Set[str]:
    """Every string literal under ``node``, including f-string fragments."""
    out: Set[str] = set()
    for sub in ast.walk(node):
        s = _str_const(sub)
        if s is not None:
            out.add(s)
    return out


def _covered(field: str, literals: Set[str]) -> bool:
    """A counter is covered by a renderer when its full name appears, or
    when it composes as an f-string prefix (ending ``_``) plus a literal
    suffix, the labeled-series idiom ``f"nr_landing_{path}"``."""
    if field in literals:
        return True
    for p in literals:
        if p.endswith("_") and field.startswith(p) and field[len(p):] in literals:
            return True
    return False


def _has_generic_dump(func_or_tree: ast.AST) -> bool:
    """A ``for k in sorted(...)`` loop renders every counter it is handed."""
    for node in ast.walk(func_or_tree):
        if (isinstance(node, ast.For) and isinstance(node.iter, ast.Call)
                and isinstance(node.iter.func, ast.Name)
                and node.iter.func.id == "sorted"):
            return True
    return False


def _generic_skip_literals(func: ast.AST) -> Set[str]:
    """String literals tested inside the generic loop's ``continue``
    guards — counters matching one are NOT generically rendered."""
    skips: Set[str] = set()
    for node in ast.walk(func):
        if not (isinstance(node, ast.For) and isinstance(node.iter, ast.Call)
                and isinstance(node.iter.func, ast.Name)
                and node.iter.func.id == "sorted"):
            continue
        for stmt in ast.walk(node):
            if isinstance(stmt, ast.If) and any(
                    isinstance(s, ast.Continue) for s in stmt.body):
                skips |= _string_constants(stmt.test)
    return skips


def _stats_receiver(fn: ast.AST) -> bool:
    if not isinstance(fn, ast.Attribute):
        return False
    recv = fn.value
    return ((isinstance(recv, ast.Name) and recv.id == "stats")
            or (isinstance(recv, ast.Attribute) and recv.attr == "stats"))


def _check_mutators(project: Project, fields: Set[str],
                    findings: List[Finding]) -> None:
    for src, tree in project.iter_trees():
        for node in ast.walk(tree):
            if not (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)
                    and _stats_receiver(node.func) and node.args):
                continue
            name = _str_const(node.args[0])
            if name is None:
                continue
            wanted = []
            if node.func.attr in _STATS_MUTATORS:
                wanted = [name]
            elif node.func.attr in ("count_clock", "stage"):
                wanted = ["nr_" + name, "clk_" + name]
            for w in wanted:
                if w not in fields:
                    findings.append(Finding(
                        src.relpath, node.lineno, "surface.undeclared",
                        f"counter '{w}' bumped via stats.{node.func.attr} "
                        f"but not declared in STAT_FIELDS"))


def _check_renderers(project: Project, fields: Set[str],
                     findings: List[Finding]) -> None:
    scoped = sorted(f for f in fields
                    if (f.startswith("nr_") or f.startswith("bytes_"))
                    and "debug" not in f)
    # tpu_stat: the human surface
    stat_src = project.file("tpu_stat.py")
    if stat_src is not None:
        tree = stat_src.tree
        if not _has_generic_dump(tree):
            lits = _string_constants(tree)
            for f in scoped:
                if not _covered(f, lits):
                    findings.append(Finding(
                        stat_src.relpath, 1, "surface.stat-render",
                        f"counter '{f}' is never rendered by tpu_stat "
                        f"(no generic dump and no literal reference)"))
    # prometheus: the machine surface
    for src, tree in project.iter_trees():
        prom = None
        for node in ast.walk(tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)) \
                    and node.name == "render_prometheus":
                prom = node
                break
        if prom is None:
            continue
        lits = _string_constants(prom)
        generic = _has_generic_dump(prom)
        skips = _generic_skip_literals(prom) if generic else set()
        for f in scoped:
            if generic and not any(s in f for s in skips):
                continue          # the sorted() loop emits it verbatim
            if not _covered(f, lits):
                findings.append(Finding(
                    src.relpath, prom.lineno, "surface.prom-render",
                    f"counter '{f}' is skipped by render_prometheus's "
                    f"generic loop but no labeled series covers it"))
        break


# -- engine backend legend -------------------------------------------------

def _assigned_literals(tree: ast.AST, name: str) -> Optional[Set[str]]:
    """String literals under the value assigned to ``name`` (module
    scope), or None when no such assignment exists."""
    for node in ast.walk(tree):
        if isinstance(node, ast.Assign):
            tgts, value = node.targets, node.value
        elif isinstance(node, ast.AnnAssign) and node.value is not None:
            tgts, value = [node.target], node.value
        else:
            continue
        for t in tgts:
            if isinstance(t, ast.Name) and t.id == name:
                return _string_constants(value)
    return None


def _check_backends(project: Project, findings: List[Finding]) -> None:
    """Rule ``surface.backend``: every ``NSTPU_BACKEND_*`` rung declared
    in the native header must be rendered by the observability surface —
    its lowercased name in ``_BACKEND_NAMES`` (the ctypes legend feeding
    ``backend_name`` and hence the stats export) AND in tpu_stat's
    ``_BACKENDS`` legend.  A new failover rung cannot ship invisible."""
    if not project.header_text:
        return
    rungs = {m.group(1).lower() for m in re.finditer(
        r"#define\s+NSTPU_BACKEND_(\w+)\b", project.header_text)}
    if not rungs:
        return
    for suffix, legend in (("_native/__init__.py", "_BACKEND_NAMES"),
                           ("tools/tpu_stat.py", "_BACKENDS")):
        src = project.file(suffix)
        if src is None:
            continue
        lits = _assigned_literals(src.tree, legend)
        if lits is None:
            findings.append(Finding(
                src.relpath, 1, "surface.backend",
                f"no {legend} legend found for the NSTPU_BACKEND_* enum "
                f"({project.header_path})"))
            continue
        for rung in sorted(rungs - lits):
            findings.append(Finding(
                src.relpath, 1, "surface.backend",
                f"backend rung '{rung}' (NSTPU_BACKEND_{rung.upper()}, "
                f"{project.header_path}) missing from {legend}"))


# -- trace schema ----------------------------------------------------------

def _collect_schema(project: Project
                    ) -> Tuple[Optional[SourceFile], int, Dict[str, str]]:
    for src, tree in project.iter_trees():
        for node in ast.walk(tree):
            if isinstance(node, ast.Assign) and node.targets:
                tgt, value = node.targets[0], node.value
            elif isinstance(node, ast.AnnAssign) and node.value is not None:
                tgt, value = node.target, node.value
            else:
                continue
            if not (isinstance(tgt, ast.Name) and tgt.id == "EVENT_SCHEMA"
                    and isinstance(value, ast.Dict)):
                continue
            schema: Dict[str, str] = {}
            for k, v in zip(value.keys, value.values):
                ks, vs = _str_const(k), _str_const(v)
                if ks is not None and vs is not None:
                    schema[ks] = vs
            return src, node.lineno, schema
    return None, 0, {}


def _collect_emissions(project: Project
                       ) -> List[Tuple[SourceFile, int, str, str]]:
    out = []
    for src, tree in project.iter_trees():
        for node in ast.walk(tree):
            if not (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)):
                continue
            if node.func.attr in ("span", "instant") and node.args:
                name = _str_const(node.args[0])
                if name is not None:
                    out.append((src, node.lineno, name, node.func.attr))
            elif node.func.attr == "stage":
                # stats.stage(..., ring="<event>") records a ring span
                for kw in node.keywords:
                    name = _str_const(kw.value) if kw.arg == "ring" else None
                    if name is not None:
                        out.append((src, node.lineno, name, "span"))
    return out


def _check_trace(project: Project, findings: List[Finding]) -> None:
    emissions = _collect_emissions(project)
    if not emissions:
        return
    schema_src, schema_line, schema = _collect_schema(project)
    if schema_src is None:
        src, line, name, _ = emissions[0]
        findings.append(Finding(
            src.relpath, line, "surface.trace-schema",
            f"trace event '{name}' emitted but no EVENT_SCHEMA dict "
            f"declares the recorder's event kinds"))
        return
    emitted: Set[str] = set()
    for src, line, name, kind in emissions:
        emitted.add(name)
        want = schema.get(name)
        if want is None:
            findings.append(Finding(
                src.relpath, line, "surface.trace-schema",
                f"trace event '{name}' ({kind}) not in EVENT_SCHEMA"))
        elif want != "any" and want != kind:
            findings.append(Finding(
                src.relpath, line, "surface.trace-kind",
                f"trace event '{name}' emitted as {kind} but EVENT_SCHEMA "
                f"declares it '{want}'"))
    for name in sorted(set(schema) - emitted):
        findings.append(Finding(
            schema_src.relpath, schema_line, "surface.trace-stale",
            f"EVENT_SCHEMA entry '{name}' is never emitted"))
    for name in schema:
        if name.endswith("_begin") and name[:-6] + "_end" not in schema:
            findings.append(Finding(
                schema_src.relpath, schema_line, "surface.trace-pair",
                f"span kind '{name}' has no matching "
                f"'{name[:-6]}_end' in EVENT_SCHEMA"))
        if name.endswith("_end") and name[:-4] + "_begin" not in schema:
            findings.append(Finding(
                schema_src.relpath, schema_line, "surface.trace-pair",
                f"span kind '{name}' has no matching "
                f"'{name[:-4]}_begin' in EVENT_SCHEMA"))


def run(project: Project) -> List[Finding]:
    findings: List[Finding] = []
    src, _line, fields = _collect_stat_fields(project)
    if src is not None:
        _check_mutators(project, fields, findings)
        _check_renderers(project, fields, findings)
    _check_backends(project, findings)
    _check_trace(project, findings)
    return findings
