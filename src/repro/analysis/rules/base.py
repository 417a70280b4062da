"""Shared AST machinery for the repro.analysis lint rules.

Every rule works on a :class:`ModuleInfo`: a parsed module with parent
links, an import-alias table (so ``jnp.where`` resolves to
``jax.numpy.where`` whatever the file imported it as), and helpers for the
two questions most rules ask — "is this function traced by jax?" and
"does this expression produce / derive from a device array?".

The analysis is deliberately file-local and name-based (no type
inference): rules are tuned so the repo's own ``src/`` is clean, false
positives are silenced with ``# repro: noqa[Rn]`` at the finding line,
and anything requiring whole-program reasoning lives in the one project
rule (R6, rules.deadcode).
"""
from __future__ import annotations

import ast
import dataclasses
from typing import Dict, Iterable, List, Optional, Set

#: transforms whose function argument is traced (its body must not branch
#: on traced values in Python)
TRACE_WRAPPERS = {
    "jax.jit", "jax.pmap", "jax.vmap", "jax.grad", "jax.value_and_grad",
    "jax.checkpoint", "jax.remat", "jax.custom_vjp", "jax.custom_jvp",
    "jax.lax.scan", "jax.lax.map", "jax.lax.while_loop", "jax.lax.fori_loop",
    "jax.lax.cond", "jax.lax.switch", "jax.lax.associative_scan",
    "jax.shard_map",
    "jax.experimental.checkify.checkify",
    "jax.experimental.pallas.pallas_call",
}

#: call prefixes that produce device arrays
DEVICE_PREFIXES = ("jax.numpy.", "jax.lax.", "jax.random.", "jax.nn.",
                   "jax.scipy.", "jax.tree.", "jax.tree_util.")

#: attribute reads that are static metadata, not traced values
STATIC_ATTRS = {"shape", "ndim", "dtype", "size", "sharding", "aval"}


@dataclasses.dataclass
class Finding:
    rule: str
    path: str
    line: int
    col: int
    message: str
    suppressed: bool = False

    def to_json(self) -> dict:
        return dataclasses.asdict(self)

    def __str__(self) -> str:
        sup = "  (noqa)" if self.suppressed else ""
        return f"{self.path}:{self.line}:{self.col}: " \
               f"{self.rule} {self.message}{sup}"


class Rule:
    """Per-file rule: subclasses set ``id``/``name`` and implement
    :meth:`check`."""

    id = "R0"
    name = "base"

    def check(self, mi: "ModuleInfo") -> List[Finding]:
        raise NotImplementedError

    def finding(self, mi: "ModuleInfo", node: ast.AST,
                message: str) -> Finding:
        return Finding(self.id, mi.path, getattr(node, "lineno", 1),
                       getattr(node, "col_offset", 0), message)


class ProjectRule(Rule):
    """Whole-file-set rule (R6): sees every linted module at once plus the
    reference modules around the source tree."""

    def check_project(self, modules: List["ModuleInfo"],
                      repo_root: Optional[str]) -> List[Finding]:
        raise NotImplementedError

    def check(self, mi: "ModuleInfo") -> List[Finding]:
        return []


def _collect_aliases(tree: ast.AST) -> Dict[str, str]:
    """Name -> dotted module/attribute path, from every import statement."""
    aliases: Dict[str, str] = {}
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for a in node.names:
                if a.asname:
                    aliases[a.asname] = a.name
                else:
                    root = a.name.split(".")[0]
                    aliases.setdefault(root, root)
        elif isinstance(node, ast.ImportFrom):
            if node.level or node.module is None:
                continue                      # relative imports stay local
            for a in node.names:
                if a.name == "*":
                    continue
                aliases[a.asname or a.name] = f"{node.module}.{a.name}"
    return aliases


def annotate_parents(tree: ast.AST) -> None:
    for node in ast.walk(tree):
        for child in ast.iter_child_nodes(node):
            child._repro_parent = node            # type: ignore[attr-defined]


def parents(node: ast.AST) -> Iterable[ast.AST]:
    cur = getattr(node, "_repro_parent", None)
    while cur is not None:
        yield cur
        cur = getattr(cur, "_repro_parent", None)


class ModuleInfo:
    def __init__(self, path: str, source: str, tree: ast.Module):
        self.path = path
        self.source = source
        self.tree = tree
        self.aliases = _collect_aliases(tree)
        annotate_parents(tree)
        self._traced: Optional[Set[ast.AST]] = None

    # -- name resolution -------------------------------------------------
    def resolve(self, node: ast.AST) -> Optional[str]:
        """Dotted path of a Name/Attribute chain with import aliases
        resolved: ``jnp.sum`` -> ``jax.numpy.sum``.  None for anything
        that is not a plain dotted chain."""
        parts: List[str] = []
        while isinstance(node, ast.Attribute):
            parts.append(node.attr)
            node = node.value
        if not isinstance(node, ast.Name):
            return None
        root = self.aliases.get(node.id, node.id)
        return ".".join([root] + list(reversed(parts)))

    def is_device_call(self, node: ast.AST) -> bool:
        """Does this Call produce a device array (by name)?"""
        if not isinstance(node, ast.Call):
            return False
        path = self.resolve(node.func)
        if path is None:
            return False
        return path.startswith(DEVICE_PREFIXES) or path in (
            "jax.device_put", "jax.block_until_ready", "jax.eval_shape")

    # -- traced-function detection ---------------------------------------
    def traced_functions(self) -> Set[ast.AST]:
        """FunctionDef/Lambda nodes whose bodies run under a jax trace:
        decorated with / passed to a TRACE_WRAPPER (or ``*.defvjp``), plus
        everything nested inside one."""
        if self._traced is not None:
            return self._traced
        defs: Dict[str, List[ast.AST]] = {}
        for node in ast.walk(self.tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                defs.setdefault(node.name, []).append(node)
        traced: Set[ast.AST] = set()

        def mark_arg(arg: ast.AST) -> None:
            if isinstance(arg, ast.Lambda):
                traced.add(arg)
            elif isinstance(arg, ast.Name):
                for d in defs.get(arg.id, []):
                    traced.add(d)

        for node in ast.walk(self.tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                for dec in node.decorator_list:
                    target = dec.func if isinstance(dec, ast.Call) else dec
                    path = self.resolve(target)
                    if path in TRACE_WRAPPERS:
                        traced.add(node)
                    elif path in ("functools.partial", "partial") and \
                            isinstance(dec, ast.Call) and dec.args and \
                            self.resolve(dec.args[0]) in TRACE_WRAPPERS:
                        traced.add(node)
            if not isinstance(node, ast.Call):
                continue
            path = self.resolve(node.func)
            if path in TRACE_WRAPPERS:
                for arg in list(node.args) + [k.value for k in node.keywords]:
                    mark_arg(arg)
            elif isinstance(node.func, ast.Attribute) and \
                    node.func.attr == "defvjp":
                for arg in node.args:
                    mark_arg(arg)
        # closure: defs nested inside a traced def run during its trace
        changed = True
        while changed:
            changed = False
            for node in ast.walk(self.tree):
                if not isinstance(node, (ast.FunctionDef,
                                         ast.AsyncFunctionDef, ast.Lambda)):
                    continue
                if node in traced:
                    continue
                if any(p in traced for p in parents(node)):
                    traced.add(node)
                    changed = True
        self._traced = traced
        return traced


def device_tainted_names(mi: ModuleInfo, fn: ast.AST,
                         extra_sources=()) -> Set[str]:
    """Names in ``fn`` assigned (directly or transitively) from device-
    array-producing calls: ``jax.*`` calls, calls to private ``self._*``
    methods (engine jit seams by convention), calls to names bound from
    ``jax.jit(...)``, and ``extra_sources``."""
    jitted: Set[str] = set(extra_sources)
    for node in ast.walk(fn):
        if isinstance(node, ast.Assign) and isinstance(node.value, ast.Call):
            path = mi.resolve(node.value.func)
            if path in ("jax.jit", "jax.pmap"):
                for t in node.targets:
                    if isinstance(t, ast.Name):
                        jitted.add(t.id)

    def value_tainted(node: ast.AST, taint: Set[str]) -> bool:
        for sub in ast.walk(node):
            if isinstance(sub, ast.Call):
                if mi.is_device_call(sub):
                    return True
                path = mi.resolve(sub.func)
                if path is not None and path.split(".")[0] in jitted:
                    return True
                if isinstance(sub.func, ast.Attribute) and \
                        isinstance(sub.func.value, ast.Name) and \
                        sub.func.value.id == "self" and \
                        sub.func.attr.startswith("_"):
                    return True
            elif isinstance(sub, ast.Name) and sub.id in taint:
                if not _is_static_access(sub):
                    return True
        return False

    taint: Set[str] = set()
    for _ in range(2):                      # two passes ~= fixpoint here
        for node in ast.walk(fn):
            targets: List[ast.AST] = []
            if isinstance(node, ast.Assign):
                targets, value = node.targets, node.value
            elif isinstance(node, ast.AugAssign):
                targets, value = [node.target], node.value
            elif isinstance(node, (ast.For, ast.AsyncFor)):
                targets, value = [node.target], node.iter
            else:
                continue
            if not value_tainted(value, taint):
                continue
            for t in targets:
                taint.update(_target_names(t))
    return taint


def _target_names(t: ast.AST) -> List[str]:
    """Names actually bound by an assignment target — the base of a
    subscript/attribute store, not its index expression (``out[path] = m``
    taints ``out``, never ``path``)."""
    if isinstance(t, ast.Name):
        return [t.id]
    if isinstance(t, (ast.Tuple, ast.List)):
        return [n for e in t.elts for n in _target_names(e)]
    if isinstance(t, ast.Starred):
        return _target_names(t.value)
    if isinstance(t, (ast.Subscript, ast.Attribute)):
        return _target_names(t.value)
    return []


def _is_static_access(name_node: ast.Name) -> bool:
    """True when the name is only read through static metadata
    (``x.shape`` / ``len(x)`` / ``isinstance(x, ...)``)."""
    parent = getattr(name_node, "_repro_parent", None)
    if isinstance(parent, ast.Attribute) and parent.attr in STATIC_ATTRS:
        return True
    if isinstance(parent, ast.Call) and isinstance(parent.func, ast.Name) \
            and parent.func.id in ("len", "isinstance", "type", "hasattr",
                                   "getattr"):
        return True
    return False


def expr_uses_device_value(mi: ModuleInfo, node: ast.AST,
                           taint: Set[str]) -> bool:
    """Does evaluating ``node`` touch a (likely) device value — a tainted
    name or a device-producing call — through anything other than static
    metadata access?"""
    for sub in ast.walk(node):
        if isinstance(sub, ast.Call) and mi.is_device_call(sub):
            return True
        if isinstance(sub, ast.Name) and sub.id in taint \
                and not _is_static_access(sub):
            return True
    return False
