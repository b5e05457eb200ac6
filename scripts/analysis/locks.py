"""CD102 — registered shared-attribute writes outside their lock.

Classes whose state crosses threads declare it with the zero-cost
marker from ``emqx_tpu/concurrency.py``::

    @shared_state(lock="_lock", attrs=("_buf",))
    class Wal: ...

This pass reads the marker from the AST and flags any mutation of a
registered attribute — assignment, augmented assignment, ``del``,
subscript store, or a mutating method call (``append``/``pop``/
``update``/...) — that is not lexically inside ``with self.<lock>``
(or ``with alias`` where ``alias = self.<lock>`` earlier in the same
function — the Metrics fast-path idiom). ``__init__`` is exempt:
construction happens before the object is shared, and so are methods
whose name ends in ``_locked`` — the naming convention for internal
helpers whose CALLER must hold the lock (the checker can't see
cross-function lock flow; the suffix makes the contract part of the
name). Deliberate lock-free fast paths (single-writer modes) carry
an inline ``# lint: ok-CD102 <why>`` waiver — the point is that the
*reason* lives next to the unguarded write.
"""

from __future__ import annotations

import ast
from typing import List, Optional, Set, Tuple

from analysis import FileInfo, Finding

RULES = {
    "CD102": "registered shared attribute mutated outside its lock",
}

_MUTATORS = {
    "append", "appendleft", "extend", "insert", "pop", "popleft",
    "popitem", "clear", "update", "add", "remove", "discard",
    "setdefault", "sort", "reverse",
}


def _applies(path: str) -> bool:
    return path.replace("\\", "/").startswith("emqx_tpu/")


def _shared_state(cls: ast.ClassDef) -> Optional[Tuple[str,
                                                       Set[str]]]:
    """Read ``@shared_state(lock=..., attrs=(...))`` off the AST."""
    for d in cls.decorator_list:
        if not isinstance(d, ast.Call):
            continue
        name = d.func.attr if isinstance(d.func, ast.Attribute) \
            else (d.func.id if isinstance(d.func, ast.Name) else None)
        if name != "shared_state":
            continue
        lock = None
        attrs: Set[str] = set()
        args = list(d.args)
        if args and isinstance(args[0], ast.Constant):
            lock = args[0].value
        if len(args) > 1:
            attrs |= {e.value for e in getattr(args[1], "elts", [])
                      if isinstance(e, ast.Constant)}
        for kw in d.keywords:
            if kw.arg == "lock" and isinstance(kw.value, ast.Constant):
                lock = kw.value.value
            elif kw.arg == "attrs":
                attrs |= {e.value
                          for e in getattr(kw.value, "elts", [])
                          if isinstance(e, ast.Constant)}
        if lock and attrs:
            return lock, attrs
    return None


def _self_attr(node) -> Optional[str]:
    """``self.<attr>`` -> attr name (possibly through a subscript)."""
    if isinstance(node, ast.Subscript):
        node = node.value
    if isinstance(node, ast.Attribute) and \
            isinstance(node.value, ast.Name) and \
            node.value.id == "self":
        return node.attr
    return None


def _is_lock_expr(item, lock: str, aliases: Set[str]) -> bool:
    e = item.context_expr
    if isinstance(e, ast.BoolOp) and isinstance(e.op, ast.Or):
        # `with self.<lock> or <stand-in>`: a mode that has no lock
        # (one thread owns the state) passes a null context
        e = e.values[0]
    if isinstance(e, ast.Attribute) and \
            isinstance(e.value, ast.Name) and e.value.id == "self" \
            and e.attr == lock:
        return True
    if isinstance(e, ast.Name) and e.id in aliases:
        return True
    return False


def _check_method(fi: FileInfo, cls: ast.ClassDef, fn, lock: str,
                  attrs: Set[str], out: List[Finding]) -> None:
    # aliases: `lk = self.<lock>` anywhere in the function
    aliases: Set[str] = set()
    for node in ast.walk(fn):
        if isinstance(node, ast.Assign) and \
                isinstance(node.value, ast.Attribute) and \
                isinstance(node.value.value, ast.Name) and \
                node.value.value.id == "self" and \
                node.value.attr == lock:
            for t in node.targets:
                if isinstance(t, ast.Name):
                    aliases.add(t.id)

    def visit(node, guarded: bool) -> None:
        if isinstance(node, ast.With):
            g = guarded or any(_is_lock_expr(it, lock, aliases)
                               for it in node.items)
            for sub in node.body:
                visit(sub, g)
            return
        hits: List[Tuple[int, str, str]] = []
        if isinstance(node, (ast.Assign, ast.AnnAssign, ast.AugAssign)):
            targets = node.targets if isinstance(node, ast.Assign) \
                else [node.target]
            flat = []
            for t in targets:
                flat.extend(t.elts if isinstance(t, (ast.Tuple,
                                                     ast.List))
                            else [t])
            for t in flat:
                a = _self_attr(t)
                if a in attrs:
                    hits.append((node.lineno, a, "write"))
        elif isinstance(node, ast.Delete):
            for t in node.targets:
                a = _self_attr(t)
                if a in attrs:
                    hits.append((node.lineno, a, "del"))
        elif isinstance(node, ast.Call) and \
                isinstance(node.func, ast.Attribute) and \
                node.func.attr in _MUTATORS:
            a = _self_attr(node.func.value)
            if a in attrs:
                hits.append((node.lineno, a, node.func.attr + "()"))
        if hits and not guarded:
            for line, a, kind in hits:
                out.append(Finding(
                    fi.path, line, "CD102",
                    f"{cls.name}.{fn.name} mutates shared "
                    f"'self.{a}' ({kind}) outside `with "
                    f"self.{lock}`"))
        for sub in ast.iter_child_nodes(node):
            # don't descend into nested defs — their execution time
            # is unknown; they get no guarantee either way
            if isinstance(sub, (ast.FunctionDef, ast.AsyncFunctionDef,
                                ast.Lambda)):
                continue
            visit(sub, guarded)

    for stmt in fn.body:
        visit(stmt, False)


def check(fi: FileInfo, ctx) -> List[Finding]:
    if not _applies(fi.path):
        return []
    out: List[Finding] = []
    for node in fi.tree.body:
        if not isinstance(node, ast.ClassDef):
            continue
        reg = _shared_state(node)
        if reg is None:
            continue
        lock, attrs = reg
        for sub in node.body:
            if isinstance(sub, (ast.FunctionDef,
                                ast.AsyncFunctionDef)) and \
                    sub.name != "__init__" and \
                    not sub.name.endswith("_locked"):
                _check_method(fi, node, sub, lock, attrs, out)
    return out
