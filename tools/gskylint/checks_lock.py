"""GSKY-LOCK: lock-discipline consistency inside lock-owning classes.

For every class that creates a ``threading.Lock``/``RLock`` on
``self``, each instance attribute must be mutated either always under
an owned lock or never under one.  An attribute written both ways is
the textbook latent race: the locked sites prove the author believed
the attribute is shared, so the unlocked site is a hole (page pool
slots, wave counters — the structures the wave ticker
and drainer threads touch concurrently).

Mechanics (deliberately syntactic — this is a consistency check, not
an alias analysis):

* a write is "locked" when it sits lexically inside
  ``with self.<lock>:`` (any owned lock; ``with self.locked_*():``
  context-manager helpers count too);
* ``__init__``/``__new__`` are skipped — the object is not shared
  until construction returns;
* methods named ``*_locked`` or carrying ``# gskylint: holds-lock``
  on their ``def`` line declare the caller-holds-the-lock contract
  and their writes count as locked (the marker makes the repo's
  "internals (hold self.lock)" comment convention machine-checked);
* writes inside nested ``def``/``lambda`` bodies are ignored — they
  execute at some other time under some other lock regime;
* tracked mutations: ``self.x = / += ...``, ``self.x[k] = / del``,
  and mutating container-method calls (``append``, ``pop``,
  ``update``, ``clear``, ...) on ``self.x``.

One finding per (class, attribute), anchored at the first unlocked
write and naming a locked counterpart.
"""

from __future__ import annotations

import ast
from typing import Dict, List, Optional, Set, Tuple

from .engine import Finding, RepoContext, SourceFile

CODE = "GSKY-LOCK"

_MUTATORS = {"append", "extend", "insert", "remove", "pop", "popitem",
             "update", "setdefault", "move_to_end", "add", "discard",
             "clear"}
_SKIP_METHODS = {"__init__", "__new__"}


def _is_lock_ctor(node: ast.AST) -> bool:
    if not isinstance(node, ast.Call):
        return False
    f = node.func
    if isinstance(f, ast.Attribute) and f.attr in ("Lock", "RLock"):
        return True
    if isinstance(f, ast.Name) and f.id in ("Lock", "RLock"):
        return True
    return False


def _self_attr(node: ast.AST) -> Optional[str]:
    if isinstance(node, ast.Attribute) and \
            isinstance(node.value, ast.Name) and node.value.id == "self":
        return node.attr
    return None


def _lock_attrs(cls: ast.ClassDef) -> Set[str]:
    """Attributes assigned a Lock/RLock anywhere in the class body."""
    locks: Set[str] = set()
    for node in ast.walk(cls):
        if isinstance(node, ast.Assign) and _is_lock_ctor(node.value):
            for tgt in node.targets:
                attr = _self_attr(tgt)
                if attr:
                    locks.add(attr)
                elif isinstance(tgt, ast.Name):
                    locks.add(tgt.id)     # class-level lock attribute
    return locks


def _withitem_is_lock(item: ast.withitem, locks: Set[str]) -> bool:
    expr = item.context_expr
    attr = _self_attr(expr)
    if attr is not None and attr in locks:
        return True
    if isinstance(expr, ast.Call):
        attr = _self_attr(expr.func)
        if attr is not None and "lock" in attr.lower():
            return True      # with self.locked_pool(): style helpers
    return False


class _MethodScanner(ast.NodeVisitor):
    """Collect (attr -> [(line, locked)]) writes for one method."""

    def __init__(self, locks: Set[str], all_locked: bool):
        self.locks = locks
        self.depth_locked = 1 if all_locked else 0
        self.writes: List[Tuple[str, int, bool]] = []

    # nested defs execute under an unknown lock regime: skip
    def visit_FunctionDef(self, node):
        pass

    visit_AsyncFunctionDef = visit_FunctionDef
    visit_Lambda = visit_FunctionDef

    def visit_With(self, node):
        locked = any(_withitem_is_lock(i, self.locks)
                     for i in node.items)
        if locked:
            self.depth_locked += 1
        for item in node.items:
            self.visit(item.context_expr)
        for child in node.body:
            self.visit(child)
        if locked:
            self.depth_locked -= 1

    def _record_target(self, tgt: ast.AST, lineno: int):
        attr = _self_attr(tgt)
        if attr is not None and attr not in self.locks:
            self.writes.append((attr, lineno, self.depth_locked > 0))
        elif isinstance(tgt, ast.Subscript):
            attr = _self_attr(tgt.value)
            if attr is not None and attr not in self.locks:
                self.writes.append((attr, lineno,
                                    self.depth_locked > 0))

    def visit_Assign(self, node):
        for tgt in node.targets:
            if isinstance(tgt, ast.Tuple):
                for el in tgt.elts:
                    self._record_target(el, node.lineno)
            else:
                self._record_target(tgt, node.lineno)
        self.visit(node.value)

    def visit_AugAssign(self, node):
        self._record_target(node.target, node.lineno)
        self.visit(node.value)

    def visit_AnnAssign(self, node):
        if node.value is not None:
            self._record_target(node.target, node.lineno)
            self.visit(node.value)

    def visit_Delete(self, node):
        for tgt in node.targets:
            self._record_target(tgt, node.lineno)

    def visit_Call(self, node):
        if isinstance(node.func, ast.Attribute) and \
                node.func.attr in _MUTATORS:
            attr = _self_attr(node.func.value)
            if attr is not None and attr not in self.locks:
                self.writes.append((attr, node.lineno,
                                    self.depth_locked > 0))
        self.generic_visit(node)


def _method_holds_lock(sf: SourceFile, meth: ast.FunctionDef) -> bool:
    if meth.name.endswith("_locked"):
        return True
    for ln in range(meth.lineno,
                    (meth.body[0].lineno if meth.body
                     else meth.lineno) + 1):
        if sf.holds_lock_marked(ln):
            return True
    return False


def check(ctx: RepoContext) -> List[Finding]:
    out: List[Finding] = []
    for sf in ctx.files:
        if sf.tree is None:
            continue
        for cls in ast.walk(sf.tree):
            if not isinstance(cls, ast.ClassDef):
                continue
            locks = _lock_attrs(cls)
            if not locks:
                continue
            # attr -> {"locked": [(meth, line)], "bare": [(meth, line)]}
            per_attr: Dict[str, Dict[str, List[Tuple[str, int]]]] = {}
            for meth in cls.body:
                if not isinstance(meth, (ast.FunctionDef,
                                         ast.AsyncFunctionDef)):
                    continue
                if meth.name in _SKIP_METHODS:
                    continue
                scan = _MethodScanner(
                    locks, all_locked=_method_holds_lock(sf, meth))
                for stmt in meth.body:
                    scan.visit(stmt)
                for attr, line, locked in scan.writes:
                    bucket = per_attr.setdefault(
                        attr, {"locked": [], "bare": []})
                    bucket["locked" if locked else "bare"].append(
                        (meth.name, line))
            for attr, buckets in sorted(per_attr.items()):
                if buckets["locked"] and buckets["bare"]:
                    l_meth, l_line = buckets["locked"][0]
                    b_meth, b_line = buckets["bare"][0]
                    out.append(Finding(
                        CODE, sf.path, b_line,
                        f"{cls.name}.{attr} is mutated without the "
                        f"owning lock in {b_meth}() (line {b_line}) "
                        f"but under it in {l_meth}() (line {l_line}) "
                        f"— hold the lock, or mark the method "
                        f"`# gskylint: holds-lock` if the caller "
                        f"holds it"))
    return out
