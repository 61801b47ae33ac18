"""Checker family 7: buffer-donation discipline.

``donate_argnums`` hands a buffer's storage to XLA: after the donating
call the Python binding still points at a deleted array, and touching
it raises (or worse, silently reads garbage under some backends).  The
fused gbdt paths donate the arena and the score plane every iteration,
the partition kernels donate their scratch arena, and a timing
script may thread a donated arena through stateful dict closures
(tests/fixtures/lint/donation_bench.py) — all patterns
this checker must accept, while catching the three ways they rot:

- ``donation-use-after``  HIGH  a donated binding is read after the
                                donating call and before it is rebound
- ``donation-double``     HIGH  one binding donated twice — in two
                                positions of one call, or to a second
                                call with no rebind in between
- ``donation-escape``     HIGH  a donated binding returned to the
                                caller, exporting the dead reference

Donating callables are recognized in every form the tree uses:
``jax.jit(f, donate_argnums=...)`` assignments,
``@functools.partial(jax.jit, donate_argnums=...)`` decorators,
``partial(jax.jit, ...)(impl)`` wraps, and methods that *return* a
donating jit (``self._fused_fn = self._build_fused_iter(...)`` then
``self._fused_fn(*args)`` — the star-call is mapped through the local
tuple literal).  Donated bindings are tracked as plain names, dotted
attribute chains (``self._arena``), and constant-keyed subscripts
(``state["arena"]``); the assignment targets of the donating statement
itself count as post-call rebinds, so the idiomatic
``tree, ids, self._arena, _ = fn(self._arena, ...)`` is clean.  The
scan is branch-aware: a donation in one arm of an ``if`` never flags a
read in the other arm.
"""
from __future__ import annotations

import ast
from typing import Dict, Iterable, List, Optional, Sequence, Set, Tuple

from ..core import (COMMON_CALL_NAMES, Checker, Finding, HIGH, Project,
                    SourceFile, binding_key, call_name, expr_text)

CHECK_USE_AFTER = "donation-use-after"
CHECK_DOUBLE = "donation-double"
CHECK_ESCAPE = "donation-escape"

_JIT_TAILS = ("jit",)
_PARTIAL_NAMES = ("partial", "functools.partial")


def _parse_argnums(expr: ast.AST) -> Optional[Tuple[int, ...]]:
    if isinstance(expr, ast.Constant) and isinstance(expr.value, int):
        return (expr.value,)
    if isinstance(expr, (ast.Tuple, ast.List)):
        out = []
        for e in expr.elts:
            if not (isinstance(e, ast.Constant)
                    and isinstance(e.value, int)):
                return None
            out.append(e.value)
        return tuple(out)
    return None


def _is_jit_ref(expr: ast.AST) -> bool:
    text = expr_text(expr)
    return bool(text) and (text in _JIT_TAILS
                           or text.rsplit(".", 1)[-1] in _JIT_TAILS)


def _partial_of_jit_argnums(call: ast.AST) -> Optional[Tuple[int, ...]]:
    """argnums when ``call`` is partial(jax.jit, ..., donate_argnums=X)."""
    if not isinstance(call, ast.Call):
        return None
    if expr_text(call.func) not in _PARTIAL_NAMES:
        return None
    if not (call.args and _is_jit_ref(call.args[0])):
        return None
    for kw in call.keywords:
        if kw.arg == "donate_argnums":
            return _parse_argnums(kw.value)
    return None


def donating_argnums(expr: ast.AST) -> Optional[Tuple[int, ...]]:
    """Donated argnums when ``expr`` evaluates to a donating callable:
    ``jax.jit(f, donate_argnums=X)`` or ``partial(jax.jit, ...,
    donate_argnums=X)(f)``."""
    if not isinstance(expr, ast.Call):
        return None
    if _is_jit_ref(expr.func):
        for kw in expr.keywords:
            if kw.arg == "donate_argnums":
                return _parse_argnums(kw.value)
        return None
    return _partial_of_jit_argnums(expr.func)


class _Donation:
    __slots__ = ("key", "lineno", "sig", "call")

    def __init__(self, key, lineno, sig, call):
        self.key = key
        self.lineno = lineno
        self.sig = sig          # branch signature: ((id(if_stmt), arm), ...)
        self.call = call


def _sigs_compatible(a: Tuple, b: Tuple) -> bool:
    """False when the two statements sit in opposite arms of a shared
    ``if`` — they can never execute on the same path."""
    arms_a = dict(a)
    for if_id, arm in b:
        if if_id in arms_a and arms_a[if_id] != arm:
            return False
    return True


class DonationChecker(Checker):
    id = "donation"
    description = ("reads of donated buffers after the donating call, "
                   "double donation, donated refs escaping via return")
    checks = (CHECK_USE_AFTER, CHECK_DOUBLE, CHECK_ESCAPE)

    def run(self, project: Project) -> Iterable[Finding]:
        global_donors = self._global_donors(project)
        findings: List[Finding] = []
        for sf in project.files:
            class_donors = self._class_donors(sf)
            for node in ast.walk(sf.tree):
                if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                    owner = self._owning_class(sf, node)
                    attrs = class_donors.get(owner, {}) if owner else {}
                    findings.extend(self._check_function(
                        sf, node, global_donors, attrs))
        return findings

    # -- donor discovery ------------------------------------------------
    def _global_donors(self, project: Project) -> Dict[str, Tuple[int, ...]]:
        """Module-level donating callables by simple name, project-wide
        (``grow_tree_partition``, ``init_pristine``)."""
        donors: Dict[str, Tuple[int, ...]] = {}
        for sf in project.files:
            for stmt in sf.tree.body:
                if isinstance(stmt, ast.FunctionDef):
                    for dec in stmt.decorator_list:
                        argnums = _partial_of_jit_argnums(dec)
                        if argnums and stmt.name not in COMMON_CALL_NAMES:
                            donors[stmt.name] = argnums
                elif isinstance(stmt, ast.Assign):
                    argnums = donating_argnums(stmt.value)
                    if argnums:
                        for tgt in stmt.targets:
                            if isinstance(tgt, ast.Name) \
                                    and tgt.id not in COMMON_CALL_NAMES:
                                donors[tgt.id] = argnums
        return donors

    def _method_returns_donating(self, func: ast.AST
                                 ) -> Optional[Tuple[int, ...]]:
        """argnums when any ``return`` of ``func`` yields a donating
        jit — directly or via a local bound to one (the build-and-cache
        idiom: ``fn = jax.jit(..., donate_argnums=(0,)); ...;
        return fn``)."""
        local: Dict[str, Tuple[int, ...]] = {}
        for n in ast.walk(func):
            if isinstance(n, ast.Assign):
                argnums = donating_argnums(n.value)
                if argnums:
                    for tgt in n.targets:
                        if isinstance(tgt, ast.Name):
                            local[tgt.id] = argnums
        for n in ast.walk(func):
            if not isinstance(n, ast.Return) or n.value is None:
                continue
            argnums = donating_argnums(n.value)
            if argnums:
                return argnums
            if isinstance(n.value, ast.Name) and n.value.id in local:
                return local[n.value.id]
        return None

    def _class_donors(self, sf: SourceFile
                      ) -> Dict[str, Dict[str, Tuple[int, ...]]]:
        """class name -> {donating member: argnums}, covering methods
        that return donating jits and the attrs those are cached on
        (``self._fused_fn = self._build_fused_iter(...)``)."""
        out: Dict[str, Dict[str, Tuple[int, ...]]] = {}
        for node in ast.walk(sf.tree):
            if not isinstance(node, ast.ClassDef):
                continue
            members: Dict[str, Tuple[int, ...]] = {}
            methods = [n for n in node.body
                       if isinstance(n, ast.FunctionDef)]
            for meth in methods:
                argnums = self._method_returns_donating(meth)
                if argnums:
                    members[meth.name] = argnums
            for meth in methods:
                for n in ast.walk(meth):
                    if not isinstance(n, ast.Assign):
                        continue
                    argnums = donating_argnums(n.value)
                    if argnums is None and isinstance(n.value, ast.Call):
                        callee, recv = call_name(n.value)
                        if recv == "self" and callee in members:
                            argnums = members[callee]
                    if argnums is None:
                        continue
                    for tgt in n.targets:
                        key = binding_key(tgt)
                        if key and key.startswith("self."):
                            members[key[len("self."):]] = argnums
            if members:
                out[node.name] = members
        return out

    def _owning_class(self, sf: SourceFile, func: ast.AST) -> Optional[str]:
        cur = sf.parent(func)
        while cur is not None:
            if isinstance(cur, ast.ClassDef):
                return cur.name
            if isinstance(cur, (ast.FunctionDef, ast.AsyncFunctionDef)):
                return None
            cur = sf.parent(cur)
        return None

    # -- per-function flow scan -----------------------------------------
    def _check_function(self, sf: SourceFile, func: ast.AST,
                        global_donors: Dict[str, Tuple[int, ...]],
                        attr_donors: Dict[str, Tuple[int, ...]]
                        ) -> List[Finding]:
        out: List[Finding] = []
        donated: Dict[str, _Donation] = {}
        local_donors: Dict[str, Tuple[int, ...]] = {}
        tuple_literals: Dict[str, List[ast.AST]] = {}

        def call_argnums(call: ast.Call) -> Optional[Tuple[int, ...]]:
            callee, recv = call_name(call)
            if recv == "self" and callee in attr_donors:
                return attr_donors[callee]
            if recv == "" and callee in local_donors:
                return local_donors[callee]
            if callee in global_donors and callee not in local_donors:
                return global_donors[callee]
            return None

        def donated_args(call: ast.Call,
                         argnums: Tuple[int, ...]) -> List[ast.AST]:
            args = call.args
            if len(args) == 1 and isinstance(args[0], ast.Starred):
                star = args[0].value
                if isinstance(star, ast.Name) \
                        and star.id in tuple_literals:
                    args = tuple_literals[star.id]
                else:
                    return []
            return [args[i] for i in argnums if i < len(args)]

        def flag_reads(expr: ast.AST, sig: Tuple, escape: bool) -> None:
            stack: List[ast.AST] = [expr]
            while stack:
                n = stack.pop()
                if isinstance(n, ast.Lambda):
                    continue
                key = binding_key(n)
                if key is not None and key in donated \
                        and isinstance(getattr(n, "ctx", ast.Load()),
                                       ast.Load):
                    d = donated[key]
                    if _sigs_compatible(d.sig, sig):
                        if escape:
                            out.append(self.finding(
                                sf, n, HIGH,
                                "returning %s after it was donated on "
                                "line %d — the caller receives a deleted "
                                "buffer" % (key, d.lineno),
                                check=CHECK_ESCAPE))
                        else:
                            out.append(self.finding(
                                sf, n, HIGH,
                                "%s is read here but was donated to the "
                                "call on line %d — the buffer is deleted; "
                                "rebind it from the call's result first"
                                % (key, d.lineno), check=CHECK_USE_AFTER))
                        continue    # report once per statement per key
                stack.extend(ast.iter_child_nodes(n))

        def register_donations(stmt: ast.stmt, sig: Tuple) -> None:
            stack: List[ast.AST] = [stmt]
            while stack:
                n = stack.pop()
                if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef,
                                  ast.Lambda)):
                    continue
                stack.extend(ast.iter_child_nodes(n))
                if not isinstance(n, ast.Call):
                    continue
                argnums = call_argnums(n)
                if not argnums:
                    continue
                seen: Set[str] = set()
                for arg in donated_args(n, argnums):
                    key = binding_key(arg)
                    if key is None:
                        continue
                    if key in seen:
                        out.append(self.finding(
                            sf, arg, HIGH,
                            "%s is donated twice in one call — XLA "
                            "deletes it once and the second donation "
                            "aliases a dead buffer" % key,
                            check=CHECK_DOUBLE))
                        continue
                    seen.add(key)
                    prev = donated.get(key)
                    if prev is not None \
                            and _sigs_compatible(prev.sig, sig):
                        out.append(self.finding(
                            sf, arg, HIGH,
                            "%s donated again here but was already "
                            "donated on line %d with no rebind in "
                            "between" % (key, prev.lineno),
                            check=CHECK_DOUBLE))
                    donated[key] = _Donation(key, stmt.lineno, sig, n)

        def clear_rebinds(targets: Sequence[ast.AST], sig: Tuple) -> None:
            for tgt in targets:
                for leaf in self._target_leaves(tgt):
                    key = binding_key(leaf)
                    if key is None:
                        continue
                    d = donated.get(key)
                    if d is not None and _sigs_compatible(d.sig, sig):
                        del donated[key]

        def scan(body: Sequence[ast.stmt], sig: Tuple) -> None:
            for stmt in body:
                if isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef,
                                     ast.ClassDef)):
                    continue
                if isinstance(stmt, ast.If):
                    flag_reads(stmt.test, sig, escape=False)
                    scan(stmt.body, sig + ((id(stmt), "if"),))
                    scan(stmt.orelse, sig + ((id(stmt), "else"),))
                    continue
                if isinstance(stmt, (ast.While,)):
                    flag_reads(stmt.test, sig, escape=False)
                    scan(stmt.body, sig)
                    scan(stmt.orelse, sig)
                    continue
                if isinstance(stmt, (ast.For, ast.AsyncFor)):
                    flag_reads(stmt.iter, sig, escape=False)
                    clear_rebinds([stmt.target], sig)
                    scan(stmt.body, sig)
                    scan(stmt.orelse, sig)
                    continue
                if isinstance(stmt, (ast.With, ast.AsyncWith)):
                    for item in stmt.items:
                        flag_reads(item.context_expr, sig, escape=False)
                        if item.optional_vars is not None:
                            clear_rebinds([item.optional_vars], sig)
                    scan(stmt.body, sig)
                    continue
                if isinstance(stmt, ast.Try):
                    scan(stmt.body, sig)
                    for h in stmt.handlers:
                        scan(h.body, sig)
                    scan(stmt.orelse, sig)
                    scan(stmt.finalbody, sig)
                    continue
                # plain statement: reads, then donations, then rebinds —
                # so the donating statement's own args never flag and
                # its own assignment targets count as rebinds
                if isinstance(stmt, ast.Return):
                    if stmt.value is not None:
                        flag_reads(stmt.value, sig, escape=True)
                    continue
                if isinstance(stmt, ast.AugAssign):
                    # += reads its target before writing it back
                    key = binding_key(stmt.target)
                    d = donated.get(key) if key else None
                    if d is not None and _sigs_compatible(d.sig, sig):
                        out.append(self.finding(
                            sf, stmt.target, HIGH,
                            "%s is read here but was donated to the call "
                            "on line %d — the buffer is deleted; rebind "
                            "it from the call's result first"
                            % (key, d.lineno), check=CHECK_USE_AFTER))
                flag_reads(stmt, sig, escape=False)
                register_donations(stmt, sig)
                if isinstance(stmt, ast.Assign):
                    # remember local tuple literals for star-call mapping
                    if isinstance(stmt.value, ast.Tuple) \
                            and len(stmt.targets) == 1 \
                            and isinstance(stmt.targets[0], ast.Name):
                        tuple_literals[stmt.targets[0].id] = \
                            list(stmt.value.elts)
                    argnums = donating_argnums(stmt.value)
                    if argnums:
                        for tgt in stmt.targets:
                            if isinstance(tgt, ast.Name):
                                local_donors[tgt.id] = argnums
                    clear_rebinds(stmt.targets, sig)
                elif isinstance(stmt, (ast.AugAssign, ast.AnnAssign)):
                    if stmt.target is not None:
                        clear_rebinds([stmt.target], sig)
                elif isinstance(stmt, ast.Delete):
                    clear_rebinds(stmt.targets, sig)

        scan(func.body, ())
        return out

    def _target_leaves(self, tgt: ast.AST) -> List[ast.AST]:
        if isinstance(tgt, (ast.Tuple, ast.List)):
            out: List[ast.AST] = []
            for elt in tgt.elts:
                out.extend(self._target_leaves(elt))
            return out
        if isinstance(tgt, ast.Starred):
            return self._target_leaves(tgt.value)
        return [tgt]
