"""tpulint core: jax-import-free AST analysis framework.

The reference enforces its threading invariants by convention — the
exception-safe ``OMP_INIT_EX()`` / ``OMP_LOOP_EX_BEGIN()`` macro
discipline (include/LightGBM/utils/openmp_wrapper.h) that every hot
loop must follow by hand.  This package is the JAX/threading analogue
enforced by a checker: a small visitor framework over ``ast`` plus four
checker families (jit/retrace hazards, lock discipline, config drift,
resource/exception hygiene) that gate CI via ``tools/lint.py``.

Design constraints:

- **No jax import, no lightgbm_tpu import.**  The linter must run in
  environments where ``JAX_PLATFORMS`` is unavailable (pre-merge CI,
  doc builders), so everything here is stdlib-only and the package is
  loadable standalone (tools/lint.py loads it by file path without
  executing ``lightgbm_tpu/__init__``).
- **Stable fingerprints.**  A finding's identity must survive line
  shifts AND file moves, or the baseline churns on every refactor.
  Fingerprints hash (check id, file basename, enclosing qualname,
  normalized source line, occurrence index) — never the directory or
  the line number.
- **Suppression is visible.**  ``# tpulint: ok=<check>`` on the
  offending line (or ``# tpulint: disable-next-line=<check>`` above it)
  is the allowlist for deliberate sync points / long-lived sockets; a
  bare ``# tpulint: ok`` suppresses every check on that line.  Grep for
  ``tpulint:`` to audit every exemption.
"""
from __future__ import annotations

import ast
import hashlib
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Set, Tuple

HIGH = "HIGH"
MEDIUM = "MEDIUM"
LOW = "LOW"
SEVERITIES = (HIGH, MEDIUM, LOW)
_SEV_RANK = {s: i for i, s in enumerate(SEVERITIES)}

_SUPPRESS_RE = re.compile(   # longest alternative first: 'disable' must
    r"#\s*tpulint:\s*"       # not shadow 'disable-next-line'
    r"(disable-next-line|ok|disable)\s*(?:=\s*([\w,\- ]+))?")


class Finding:
    """One diagnostic: where, what, how bad, and a move-stable identity."""

    __slots__ = ("check", "severity", "path", "line", "col", "message",
                 "scope", "fingerprint")

    def __init__(self, check: str, severity: str, path: str, line: int,
                 col: int, message: str, scope: str = "",
                 fingerprint: str = ""):
        assert severity in SEVERITIES, severity
        self.check = check
        self.severity = severity
        self.path = path
        self.line = int(line)
        self.col = int(col)
        self.message = message
        self.scope = scope
        self.fingerprint = fingerprint

    def sort_key(self):
        return (_SEV_RANK[self.severity], self.path, self.line, self.check)

    def to_dict(self) -> Dict:
        return {"check": self.check, "severity": self.severity,
                "path": self.path, "line": self.line, "col": self.col,
                "message": self.message, "scope": self.scope,
                "fingerprint": self.fingerprint}

    def format(self) -> str:
        where = "%s:%d:%d" % (self.path, self.line, self.col)
        scope = (" [%s]" % self.scope) if self.scope else ""
        return "%s: %s %s: %s%s" % (where, self.severity, self.check,
                                    self.message, scope)

    def __repr__(self) -> str:  # pragma: no cover - debugging aid
        return "Finding(%s)" % self.format()


def _parse_suppressions(lines: Sequence[str]) -> Dict[int, Set[str]]:
    """line number (1-based) -> set of suppressed check ids ('*' = all)."""
    out: Dict[int, Set[str]] = {}
    for i, line in enumerate(lines, start=1):
        m = _SUPPRESS_RE.search(line)
        if not m:
            continue
        kind, arg = m.group(1), m.group(2)
        checks = ({c.strip() for c in arg.split(",") if c.strip()}
                  if arg else {"*"})
        target = i + 1 if kind == "disable-next-line" else i
        out.setdefault(target, set()).update(checks)
    return out


class SourceFile:
    """One parsed module: source text, AST with parent links, and the
    per-line suppression table."""

    def __init__(self, abspath: str, rel: str, text: str):
        self.abspath = abspath
        self.rel = rel.replace(os.sep, "/")
        self.text = text
        self.lines = text.splitlines()
        self.tree = ast.parse(text, filename=rel)
        self.suppress = _parse_suppressions(self.lines)
        self._parents: Dict[ast.AST, ast.AST] = {}
        for node in ast.walk(self.tree):
            for child in ast.iter_child_nodes(node):
                self._parents[child] = node

    def parent(self, node: ast.AST) -> Optional[ast.AST]:
        return self._parents.get(node)

    def qualname(self, node: ast.AST) -> str:
        """Enclosing 'Class.method' (or 'func', or '<module>') of a node
        — the scope component of the fingerprint."""
        parts: List[str] = []
        cur: Optional[ast.AST] = node
        while cur is not None:
            if isinstance(cur, (ast.FunctionDef, ast.AsyncFunctionDef,
                                ast.ClassDef)):
                parts.append(cur.name)
            cur = self._parents.get(cur)
        return ".".join(reversed(parts)) or "<module>"

    def line_text(self, line: int) -> str:
        if 1 <= line <= len(self.lines):
            return self.lines[line - 1]
        return ""

    def is_suppressed(self, line: int, check: str) -> bool:
        checks = self.suppress.get(line)
        return bool(checks) and ("*" in checks or check in checks)


class Project:
    """The file set one lint run sees, plus the repo root for checkers
    that need non-Python inputs (docs/Parameters.md)."""

    def __init__(self, root: str, files: Sequence[SourceFile]):
        self.root = root
        self.files = list(files)
        self.by_rel = {f.rel: f for f in self.files}
        self._call_graph: Optional["CallGraph"] = None

    @property
    def call_graph(self) -> "CallGraph":
        """Lazy project-wide call graph (built once per run; the
        collectives, wireproto and lock-order analyses all share it)."""
        if self._call_graph is None:
            self._call_graph = CallGraph(self)
        return self._call_graph

    def iter_files(self, prefixes: Optional[Sequence[str]] = None
                   ) -> Iterable[SourceFile]:
        if prefixes is None:
            yield from self.files
            return
        for f in self.files:
            if any(f.rel.startswith(p) or f.rel == p.rstrip("/")
                   for p in prefixes):
                yield f


class Checker:
    """One checker family.  Subclasses set ``id``/``description`` and
    implement ``run`` over the whole project (cross-file checks like
    config drift and lock-order cycles need the global view)."""

    id = "base"
    description = ""

    def run(self, project: Project) -> Iterable[Finding]:  # pragma: no cover
        raise NotImplementedError

    def finding(self, sf: SourceFile, node: ast.AST, severity: str,
                message: str, check: Optional[str] = None) -> Finding:
        return Finding(check or self.id, severity, sf.rel,
                       getattr(node, "lineno", 1),
                       getattr(node, "col_offset", 0) + 1,
                       message, scope=sf.qualname(node))


# -- shared syntactic helpers ----------------------------------------------
#
# These used to live inside the lock checker; the collectives / wireproto /
# donation families need the same primitives, so they are core now.

MUTATOR_METHODS = frozenset({
    "append", "extend", "insert", "remove", "pop", "clear", "update",
    "add", "discard", "setdefault", "popitem", "sort", "reverse",
    "appendleft", "popleft"})

#: method names shared with dict/list/set/queue/thread — never resolve a
#: cross-object call edge through one of these; a ``.get()`` is
#: overwhelmingly a dict read, not a call into another analyzed class.
COMMON_CALL_NAMES = MUTATOR_METHODS | frozenset({
    "get", "keys", "values", "items", "copy", "put", "close", "join",
    "start", "stop", "wait", "notify", "notify_all", "acquire",
    "release", "send", "recv", "read", "write", "flush"})

#: cross-object call edges only when <= this many definitions share the name
AMBIGUITY_CAP = 3

LOCK_CTORS = frozenset({"Lock", "RLock"})


def self_attr(node: ast.AST) -> Optional[str]:
    """'x' when node is ``self.x``, else None."""
    if (isinstance(node, ast.Attribute)
            and isinstance(node.value, ast.Name) and node.value.id == "self"):
        return node.attr
    return None


def lock_ctor_name(value: ast.AST) -> Optional[str]:
    """'Lock' / 'RLock' / 'Condition' when value is ``threading.X(...)``."""
    if not isinstance(value, ast.Call):
        return None
    f = value.func
    if isinstance(f, ast.Attribute) and f.attr in LOCK_CTORS | {"Condition"}:
        return f.attr
    if isinstance(f, ast.Name) and f.id in LOCK_CTORS | {"Condition"}:
        return f.id
    return None


def shallow_exprs(stmt: ast.stmt) -> Iterable[ast.AST]:
    """Expression-level nodes belonging to this statement, without
    descending into nested statements, nested defs, or lambda bodies
    (those do not execute at the statement's own control point)."""
    stack: List[ast.AST] = []

    def push_children(n: ast.AST) -> None:
        for child in ast.iter_child_nodes(n):
            if isinstance(child, (ast.stmt, ast.FunctionDef,
                                  ast.AsyncFunctionDef, ast.Lambda,
                                  ast.excepthandler)):
                continue
            stack.append(child)

    push_children(stmt)
    while stack:
        n = stack.pop()
        yield n
        if not isinstance(n, ast.Lambda):
            push_children(n)


def expr_text(node: ast.AST) -> str:
    """Dotted text of a Name/Attribute chain ('self.comm', 'jax.lax'),
    or '' when the expression is anything more dynamic."""
    parts: List[str] = []
    cur = node
    while isinstance(cur, ast.Attribute):
        parts.append(cur.attr)
        cur = cur.value
    if isinstance(cur, ast.Name):
        parts.append(cur.id)
        return ".".join(reversed(parts))
    return ""


def binding_key(node: ast.AST) -> Optional[str]:
    """Stable key for a rebindable storage location: a plain name
    ('arena'), a dotted attribute chain ('self._arena',
    'self.train_state.score'), or a constant-keyed subscript
    ('state["arena"]').  None for fresh temporaries / dynamic refs."""
    if isinstance(node, ast.Subscript):
        base = expr_text(node.value)
        sl = node.slice
        if base and isinstance(sl, ast.Constant):
            return "%s[%r]" % (base, sl.value)
        return None
    text = expr_text(node)
    return text or None


def call_name(call: ast.Call) -> Tuple[str, str]:
    """(simple callee name, receiver text) — ('allgather', 'self.comm')
    for ``self.comm.allgather(x)``, ('psum', 'jax.lax') for
    ``jax.lax.psum(...)``, ('f', '') for ``f(x)``."""
    f = call.func
    if isinstance(f, ast.Attribute):
        return f.attr, expr_text(f.value)
    if isinstance(f, ast.Name):
        return f.id, ""
    return "", ""


# -- call graph + path-sensitive call contexts ------------------------------

class ControlCtx:
    """The control-flow path context a call executes under: the stack of
    enclosing branch/loop statements (as (kind, stmt) pairs, kind in
    {'if', 'else', 'while', 'for'}) and the with-contexts held."""

    __slots__ = ("branches", "withs")

    def __init__(self, branches: Tuple = (), withs: Tuple = ()):
        self.branches = branches
        self.withs = withs

    def push_branch(self, kind: str, stmt: ast.stmt) -> "ControlCtx":
        return ControlCtx(self.branches + ((kind, stmt),), self.withs)

    def push_withs(self, exprs: Sequence[ast.AST]) -> "ControlCtx":
        return ControlCtx(self.branches, self.withs + tuple(exprs))


class CallSite:
    """One call expression inside a function, with its path context."""

    __slots__ = ("node", "name", "recv", "ctx")

    def __init__(self, node: ast.Call, name: str, recv: str,
                 ctx: ControlCtx):
        self.node = node
        self.name = name
        self.recv = recv
        self.ctx = ctx


class FunctionInfo:
    """One function/method definition in the project."""

    __slots__ = ("sf", "node", "qualname", "key", "calls")

    def __init__(self, sf: SourceFile, node: ast.AST):
        self.sf = sf
        self.node = node
        self.qualname = sf.qualname(node)
        self.key = "%s:%s:%d" % (sf.rel, self.qualname, node.lineno)
        self.calls: List[CallSite] = []


class CallGraph:
    """Project-wide, name-resolved call graph.  Every def/method becomes
    a FunctionInfo whose ``calls`` carry path-sensitive ControlCtx;
    ``resolve`` maps a simple callee name to candidate definitions with
    the shared ambiguity cap, so interprocedural checks (collective
    reachability, cross-module lock order) share one resolution policy."""

    def __init__(self, project: "Project"):
        self.functions: Dict[str, FunctionInfo] = {}
        self.by_name: Dict[str, List[FunctionInfo]] = {}
        for sf in project.files:
            for node in ast.walk(sf.tree):
                if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                    fi = FunctionInfo(sf, node)
                    self._collect_calls(fi)
                    self.functions[fi.key] = fi
                    self.by_name.setdefault(node.name, []).append(fi)

    def resolve(self, name: str, cap: Optional[int] = AMBIGUITY_CAP,
                allow_common: bool = False) -> List[FunctionInfo]:
        """Candidate definitions for a simple callee name.  Empty when
        the name is too common to resolve or has more than ``cap``
        definitions (ambiguous edges create false positives)."""
        if not name or (not allow_common and name in COMMON_CALL_NAMES):
            return []
        cands = self.by_name.get(name, [])
        if cap is not None and len(cands) > cap:
            return []
        return list(cands)

    def _collect_calls(self, fi: FunctionInfo) -> None:
        def record(expr: ast.AST, ctx: ControlCtx) -> None:
            stack: List[ast.AST] = [expr]
            while stack:
                n = stack.pop()
                if isinstance(n, ast.Lambda):
                    continue        # lambda bodies run later, elsewhere
                if isinstance(n, ast.Call):
                    name, recv = call_name(n)
                    fi.calls.append(CallSite(n, name, recv, ctx))
                stack.extend(ast.iter_child_nodes(n))

        def walk(body: Sequence[ast.stmt], ctx: ControlCtx) -> None:
            for stmt in body:
                if isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef,
                                     ast.ClassDef)):
                    continue        # separate FunctionInfo / class scope
                if isinstance(stmt, ast.If):
                    record(stmt.test, ctx)
                    walk(stmt.body, ctx.push_branch("if", stmt))
                    walk(stmt.orelse, ctx.push_branch("else", stmt))
                elif isinstance(stmt, ast.While):
                    record(stmt.test, ctx)
                    walk(stmt.body, ctx.push_branch("while", stmt))
                    walk(stmt.orelse, ctx)
                elif isinstance(stmt, (ast.For, ast.AsyncFor)):
                    record(stmt.iter, ctx)  # iter evaluates once, outside
                    walk(stmt.body, ctx.push_branch("for", stmt))
                    walk(stmt.orelse, ctx)
                elif isinstance(stmt, (ast.With, ast.AsyncWith)):
                    exprs = []
                    for item in stmt.items:
                        record(item.context_expr, ctx)
                        exprs.append(item.context_expr)
                    walk(stmt.body, ctx.push_withs(exprs))
                elif isinstance(stmt, ast.Try):
                    walk(stmt.body, ctx)
                    for h in stmt.handlers:
                        walk(h.body, ctx)
                    walk(stmt.orelse, ctx)
                    walk(stmt.finalbody, ctx)
                else:
                    for n in shallow_exprs(stmt):
                        if isinstance(n, ast.Call):
                            name, recv = call_name(n)
                            fi.calls.append(CallSite(n, name, recv, ctx))

        walk(fi.node.body, ControlCtx())


# -- fingerprints ----------------------------------------------------------

def _norm_line(text: str) -> str:
    return " ".join(text.split())


def assign_fingerprints(findings: List[Finding],
                        by_rel: Dict[str, SourceFile]) -> None:
    """Stable identity: sha1(check | basename | scope | normalized line
    | k) where k disambiguates identical lines within one scope by
    order of appearance.  Deliberately excludes directory and line
    number so renames/moves and unrelated edits don't churn the
    baseline."""
    seen: Dict[Tuple, int] = {}
    for f in sorted(findings, key=lambda x: (x.path, x.line, x.col, x.check)):
        sf = by_rel.get(f.path)
        line_text = _norm_line(sf.line_text(f.line)) if sf else ""
        key = (f.check, os.path.basename(f.path), f.scope, line_text)
        k = seen.get(key, 0)
        seen[key] = k + 1
        blob = "|".join((f.check, os.path.basename(f.path), f.scope,
                         line_text, str(k)))
        f.fingerprint = hashlib.sha1(blob.encode("utf-8")).hexdigest()[:16]


# -- file collection and the suite entry point -----------------------------

DEFAULT_ROOTS = ("lightgbm_tpu", "tools", "chip_smoke.py")
_SKIP_DIRS = {"__pycache__", ".git", "node_modules"}


def collect_files(root: str, paths: Optional[Sequence[str]] = None
                  ) -> Tuple[List[SourceFile], List[Finding]]:
    """Load every .py under the default roots (or the explicit paths).
    Unparseable files become parse-error findings instead of crashing
    the run — a linter that dies on bad input can't gate anything."""
    targets: List[str] = []
    for p in (paths or DEFAULT_ROOTS):
        absp = p if os.path.isabs(p) else os.path.join(root, p)
        if os.path.isfile(absp):
            targets.append(absp)
        elif os.path.isdir(absp):
            for dirpath, dirnames, filenames in os.walk(absp):
                dirnames[:] = sorted(d for d in dirnames
                                     if d not in _SKIP_DIRS)
                for fn in sorted(filenames):
                    if fn.endswith(".py"):
                        targets.append(os.path.join(dirpath, fn))
    files: List[SourceFile] = []
    errors: List[Finding] = []
    for absp in targets:
        rel = os.path.relpath(absp, root).replace(os.sep, "/")
        try:
            with open(absp, encoding="utf-8") as fh:
                text = fh.read()
            files.append(SourceFile(absp, rel, text))
        except (SyntaxError, UnicodeDecodeError, OSError) as e:
            line = getattr(e, "lineno", 1) or 1
            errors.append(Finding("parse-error", HIGH, rel, line, 1,
                                  "cannot analyze: %s" % e))
    return files, errors


def run_suite(root: str, paths: Optional[Sequence[str]] = None,
              only: Optional[Sequence[str]] = None) -> List[Finding]:
    """Run every registered checker (or the ``only`` subset) and return
    fingerprinted, suppression-filtered, severity-sorted findings."""
    from .checkers import all_checkers

    files, findings = collect_files(root, paths)
    project = Project(root, files)
    for checker in all_checkers():
        if only and checker.id not in only:
            continue
        findings.extend(checker.run(project))
    findings = [f for f in findings
                if not (f.path in project.by_rel
                        and project.by_rel[f.path].is_suppressed(f.line,
                                                                 f.check))]
    assign_fingerprints(findings, project.by_rel)
    findings.sort(key=Finding.sort_key)
    return findings


def severity_counts(findings: Iterable[Finding]) -> Dict[str, int]:
    out = {s: 0 for s in SEVERITIES}
    for f in findings:
        out[f.severity] += 1
    return out
