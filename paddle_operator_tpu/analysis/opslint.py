"""opslint — AST lint passes for the operator's own invariants.

Generic linters cannot know that every field ever guarded by
``self._lock`` must always be guarded, that every ``threading.Thread``
in this codebase must be named and daemon-or-joined, that a
``Reconciler`` method must never block, or that every emitted metric
family needs a ``# TYPE`` declaration and a ``tpujob_`` prefix. PR 2 and
PR 3 each shipped hand-found bugs of exactly these classes (workqueue
key-drop wedge, unlocked barrier bookkeeping, racy error-streak gauge);
these passes find them systematically.

Engine contract:

* :func:`lint_source` / :func:`lint_paths` return :class:`Finding`s.
* Suppression: a ``# opslint: disable=OPS101[,OPS201]`` comment on the
  flagged line (or the line above it) silences those rules there.
* Baseline: :func:`load_baseline` / :func:`apply_baseline` split
  findings into new vs accepted-pre-existing by a line-number-free
  fingerprint, so moving code does not churn the baseline.

All passes are purely syntactic (``ast`` + the raw source for comment
scanning); nothing is imported or executed.
"""

from __future__ import annotations

import ast
import hashlib
import json
import os
import re
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Sequence, Set, Tuple

# rule id -> (name, one-line description)
RULES: Dict[str, Tuple[str, str]] = {
    "OPS001": (
        "stale-suppression",
        "an `# opslint: disable=...` comment (or a baseline fingerprint) "
        "that no longer matches any finding: suppressions must shrink "
        "with the findings they silence — delete the comment, or "
        "--prune-baseline",
    ),
    "OPS101": (
        "lock-discipline",
        "attribute written under a lock is read/written outside any "
        "holder of that lock",
    ),
    "OPS201": (
        "thread-name",
        "threading.Thread(...) without a name= kwarg",
    ),
    "OPS202": (
        "thread-leak",
        "threading.Thread neither daemon=True nor joined anywhere in "
        "its class/module",
    ),
    "OPS301": (
        "reconcile-blocking",
        "blocking call (time.sleep / blocking socket I/O) inside a "
        "Reconciler method",
    ),
    "OPS302": (
        "raw-http-in-controller",
        "raw HTTP (urllib.request/http.client/requests) in reconcile "
        "code: k8s mutations must go through the client wrapper",
    ),
    "OPS501": (
        "recompile-hazard",
        "jax.jit(...) call on a per-step path (inside a loop body, or in "
        "a function reachable from one): every invocation builds a NEW "
        "jit wrapper whose compile cache dies with it — hoist it out of "
        "the loop or route it through compile_cache.cached_jit",
    ),
    "OPS502": (
        "jit-nonhashable-static",
        "argument at a jit static_argnums position is a list/dict/set "
        "(unhashable): every call raises or, with a tuple-coerced "
        "workaround, silently recompiles per distinct value",
    ),
    "OPS401": (
        "metric-undeclared",
        "emitted metric family has no # TYPE declaration or registry "
        "entry anywhere in the package",
    ),
    "OPS402": (
        "metric-prefix",
        "metric family does not carry the tpujob_ prefix",
    ),
    "OPS403": (
        "metric-labels",
        "metric family emitted with inconsistent label sets",
    ),
}

_LOCK_FACTORIES = {"Lock", "RLock", "Condition", "Semaphore",
                   "BoundedSemaphore"}
_LOCKISH_ATTR = re.compile(r"(lock|cond|cv|mutex)", re.IGNORECASE)
_METRIC_FAMILY = re.compile(r"^[a-z_:][a-z0-9_:]*$")
_METRIC_PREFIX = "tpujob_"
_METRIC_TYPES = {"counter", "gauge", "histogram", "summary", "untyped"}
# sample-looking string literal: family then '{' or ' ' (value/format)
_SAMPLE_RE = re.compile(r"^([a-zA-Z_:][a-zA-Z0-9_:]*)(\{|\s)")
_TYPE_LINE_RE = re.compile(
    r"# TYPE ([a-zA-Z_:][a-zA-Z0-9_:]*) (counter|gauge|histogram|summary"
    r"|untyped)")
_LABEL_NAME_RE = re.compile(r"([a-zA-Z_][a-zA-Z0-9_]*)=")
_DISABLE_RE = re.compile(r"#\s*opslint:\s*disable=([A-Z0-9, ]+)")


@dataclass
class Finding:
    rule: str
    path: str
    line: int
    message: str
    symbol: str = ""

    def fingerprint(self) -> str:
        """Stable id for baselining: rule + path + symbol + message —
        deliberately line-number-free so unrelated edits above a finding
        do not churn the baseline."""
        raw = "|".join((self.rule, self.path, self.symbol, self.message))
        return hashlib.sha1(raw.encode()).hexdigest()[:16]

    def render(self) -> str:
        return "%s:%d: %s [%s] %s" % (
            self.path, self.line, self.rule, RULES[self.rule][0],
            self.message)


def suppression_sites(source: str) -> List[Tuple[int, Set[str]]]:
    """(comment line, rule ids) for every disable pragma — the raw
    sites, for the OPS001 stale-suppression audit. Only real COMMENT
    tokens count: a docstring *describing* the pragma syntax is neither
    a suppression nor a stale one."""
    import io
    import tokenize

    out: List[Tuple[int, Set[str]]] = []
    try:
        tokens = list(tokenize.generate_tokens(
            io.StringIO(source).readline))
    except (tokenize.TokenError, IndentationError, SyntaxError):
        tokens = []
    for tok in tokens:
        if tok.type != tokenize.COMMENT:
            continue
        m = _DISABLE_RE.search(tok.string)
        if not m:
            continue
        rules = {r.strip() for r in m.group(1).split(",") if r.strip()}
        out.append((tok.start[0], rules))
    return out


def _suppressed_lines(source: str) -> Dict[int, Set[str]]:
    """line number -> rule ids disabled on that line (a disable comment
    also covers the line directly below it, for statements too long to
    share a line with the pragma)."""
    out: Dict[int, Set[str]] = {}
    for i, rules in suppression_sites(source):
        out.setdefault(i, set()).update(rules)
        out.setdefault(i + 1, set()).update(rules)
    return out


def _is_self_attr(node: ast.AST) -> Optional[str]:
    if (isinstance(node, ast.Attribute)
            and isinstance(node.value, ast.Name)
            and node.value.id == "self"):
        return node.attr
    return None


def _call_name(node: ast.Call) -> str:
    """Dotted name of a call target ('threading.Thread', 'Thread')."""
    parts: List[str] = []
    cur: ast.AST = node.func
    while isinstance(cur, ast.Attribute):
        parts.append(cur.attr)
        cur = cur.value
    if isinstance(cur, ast.Name):
        parts.append(cur.id)
    return ".".join(reversed(parts))


class _Union:
    """Tiny union-find over lock-attribute names (Condition(self._lock)
    aliases _cv with _lock — acquiring either guards the same state)."""

    def __init__(self) -> None:
        self._parent: Dict[str, str] = {}

    def add(self, name: str) -> None:
        self._parent.setdefault(name, name)

    def find(self, name: str) -> str:
        self.add(name)
        root = name
        while self._parent[root] != root:
            root = self._parent[root]
        while self._parent[name] != root:
            self._parent[name], name = root, self._parent[name]
        return root

    def union(self, a: str, b: str) -> None:
        ra, rb = self.find(a), self.find(b)
        if ra != rb:
            self._parent[ra] = rb

    def known(self, name: str) -> bool:
        return name in self._parent


@dataclass
class _Access:
    attr: str
    line: int
    func: str
    groups: Tuple[str, ...]  # lock groups held (lexically) at the access
    is_write: bool


_EXEMPT_FUNCS = {"__init__", "__del__", "__enter__", "__exit__"}


class _ClassScanner:
    """Collects lock attrs + attribute accesses for one class."""

    def __init__(self, cls: ast.ClassDef) -> None:
        self.cls = cls
        self.locks = _Union()
        self.accesses: List[_Access] = []
        self._find_locks()
        for fn in self._methods(cls):
            self._scan_func(fn, fn.name, ())

    def _match(self, node: ast.AST) -> Optional[str]:
        """The guarded-state matcher: ``self.<attr>`` here; overridden by
        the module-scope scanner to match global names instead."""
        return _is_self_attr(node)

    @staticmethod
    def _methods(cls: ast.ClassDef) -> List[ast.FunctionDef]:
        return [n for n in cls.body
                if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))]

    def _find_locks(self) -> None:
        for fn in self._methods(self.cls):
            for node in ast.walk(fn):
                if not isinstance(node, ast.Assign):
                    continue
                if not isinstance(node.value, ast.Call):
                    continue
                callee = _call_name(node.value)
                short = callee.rsplit(".", 1)[-1]
                if short not in _LOCK_FACTORIES:
                    continue
                for tgt in node.targets:
                    attr = self._match(tgt)
                    if attr is None:
                        continue
                    self.locks.add(attr)
                    # Condition(self._lock): either name guards the state
                    for arg in node.value.args:
                        wrapped = self._match(arg)
                        if wrapped is not None:
                            self.locks.union(attr, wrapped)

    # -- lexical scan ---------------------------------------------------

    def _with_groups(self, node: ast.With) -> List[str]:
        out = []
        for item in node.items:
            expr = item.context_expr
            attr = self._match(expr)
            if attr is not None and self.locks.known(attr):
                out.append(self.locks.find(attr))
        return out

    def _scan_func(self, fn: ast.AST, func_name: str,
                   groups: Tuple[str, ...]) -> None:
        """Walk one function body tracking active lock groups; descends
        into nested functions (closures capture the same ``self``) but
        NOT nested classes (their ``self`` is a different object)."""
        body = getattr(fn, "body", [])
        for stmt in body:
            self._scan_stmt(stmt, func_name, groups)

    def _scan_stmt(self, node: ast.AST, func_name: str,
                   groups: Tuple[str, ...]) -> None:
        if isinstance(node, ast.ClassDef):
            return  # different self
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            # a closure over self: lexical lock context does NOT carry
            # into it (it runs later, on another thread as often as not)
            self._scan_func(node, func_name, ())
            return
        if isinstance(node, ast.With):
            inner = tuple(dict.fromkeys(
                groups + tuple(self._with_groups(node))))
            for expr_item in node.items:
                self._scan_expr(expr_item.context_expr, func_name, groups)
            for stmt in node.body:
                self._scan_stmt(stmt, func_name, inner)
            return
        # statements with expression children + nested statement bodies
        for fname in ("body", "orelse", "finalbody", "handlers"):
            sub = getattr(node, fname, None)
            if isinstance(sub, list) and sub and isinstance(
                    sub[0], (ast.stmt, ast.excepthandler)):
                for stmt in sub:
                    self._scan_stmt(stmt, func_name, groups)
        if isinstance(node, ast.excepthandler):
            return
        self._record_targets(node, func_name, groups)
        for child in ast.iter_child_nodes(node):
            if isinstance(child, (ast.stmt, ast.excepthandler)):
                continue  # handled above
            self._scan_expr(child, func_name, groups)

    def _record_targets(self, node: ast.AST, func_name: str,
                        groups: Tuple[str, ...]) -> None:
        targets: List[ast.AST] = []
        if isinstance(node, ast.Assign):
            targets = list(node.targets)
        elif isinstance(node, (ast.AugAssign, ast.AnnAssign)):
            targets = [node.target]
        elif isinstance(node, ast.Delete):
            targets = list(node.targets)
        elif isinstance(node, ast.For):
            targets = [node.target]
        for tgt in targets:
            for sub in ast.walk(tgt):
                attr = self._match(sub)
                if attr is not None:
                    self.accesses.append(_Access(
                        attr, sub.lineno, func_name, groups, True))
                elif (isinstance(sub, ast.Subscript)):
                    base = self._match(sub.value)
                    if base is not None:
                        self.accesses.append(_Access(
                            base, sub.lineno, func_name, groups, True))

    def _scan_expr(self, node: ast.AST, func_name: str,
                   groups: Tuple[str, ...]) -> None:
        for sub in ast.walk(node):
            if isinstance(sub, (ast.FunctionDef, ast.AsyncFunctionDef,
                                ast.Lambda, ast.ClassDef)):
                continue
            attr = self._match(sub)
            if attr is None:
                continue
            is_write = isinstance(getattr(sub, "ctx", None),
                                  (ast.Store, ast.Del))
            # subscript store through the attr (self.d[k] = v) arrives
            # here with Load ctx on the Attribute; _record_targets
            # catches the write side — Load here is still an access
            self.accesses.append(_Access(
                attr, sub.lineno, func_name, groups, is_write))


class _Pass:
    rule_ids: Tuple[str, ...] = ()

    def run(self, path: str, tree: ast.Module,
            source: str) -> List[Finding]:  # pragma: no cover - interface
        raise NotImplementedError


class _ModuleScanner(_ClassScanner):
    """Module-scope twin of :class:`_ClassScanner`: module-level locks
    (``_gc_lock = threading.Lock()``) guarding module GLOBALS — names a
    module function declares ``global`` and writes under ``with <lock>:``
    (the checkpoint-layer observer/GC pattern). Per function, a global
    shadowed by a plain local assignment (no ``global`` decl) is not
    tracked there."""

    def __init__(self, tree: ast.Module) -> None:
        self.cls = None
        self.locks = _Union()
        self.accesses: List[_Access] = []
        self._tracked: Set[str] = set()
        for node in tree.body:
            if (not isinstance(node, ast.Assign)
                    or not isinstance(node.value, ast.Call)):
                continue
            callee = _call_name(node.value)
            if callee.rsplit(".", 1)[-1] not in _LOCK_FACTORIES:
                continue
            for tgt in node.targets:
                if isinstance(tgt, ast.Name):
                    self.locks.add(tgt.id)
                    for arg in node.value.args:
                        if isinstance(arg, ast.Name):
                            self.locks.union(tgt.id, arg.id)
        funcs = [n for n in tree.body
                 if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))]
        module_globals: Set[str] = set()
        for fn in funcs:
            for node in ast.walk(fn):
                if isinstance(node, ast.Global):
                    module_globals.update(node.names)
        for fn in funcs:
            decls: Set[str] = set()
            shadowed: Set[str] = set()
            for node in ast.walk(fn):
                if isinstance(node, ast.Global):
                    decls.update(node.names)
                elif isinstance(node, ast.Name) and isinstance(
                        node.ctx, (ast.Store, ast.Del)):
                    shadowed.add(node.id)
            self._tracked = module_globals - (shadowed - decls)
            self._scan_func(fn, fn.name, ())

    def _match(self, node: ast.AST) -> Optional[str]:
        if isinstance(node, ast.Name) and (
                node.id in self._tracked or self.locks.known(node.id)):
            return node.id
        return None


class LockDisciplinePass(_Pass):
    """OPS101: state ever *written* under ``with <lock>`` in non-init
    code is lock-owned; any later read or write of it outside a holder of
    that lock (or an alias — ``Condition(self._lock)``) is a race. Two
    scopes share one audit: class attributes guarded by ``self.<lock>``
    (:class:`_ClassScanner`) and module globals guarded by a module-level
    lock (:class:`_ModuleScanner` — the checkpoint GC/observer pattern).
    Helper methods named ``*_locked`` are assumed to run under the lock
    (the ``_prune_locked`` convention) and are exempt."""

    rule_ids = ("OPS101",)

    def run(self, path: str, tree: ast.Module,
            source: str) -> List[Finding]:
        findings: List[Finding] = []
        for cls in [n for n in ast.walk(tree)
                    if isinstance(n, ast.ClassDef)]:
            findings.extend(self._audit(_ClassScanner(cls), cls.name, path))
        findings.extend(self._audit(_ModuleScanner(tree), "<module>", path))
        return findings

    @staticmethod
    def _audit(scan: _ClassScanner, label: str,
               path: str) -> List[Finding]:
        findings: List[Finding] = []
        owner: Dict[str, Optional[str]] = {}
        for acc in scan.accesses:
            if not acc.is_write or not acc.groups:
                continue
            if acc.func in _EXEMPT_FUNCS or acc.func.endswith("_locked"):
                continue
            if scan.locks.known(acc.attr):
                continue  # the lock itself
            prev = owner.get(acc.attr, acc.groups[-1])
            # written under two different locks: ambiguous, skip
            owner[acc.attr] = (acc.groups[-1]
                               if prev == acc.groups[-1] else None)
        # one finding per (attr, line, method) — an assignment target
        # is visited both as a target and as an expression, and a
        # write subsumes the read half of the same access
        flagged: Dict[Tuple[str, int, str], _Access] = {}
        for acc in scan.accesses:
            grp = owner.get(acc.attr)
            if grp is None:
                continue
            if acc.func in _EXEMPT_FUNCS or acc.func.endswith("_locked"):
                continue
            if grp in acc.groups:
                continue
            key = (acc.attr, acc.line, acc.func)
            prev = flagged.get(key)
            if prev is None or (acc.is_write and not prev.is_write):
                flagged[key] = acc
        for acc in flagged.values():
            findings.append(Finding(
                "OPS101", path, acc.line,
                "%s.%s is lock-owned (guarded writes exist) but is "
                "%s here without holding the lock" % (
                    label, acc.attr,
                    "written" if acc.is_write else "read"),
                symbol="%s.%s.%s" % (label, acc.func, acc.attr)))
        return findings


class ThreadHygienePass(_Pass):
    """OPS201/OPS202: every ``threading.Thread`` must carry ``name=`` —
    an anonymous ``Thread-7`` in a stack dump of a wedged operator is
    useless — and must be ``daemon=True`` or joined somewhere in its
    module, or process exit hangs on it forever."""

    rule_ids = ("OPS201", "OPS202")

    @staticmethod
    def _target_name(node: ast.AST) -> Optional[str]:
        if isinstance(node, ast.Name):
            return node.id
        if isinstance(node, ast.Attribute):
            return node.attr
        return None

    def run(self, path: str, tree: ast.Module,
            source: str) -> List[Finding]:
        findings: List[Finding] = []
        # names (variable or self-attribute) ever assigned from a
        # threading.Thread call — only a .join() on one of THOSE counts
        # as joining a thread (os.path.join / sep.join must not satisfy
        # the rule for an unrelated leaked thread)
        thread_names: Set[str] = set()
        assigned_name: Dict[int, str] = {}  # id(Thread call) -> name
        for node in ast.walk(tree):
            if not isinstance(node, ast.Assign):
                continue
            if not (isinstance(node.value, ast.Call) and _call_name(
                    node.value) in ("threading.Thread", "Thread")):
                continue
            for tgt in node.targets:
                name = self._target_name(tgt)
                if name is not None:
                    thread_names.add(name)
                    assigned_name[id(node.value)] = name
        joined_names: Set[str] = set()
        for node in ast.walk(tree):
            if (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)
                    and node.func.attr == "join"):
                recv = self._target_name(node.func.value)
                if recv is not None:
                    joined_names.add(recv)
        seq = 0
        for node in ast.walk(tree):
            if not isinstance(node, ast.Call):
                continue
            callee = _call_name(node)
            if callee not in ("threading.Thread", "Thread"):
                continue
            seq += 1
            kwargs = {kw.arg: kw.value for kw in node.keywords if kw.arg}
            sym = "thread#%d" % seq
            tgt = kwargs.get("target")
            if tgt is not None:
                sym = ast.unparse(tgt) if hasattr(ast, "unparse") else sym
            if "name" not in kwargs:
                findings.append(Finding(
                    "OPS201", path, node.lineno,
                    "threading.Thread without name= (target=%s): name "
                    "every thread so stack dumps and leak reports are "
                    "attributable" % sym,
                    symbol=sym))
            daemon = kwargs.get("daemon")
            is_daemon = (isinstance(daemon, ast.Constant)
                         and daemon.value is True)
            joined = assigned_name.get(id(node)) in joined_names
            if not is_daemon and not joined:
                findings.append(Finding(
                    "OPS202", path, node.lineno,
                    "threading.Thread (target=%s) is neither daemon=True "
                    "nor joined anywhere in this module: process exit "
                    "will hang on it" % sym,
                    symbol=sym))
        return findings


_BLOCKING_CALLS = {
    "time.sleep": "OPS301",
    "socket.create_connection": "OPS301",
    "urllib.request.urlopen": "OPS302",
    "urlopen": "OPS302",
    "requests.get": "OPS302",
    "requests.post": "OPS302",
    "http.client.HTTPConnection": "OPS302",
    "http.client.HTTPSConnection": "OPS302",
}

# modules where even imports of raw-HTTP machinery are banned: the
# reconcile path must mutate k8s only through the KubeClient wrapper so
# chaos middleware and the informer write-through see every mutation
_PURE_CONTROLLER_MODULES = ("controllers/reconciler.py",
                            "controllers/helper.py")


class ReconcilePurityPass(_Pass):
    """OPS301/OPS302: a reconcile pass runs on the controller worker —
    a ``time.sleep`` there stalls the whole workqueue (use
    ``Result(requeue_after=...)``), and raw HTTP bypasses the client
    wrapper the chaos harness and informer write-through interpose on."""

    rule_ids = ("OPS301", "OPS302")

    def run(self, path: str, tree: ast.Module,
            source: str) -> List[Finding]:
        findings: List[Finding] = []
        norm = path.replace(os.sep, "/")
        pure_module = any(norm.endswith(m)
                          for m in _PURE_CONTROLLER_MODULES)
        if pure_module:
            for node in ast.walk(tree):
                banned = None
                if isinstance(node, ast.Import):
                    for alias in node.names:
                        if alias.name.split(".")[0] in (
                                "urllib", "requests") or alias.name in (
                                "http.client",):
                            banned = alias.name
                elif isinstance(node, ast.ImportFrom) and node.module:
                    if node.module.split(".")[0] in ("urllib", "requests") \
                            or node.module == "http.client":
                        banned = node.module
                if banned:
                    findings.append(Finding(
                        "OPS302", path, node.lineno,
                        "import of %r in reconcile-path module: k8s "
                        "mutations must go through the KubeClient "
                        "wrapper" % banned,
                        symbol="import.%s" % banned))
        for cls in [n for n in ast.walk(tree)
                    if isinstance(n, ast.ClassDef)
                    and "Reconciler" in n.name]:
            for node in ast.walk(cls):
                if not isinstance(node, ast.Call):
                    continue
                callee = _call_name(node)
                rule = _BLOCKING_CALLS.get(callee)
                if rule is None:
                    continue
                findings.append(Finding(
                    rule, path, node.lineno,
                    "%s inside Reconciler class %s: reconcile passes "
                    "must not block (use Result(requeue_after=...)) or "
                    "bypass the client wrapper" % (callee, cls.name),
                    symbol="%s.%s" % (cls.name, callee)))
        return findings


_JIT_NAMES = ("jax.jit", "jit", "jax.pjit", "pjit")


class RecompileHazardPass(_Pass):
    """OPS501/OPS502: the cold-start work (PR 8) makes compilation a
    managed resource — a stray ``jax.jit(...)`` executed per step defeats
    it silently. Every ``jax.jit`` call builds a NEW wrapper object with
    its own in-memory compile cache; constructed inside a per-step or
    per-reconcile path (a loop body, or any module-local function
    reachable from one through the module's call graph) it re-traces —
    and without the persistent cache re-COMPILES — on every iteration.
    OPS502 flags call sites that pass a list/dict/set at a declared
    ``static_argnums`` position: unhashable statics raise at best and
    recompile per distinct value at worst.

    Purely module-local by design: a loop calling an imported builder
    (``build_train_step``) is the sanctioned pattern — the builder's own
    module is linted in its own right.
    """

    rule_ids = ("OPS501", "OPS502")

    @staticmethod
    def _called_names(node: ast.AST) -> Set[str]:
        out: Set[str] = set()
        for sub in ast.walk(node):
            if isinstance(sub, ast.Call):
                callee = _call_name(sub)
                if callee:
                    out.add(callee.rsplit(".", 1)[-1])
        return out

    def run(self, path: str, tree: ast.Module,
            source: str) -> List[Finding]:
        findings: List[Finding] = []
        funcs: Dict[str, ast.AST] = {}
        for node in ast.walk(tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                funcs[node.name] = node

        # seeds: names called from any For/While body (the loop statement
        # itself, not its else clause — else runs once)
        seeds: Set[str] = set()
        loop_bodies: List[ast.AST] = []
        for node in ast.walk(tree):
            if isinstance(node, (ast.For, ast.AsyncFor, ast.While)):
                loop_bodies.extend(node.body)
        for stmt in loop_bodies:
            seeds |= self._called_names(stmt)

        # transitive closure over the module-local call graph
        reachable: Set[str] = set()
        frontier = [n for n in seeds if n in funcs]
        while frontier:
            name = frontier.pop()
            if name in reachable:
                continue
            reachable.add(name)
            frontier.extend(n for n in self._called_names(funcs[name])
                            if n in funcs and n not in reachable)

        def flag_jits(scope: ast.AST, where: str) -> None:
            for sub in ast.walk(scope):
                if (isinstance(sub, ast.Call)
                        and _call_name(sub) in _JIT_NAMES):
                    findings.append(Finding(
                        "OPS501", path, sub.lineno,
                        "jax.jit constructed on a per-step path (%s): "
                        "hoist it above the loop or use "
                        "compile_cache.cached_jit" % where,
                        symbol="%s.jit" % where))

        for stmt in loop_bodies:
            flag_jits(stmt, "loop body")
        for name in sorted(reachable):
            flag_jits(funcs[name], name)

        findings.extend(self._nonhashable_statics(path, tree))
        return findings

    @staticmethod
    def _static_positions(call: ast.Call) -> Tuple[int, ...]:
        """Declared static_argnums of a jax.jit(...) call, when literal."""
        for kw in call.keywords:
            if kw.arg != "static_argnums":
                continue
            v = kw.value
            if isinstance(v, ast.Constant) and isinstance(v.value, int):
                return (v.value,)
            if isinstance(v, (ast.Tuple, ast.List)):
                out = []
                for e in v.elts:
                    if (isinstance(e, ast.Constant)
                            and isinstance(e.value, int)):
                        out.append(e.value)
                return tuple(out)
        return ()

    _UNHASHABLE = (ast.List, ast.Dict, ast.Set, ast.ListComp, ast.DictComp,
                   ast.SetComp, ast.GeneratorExp)

    def _nonhashable_statics(self, path: str,
                             tree: ast.Module) -> List[Finding]:
        findings: List[Finding] = []
        # jitted-name -> static positions (adjusted for the wrapped fn's
        # signature: static_argnums counts the ORIGINAL args, which map
        # 1:1 onto the wrapper's)
        jitted: Dict[str, Tuple[int, ...]] = {}
        for node in ast.walk(tree):
            if not isinstance(node, ast.Assign):
                continue
            if not (isinstance(node.value, ast.Call)
                    and _call_name(node.value) in _JIT_NAMES):
                continue
            statics = self._static_positions(node.value)
            if not statics:
                continue
            for tgt in node.targets:
                if isinstance(tgt, ast.Name):
                    jitted[tgt.id] = statics

        def check_call(call: ast.Call, statics: Tuple[int, ...],
                       sym: str) -> None:
            for pos in statics:
                if pos < len(call.args) and isinstance(
                        call.args[pos], self._UNHASHABLE):
                    findings.append(Finding(
                        "OPS502", path, call.args[pos].lineno,
                        "unhashable literal passed at static_argnums "
                        "position %d of jitted %s" % (pos, sym),
                        symbol="%s.static%d" % (sym, pos)))

        for node in ast.walk(tree):
            if not isinstance(node, ast.Call):
                continue
            if isinstance(node.func, ast.Name) and node.func.id in jitted:
                check_call(node, jitted[node.func.id], node.func.id)
            # immediate form: jax.jit(f, static_argnums=...)(args)
            elif (isinstance(node.func, ast.Call)
                  and _call_name(node.func) in _JIT_NAMES):
                statics = self._static_positions(node.func)
                if statics:
                    check_call(node, statics, "<inline jit>")
        return findings


def _string_constants(tree: ast.Module) -> List[Tuple[int, str]]:
    out = []
    for node in ast.walk(tree):
        if isinstance(node, ast.Constant) and isinstance(node.value, str):
            out.append((node.lineno, node.value))
    return out


def _registry_families(tree: ast.Module) -> List[Tuple[int, str, str]]:
    """(line, family, type) from registry tuples like
    ``("tpujob_x_total", "help...", "counter")`` — the `_FAMILIES` /
    `_WORKER_GAUGES` pattern whose HELP/TYPE lines are format-built."""
    out = []
    for node in ast.walk(tree):
        if not isinstance(node, (ast.Tuple, ast.List)):
            continue
        elts = node.elts
        if len(elts) < 2:
            continue
        first, last = elts[0], elts[-1]
        if not (isinstance(first, ast.Constant)
                and isinstance(first.value, str)
                and isinstance(last, ast.Constant)
                and isinstance(last.value, str)):
            continue
        if (last.value in _METRIC_TYPES
                and first.value not in _METRIC_TYPES
                and "_" in first.value
                and _METRIC_FAMILY.match(first.value)):
            out.append((first.lineno, first.value, last.value))
    return out


@dataclass
class _MetricsInventory:
    # family -> declared type (first wins), with the declaring site
    declared: Dict[str, Tuple[str, str, int]] = field(default_factory=dict)
    # family -> list of (path, line, frozenset(label names))
    samples: Dict[str, List[Tuple[str, int, frozenset]]] = (
        field(default_factory=dict))


class MetricsConventionsPass(_Pass):
    """OPS401-403, source-level: families are harvested from string
    constants — literal ``# TYPE fam type`` declarations, registry
    tuples ``(family, ..., type)``, and sample-shaped literals like
    ``'tpujob_x{a="%s"} %d'``. Package-wide resolution happens in
    :func:`lint_paths` (a family may be declared in one module and
    emitted from another); single-source runs resolve within the file.

    Supersedes the runtime-side ``scripts/metrics_lint.py`` check at the
    source level: an undeclared family is caught before any process
    serves it."""

    rule_ids = ("OPS401", "OPS402", "OPS403")

    def collect(self, path: str, tree: ast.Module,
                inv: _MetricsInventory) -> None:
        for line, fam, mtype in _registry_families(tree):
            inv.declared.setdefault(fam, (mtype, path, line))
        for line, text in _string_constants(tree):
            for m in _TYPE_LINE_RE.finditer(text):
                inv.declared.setdefault(m.group(1), (m.group(2), path, line))
        for line, text in _string_constants(tree):
            if text.startswith("#"):
                continue
            m = _SAMPLE_RE.match(text)
            if not m:
                continue
            fam = m.group(1)
            if not fam.startswith(_METRIC_PREFIX):
                continue
            if "%" in fam:  # dynamic family name: not statically checkable
                continue
            labels: frozenset = frozenset()
            if m.group(2) == "{":
                block = text[text.find("{") + 1:text.rfind("}")]
                if "%" in block and "=" not in block:
                    labels = frozenset(("<dynamic>",))
                else:
                    labels = frozenset(_LABEL_NAME_RE.findall(block))
            inv.samples.setdefault(fam, []).append((path, line, labels))

    @staticmethod
    def _fold(fam: str, declared: Dict[str, Tuple[str, str, int]]
              ) -> Optional[str]:
        """Same suffix rules as k8s.runtime.fold_suffix, duplicated here
        so the linter stays import-free of the package it lints."""
        if fam in declared:
            return fam
        for suffix, kinds in (("_bucket", ("histogram",)),
                              ("_sum", ("histogram", "summary")),
                              ("_count", ("histogram", "summary"))):
            if fam.endswith(suffix):
                base = fam[:-len(suffix)]
                if declared.get(base, ("",))[0] in kinds:
                    return base
        return None

    def finish(self, inv: _MetricsInventory) -> List[Finding]:
        findings: List[Finding] = []
        for fam, (mtype, path, line) in sorted(inv.declared.items()):
            if not fam.startswith(_METRIC_PREFIX):
                findings.append(Finding(
                    "OPS402", path, line,
                    "metric family %r lacks the %s prefix"
                    % (fam, _METRIC_PREFIX), symbol=fam))
        for fam, sites in sorted(inv.samples.items()):
            base = self._fold(fam, inv.declared)
            if base is None:
                path, line, _ = sites[0]
                findings.append(Finding(
                    "OPS401", path, line,
                    "sample family %r is emitted but never declared "
                    "(# TYPE line or registry tuple)" % fam, symbol=fam))
                continue
            label_sets = {labels for (_, _, labels) in sites
                          if "<dynamic>" not in labels}
            if len(label_sets) > 1:
                path, line, _ = sites[0]
                findings.append(Finding(
                    "OPS403", path, line,
                    "family %r emitted with inconsistent label sets: %s"
                    % (fam, " vs ".join(
                        "{%s}" % ",".join(sorted(s)) or "{}"
                        for s in sorted(label_sets,
                                        key=lambda s: sorted(s)))),
                    symbol=fam))
        return findings

    def run(self, path: str, tree: ast.Module,
            source: str) -> List[Finding]:
        inv = _MetricsInventory()
        self.collect(path, tree, inv)
        return self.finish(inv)


_AST_PASSES = (LockDisciplinePass(), ThreadHygienePass(),
               ReconcilePurityPass(), RecompileHazardPass())
_METRICS_PASS = MetricsConventionsPass()


def _filter_suppressed(findings: List[Finding],
                       suppressed: Dict[int, Set[str]]) -> List[Finding]:
    return [f for f in findings
            if f.rule not in suppressed.get(f.line, ())]


def lint_source(source: str, path: str = "<memory>",
                rules: Optional[Iterable[str]] = None,
                metrics: bool = True) -> List[Finding]:
    """Lint one source blob (fixture tests use this directly)."""
    tree = ast.parse(source)
    findings: List[Finding] = []
    for p in _AST_PASSES:
        findings.extend(p.run(path, tree, source))
    if metrics:
        findings.extend(_METRICS_PASS.run(path, tree, source))
    findings = _filter_suppressed(findings, _suppressed_lines(source))
    if rules is not None:
        want = set(rules)
        findings = [f for f in findings if f.rule in want]
    return sorted(findings, key=lambda f: (f.path, f.line, f.rule))


def _iter_py_files(paths: Sequence[str]) -> List[str]:
    out: List[str] = []
    for p in paths:
        if os.path.isfile(p):
            out.append(p)
            continue
        for root, dirs, files in os.walk(p):
            dirs[:] = [d for d in dirs
                       if d not in ("__pycache__", ".git", "build")]
            for f in sorted(files):
                if f.endswith(".py"):
                    out.append(os.path.join(root, f))
    return sorted(dict.fromkeys(out))


def lint_paths(paths: Sequence[str], root: Optional[str] = None,
               rules: Optional[Iterable[str]] = None) -> List[Finding]:
    """Lint files/trees. Metric families resolve PACKAGE-WIDE: a family
    declared in runtime.py and emitted from obs/ is fine."""
    findings: List[Finding] = []
    inv = _MetricsInventory()
    for fpath in _iter_py_files(paths):
        with open(fpath, "r", encoding="utf-8") as fh:
            source = fh.read()
        rel = os.path.relpath(fpath, root) if root else fpath
        try:
            tree = ast.parse(source)
        except SyntaxError as e:
            findings.append(Finding(
                "OPS401", rel, e.lineno or 0,
                "unparseable module: %s" % e, symbol="syntax"))
            continue
        suppressed = _suppressed_lines(source)
        per_file: List[Finding] = []
        for p in _AST_PASSES:
            per_file.extend(p.run(rel, tree, source))
        findings.extend(_filter_suppressed(per_file, suppressed))
        _METRICS_PASS.collect(rel, tree, inv)
    findings.extend(_METRICS_PASS.finish(inv))
    if rules is not None:
        want = set(rules)
        findings = [f for f in findings if f.rule in want]
    return sorted(findings, key=lambda f: (f.path, f.line, f.rule))


# ---------------------------------------------------------------------------
# baseline
# ---------------------------------------------------------------------------

def load_baseline(path: str) -> Dict[str, str]:
    """fingerprint -> human-readable description (for audits)."""
    if not os.path.exists(path):
        return {}
    with open(path, "r", encoding="utf-8") as fh:
        data = json.load(fh)
    return dict(data.get("findings", {}))


def write_baseline(findings: Sequence[Finding], path: str) -> None:
    data = {
        "comment": "accepted pre-existing opslint findings; regenerate "
                   "with scripts/opslint.py --update-baseline",
        "findings": {f.fingerprint(): f.render() for f in findings},
    }
    with open(path, "w", encoding="utf-8") as fh:
        json.dump(data, fh, indent=1, sort_keys=True)
        fh.write("\n")


def apply_baseline(findings: Sequence[Finding],
                   baseline: Dict[str, str]
                   ) -> Tuple[List[Finding], List[Finding]]:
    """(new, accepted) split."""
    new, accepted = [], []
    for f in findings:
        (accepted if f.fingerprint() in baseline else new).append(f)
    return new, accepted
