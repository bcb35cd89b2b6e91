"""Interprocedural dataflow core for the OPS6xx/7xx/8xx pass families.

The PR 8 donation-aliasing corruption — a ``np.load`` array flowing
through ``device_put`` into a DONATING step function two calls away —
was invisible to the per-function syntactic passes in :mod:`opslint`:
no single function contains the bug. This module adds the machinery
those passes lacked:

* a **project model** (:class:`Project`): every module parsed once,
  imports resolved to project-qualified names, a call graph over
  module-level functions and methods;
* **abstract values** (:class:`AbstractValue`): buffer provenance
  (host-owned / zero-copy host view / device / device-aliasing-host /
  donated-dead), device residency, mesh-axis sets for mesh objects,
  and function values carrying a donation signature;
* **function summaries** (:class:`Summary`) computed to a fixpoint and
  instantiated at call sites, so effects propagate across calls —
  a helper that returns ``np.load(...)`` taints its callers, a builder
  that returns a ``donate_argnums`` jit taints every call site of the
  returned function;
* a forward, flow-sensitive walk per function body with **pass hooks**
  (:class:`DataflowPass`): passes observe donation call sites, uses of
  dead values, persist sinks, device→host coercions, and mesh/axis
  facts, and emit :class:`opslint.Finding` objects that ride the same
  suppression-comment + baseline machinery as the OPS1xx–5xx passes.

Design posture, matching opslint: **conservative against false
positives**. Unknown callees, attribute state, and dynamic values get
bottom (no tags) — imprecision silences a finding, never invents one.
Branch merges *intersect* hazard tags (a value copied on one branch —
the ``_owned_host`` "copy unless OWNDATA" pattern — is owned after the
join); loop bodies are walked twice so a donation in iteration N is
seen by the use in iteration N+1. Nothing is imported or executed.
"""

from __future__ import annotations

import ast
import os
from dataclasses import dataclass, field
from typing import (
    Any, Dict, FrozenSet, List, Optional, Sequence, Set, Tuple,
)

from .opslint import Finding

# ---------------------------------------------------------------------------
# abstract values
# ---------------------------------------------------------------------------

# buffer-provenance / residency tags
HOST_VIEW = "host_view"          # zero-copy host buffer another owner backs
                                 # (np.load/memmap/frombuffer/mmap)
HOST_OWNED = "host_owned"        # host buffer owning its memory (np.array)
DEVICE = "device"                # on-device value (device_put / jit result)
DEVICE_ALIAS = "device_alias"    # device value that may ALIAS externally
                                 # owned host memory (device_put of a view)
HOST_OF_DEVICE = "host_of_device"  # host-side zero-copy view of DEVICE bytes
                                 # (np.asarray / device_get of a jax array)
DONATED = "donated"              # donated to a donate_argnums call: dead

_HAZARD_TAGS = frozenset((HOST_VIEW, DEVICE_ALIAS, HOST_OF_DEVICE, DONATED))


@dataclass(frozen=True)
class AbstractValue:
    """One abstract value: provenance tags plus structured facts.

    ``origins`` carries (path, line, what) provenance so a finding two
    calls from its source can say where the buffer was born. ``elts``
    models tuple returns (``build_train_step`` → ``(step_fn, state)``);
    ``donates`` marks callable values that donate those positional args;
    ``axes`` carries the axis-name set of mesh values; ``cond`` holds
    summary-mode conditional effects as ``(kind, param_index)`` pairs,
    instantiated against real arguments at each call site.
    """

    tags: FrozenSet[str] = frozenset()
    origins: Tuple[Tuple[str, int, str], ...] = ()
    elts: Optional[Tuple["AbstractValue", ...]] = None
    donates: FrozenSet[int] = frozenset()
    axes: Optional[FrozenSet[str]] = None
    cond: FrozenSet[Tuple[str, int]] = frozenset()
    # qualified name of the project function this value IS (for calls
    # through variables / partials)
    fn_target: Optional[str] = None

    def with_tags(self, *tags: str) -> "AbstractValue":
        return AbstractValue(self.tags | frozenset(tags), self.origins,
                             self.elts, self.donates, self.axes,
                             self.cond, self.fn_target)

    def with_origin(self, path: str, line: int,
                    what: str) -> "AbstractValue":
        org = self.origins
        if len(org) < 6:  # bounded provenance chain
            org = org + ((path, line, what),)
        return AbstractValue(self.tags, org, self.elts, self.donates,
                             self.axes, self.cond, self.fn_target)

    def origin_note(self) -> str:
        if not self.origins:
            return ""
        path, line, what = self.origins[0]
        return " (buffer born at %s:%d: %s)" % (path, line, what)


BOTTOM = AbstractValue()


def merge_values(a: Optional[AbstractValue],
                 b: Optional[AbstractValue]) -> AbstractValue:
    """Branch join. Hazard tags intersect (must-analysis: flagged only
    when every path reaches the sink tainted — kills the ``copy unless
    OWNDATA`` false positive); benign facts union."""
    if a is None or b is None:
        # the name exists on one branch only: keep it, but drop hazard
        # tags — the other path never created the hazard
        v = a if b is None else b
        assert v is not None
        return AbstractValue(v.tags - _HAZARD_TAGS, v.origins, v.elts,
                             v.donates, v.axes, v.cond, v.fn_target)
    tags = ((a.tags & b.tags)
            | ((a.tags | b.tags) - _HAZARD_TAGS))
    cond = a.cond & b.cond
    elts = None
    if a.elts is not None and b.elts is not None \
            and len(a.elts) == len(b.elts):
        elts = tuple(merge_values(x, y) for x, y in zip(a.elts, b.elts))
    axes = a.axes if a.axes is not None else b.axes
    return AbstractValue(tags, a.origins or b.origins, elts,
                         a.donates | b.donates, axes, cond,
                         a.fn_target or b.fn_target)


# ---------------------------------------------------------------------------
# project model
# ---------------------------------------------------------------------------

@dataclass
class ModuleInfo:
    path: str            # repo-relative path (what findings report)
    abspath: str
    tree: ast.Module
    source: str
    modname: str         # dotted module name guess ("paddle_operator_tpu.runner")


@dataclass
class FunctionInfo:
    qualname: str        # "<module path>::Class.method" | "<module path>::fn"
    module: ModuleInfo
    node: Any            # ast.FunctionDef | ast.AsyncFunctionDef | ast.Lambda
    params: List[str] = field(default_factory=list)

    @property
    def simple_name(self) -> str:
        return self.qualname.rsplit(".", 1)[-1].rsplit("::", 1)[-1]


@dataclass
class Summary:
    """Interprocedural effects of one project function."""

    returns: AbstractValue = BOTTOM
    donates: FrozenSet[int] = frozenset()   # calling fn donates these args
    # (kind, param index): the param reaches a persist sink — either the
    # value itself ("passthrough") or a zero-copy host view of it taken
    # inside the callee ("asarray": hazardous only for device args)
    persists: FrozenSet[Tuple[str, int]] = frozenset()
    resolved: bool = False
    # the lockset half of the summary (filled by LocksetModel when the
    # OPS9xx family runs; None for buffer-only analyses)
    locks: Optional[Any] = None


def _iter_py(paths: Sequence[str]) -> List[str]:
    out: List[str] = []
    for p in paths:
        if os.path.isfile(p):
            out.append(p)
            continue
        for root, dirs, files in os.walk(p):
            dirs[:] = [d for d in dirs
                       if d not in ("__pycache__", ".git", "build",
                                    "node_modules")]
            for f in sorted(files):
                if f.endswith(".py"):
                    out.append(os.path.join(root, f))
    return sorted(dict.fromkeys(out))


def _dotted(node: ast.AST) -> str:
    """Dotted source text of a Name/Attribute chain ('' if dynamic)."""
    parts: List[str] = []
    cur = node
    while isinstance(cur, ast.Attribute):
        parts.append(cur.attr)
        cur = cur.value
    if isinstance(cur, ast.Name):
        parts.append(cur.id)
        return ".".join(reversed(parts))
    if isinstance(cur, ast.Call):
        # chained call like jax.jit(f)(x): caller handles
        return ""
    return ""


class Project:
    """Parsed view of the analyzed tree: modules, functions, imports,
    call graph, and the project-wide mesh-axis universe."""

    def __init__(self, paths: Sequence[str], root: Optional[str] = None,
                 axis_paths: Sequence[str] = ()) -> None:
        self.root = root
        self.modules: List[ModuleInfo] = []
        self.functions: Dict[str, FunctionInfo] = {}
        # module path -> {local name -> qualified function key}
        self.imports: Dict[str, Dict[str, str]] = {}
        # simple function name -> [qualified keys] (fallback resolution)
        self.by_name: Dict[str, List[str]] = {}
        self.summaries: Dict[str, Summary] = {}
        # module path -> abstract env of module-level assignments (the
        # hoisted `step = jax.jit(...)` pattern): functions read these
        # as globals when a name is not bound locally
        self.module_env: Dict[str, Dict[str, AbstractValue]] = {}
        # axis universe: name -> first definition site label
        self.mesh_axes: Dict[str, str] = {}
        self.errors: List[Finding] = []
        for fpath in _iter_py(paths):
            self._load(fpath, collect_only=False)
        # extra paths contribute mesh-axis vocabulary (tests/examples
        # build the fsdp/pp meshes) without being linted themselves
        seen = {m.abspath for m in self.modules}
        for fpath in _iter_py(axis_paths):
            if fpath not in seen:
                self._load(fpath, collect_only=True)
        self._index()

    # -- loading --------------------------------------------------------

    def _load(self, fpath: str, collect_only: bool) -> None:
        try:
            with open(fpath, "r", encoding="utf-8") as fh:
                source = fh.read()
            tree = ast.parse(source)
        except (OSError, SyntaxError) as e:
            if not collect_only:
                line = getattr(e, "lineno", 0) or 0
                rel = os.path.relpath(fpath, self.root) if self.root else fpath
                self.errors.append(Finding(
                    "OPS401", rel, line, "unparseable module: %s" % e,
                    symbol="syntax"))
            return
        rel = os.path.relpath(fpath, self.root) if self.root else fpath
        modname = rel[:-3].replace(os.sep, ".").replace("/", ".")
        info = ModuleInfo(rel, fpath, tree, source, modname)
        self._collect_axes(info)
        if not collect_only:
            self.modules.append(info)

    def _collect_axes(self, mod: ModuleInfo) -> None:
        """Mesh-axis universe: axis names statically visible in mesh
        construction (``make_mesh({'dp': 2, ...})``, ``make_hybrid_mesh``,
        ``Mesh(arr, ('dp', 'tp'))``, ``mesh_axes={...}``) plus the axis
        vocabulary declared by ``axis``/``*_axis`` parameter defaults."""
        def add(name: Any, line: int) -> None:
            if isinstance(name, str) and name:
                self.mesh_axes.setdefault(
                    name, "%s:%d" % (mod.path, line))

        def dict_keys(node: ast.AST, line: int) -> None:
            if isinstance(node, ast.Dict):
                for k in node.keys:
                    if isinstance(k, ast.Constant):
                        add(k.value, line)

        for node in ast.walk(mod.tree):
            if isinstance(node, ast.Call):
                callee = _dotted(node.func).rsplit(".", 1)[-1]
                if callee in ("make_mesh", "make_hybrid_mesh"):
                    for arg in list(node.args) + [
                            kw.value for kw in node.keywords]:
                        dict_keys(arg, node.lineno)
                elif callee == "Mesh" and len(node.args) >= 2:
                    names = node.args[1]
                    if isinstance(names, (ast.Tuple, ast.List)):
                        for e in names.elts:
                            if isinstance(e, ast.Constant):
                                add(e.value, node.lineno)
                for kw in node.keywords:
                    if kw.arg == "mesh_axes":
                        dict_keys(kw.value, node.lineno)
            elif isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                args = node.args
                pos = args.posonlyargs + args.args
                defaults = list(args.defaults)
                for a, d in zip(pos[len(pos) - len(defaults):], defaults):
                    if (a.arg == "axis" or a.arg.endswith("_axis")) \
                            and isinstance(d, ast.Constant):
                        add(d.value, node.lineno)
                for a, d in zip(args.kwonlyargs, args.kw_defaults):
                    if d is not None and (
                            a.arg == "axis" or a.arg.endswith("_axis")) \
                            and isinstance(d, ast.Constant):
                        add(d.value, node.lineno)
            elif isinstance(node, ast.keyword):
                if node.arg == "mesh_axes":
                    dict_keys(node.value, getattr(node.value, "lineno", 0))
            elif isinstance(node, ast.Assign):
                # `mesh_axes = {...}` locals feeding TrainJob/fixtures
                for tgt in node.targets:
                    if isinstance(tgt, ast.Name) and tgt.id == "mesh_axes":
                        dict_keys(node.value, node.lineno)

    # -- indexing -------------------------------------------------------

    def _index(self) -> None:
        for mod in self.modules:
            self._index_module(mod)
        for key in self.functions:
            simple = key.rsplit("::", 1)[-1].rsplit(".", 1)[-1]
            self.by_name.setdefault(simple, []).append(key)

    def _index_module(self, mod: ModuleInfo) -> None:
        imports: Dict[str, str] = {}

        def register(node: Any, prefix: str) -> None:
            name = prefix + node.name if prefix else node.name
            key = "%s::%s" % (mod.path, name)
            self.functions[key] = FunctionInfo(
                key, mod, node, _param_names(node))
            # nested defs analyzed in their own right (their closure
            # environment starts at bottom — conservative)
            for sub in node.body:
                if isinstance(sub, (ast.FunctionDef, ast.AsyncFunctionDef)):
                    register(sub, name + ".")

        for node in mod.tree.body:
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                register(node, "")
            elif isinstance(node, ast.ClassDef):
                for sub in node.body:
                    if isinstance(sub, (ast.FunctionDef,
                                        ast.AsyncFunctionDef)):
                        register(sub, node.name + ".")
            elif isinstance(node, ast.ImportFrom) and node.module:
                for alias in node.names:
                    local = alias.asname or alias.name
                    imports[local] = "%s.%s" % (node.module, alias.name)
            elif isinstance(node, ast.Import):
                for alias in node.names:
                    local = alias.asname or alias.name
                    imports[local] = alias.name
        self.imports[mod.path] = imports

    # -- resolution -----------------------------------------------------

    def resolve_call(self, mod: ModuleInfo,
                     name: str) -> Optional[FunctionInfo]:
        """Map a (possibly dotted) call name in ``mod`` to a project
        function. Module-local names win; imported names resolve when the
        trailing symbol is unique project-wide (ambiguity → None: an
        unresolved call is silent, never wrong)."""
        if not name:
            return None
        local = "%s::%s" % (mod.path, name)
        if local in self.functions:
            return self.functions[local]
        simple = name.rsplit(".", 1)[-1]
        # imported `from x import fn` / `from .x import fn`
        target = self.imports.get(mod.path, {}).get(simple)
        cands = self.by_name.get(simple, [])
        if target is not None and cands:
            tail = target.rsplit(".", 1)[-1]
            matches = [c for c in cands
                       if c.rsplit("::", 1)[-1].rsplit(".", 1)[-1] == tail]
            if len(matches) == 1:
                return self.functions[matches[0]]
        if simple == name:
            # bare name defined once anywhere AS A FUNCTION (methods only
            # resolve via self./imports — a bare `save()` must not bind to
            # some class's .save across the project)
            plain = [c for c in cands
                     if "." not in c.rsplit("::", 1)[-1]]
            if len(plain) == 1:
                return self.functions[plain[0]]
        return None

    def summary_of(self, key: str) -> Summary:
        return self.summaries.get(key, Summary())


def _param_names(fn: Any) -> List[str]:
    args = fn.args
    return [a.arg for a in args.posonlyargs + args.args]


# ---------------------------------------------------------------------------
# pass interface
# ---------------------------------------------------------------------------

class DataflowPass:
    """Hooks invoked during the reporting walk. Passes append
    :class:`Finding` objects to ``out``."""

    rule_ids: Tuple[str, ...] = ()

    def on_donating_call(self, ctx: "FnContext", call: ast.Call,
                         pos: int, value: AbstractValue,
                         label: str, out: List[Finding]) -> None:
        pass

    def on_use(self, ctx: "FnContext", node: ast.AST, name: str,
               value: AbstractValue, out: List[Finding]) -> None:
        pass

    def on_persist(self, ctx: "FnContext", call: ast.Call,
                   value: AbstractValue, label: str,
                   out: List[Finding]) -> None:
        pass

    def on_d2h(self, ctx: "FnContext", node: ast.AST,
               value: AbstractValue, what: str, hot_loop: bool,
               loop_exiting: bool, out: List[Finding]) -> None:
        pass

    def on_call(self, ctx: "FnContext", call: ast.Call, callee: str,
                arg_vals: List[AbstractValue],
                kw_vals: Dict[Optional[str], AbstractValue],
                out: List[Finding]) -> None:
        pass


@dataclass
class FnContext:
    project: Project
    fn: FunctionInfo

    @property
    def path(self) -> str:
        return self.fn.module.path


# ---------------------------------------------------------------------------
# builtin call semantics
# ---------------------------------------------------------------------------

# suffix-matched callee names producing zero-copy host views
_VIEW_SOURCES = {
    "np.load": "np.load", "numpy.load": "np.load",
    "np.memmap": "np.memmap", "numpy.memmap": "np.memmap",
    "np.frombuffer": "np.frombuffer", "numpy.frombuffer": "np.frombuffer",
    "np.fromfile": "np.fromfile", "numpy.fromfile": "np.fromfile",
    "mmap.mmap": "mmap.mmap",
    "open_memmap": "open_memmap",
}

_COPY_CALLS = {"np.array", "numpy.array", "np.copy", "numpy.copy",
               "np.ascontiguousarray", "numpy.ascontiguousarray"}

_ASARRAY_CALLS = {"np.asarray", "numpy.asarray", "np.asanyarray",
                  "numpy.asanyarray"}

_DEVICE_GET = {"jax.device_get", "device_get"}

_DEVICE_PUT = {"jax.device_put", "device_put"}

_JIT_NAMES = {"jax.jit", "jit", "jax.pjit", "pjit"}

_CACHED_JIT = {"compile_cache.cached_jit", "cached_jit"}

# persist sinks: positional index of the persisted payload
_PERSIST_SINKS = {
    "np.save": 1, "numpy.save": 1,
    "np.savez": None,           # all args/kwargs persist
    "numpy.savez": None,
    "np.savez_compressed": None,
    "numpy.savez_compressed": None,
    "pickle.dump": 0,
    "_save_arr": 1,
}

# D2H coercions: builtins / numpy functions forcing device->host
_D2H_BUILTINS = {"float", "int", "bool"}
_D2H_METHODS = {"item", "tolist", "numpy"}

_MESH_BUILDERS = {"make_mesh", "make_hybrid_mesh", "mesh_from_env"}

_JNP_PREFIXES = ("jnp.", "jax.numpy.", "lax.", "jax.lax.", "jax.nn.")


def _donate_positions(call: ast.Call) -> FrozenSet[int]:
    for kw in call.keywords:
        if kw.arg != "donate_argnums":
            continue
        v = kw.value
        if isinstance(v, ast.Constant) and isinstance(v.value, int):
            return frozenset((v.value,))
        if isinstance(v, (ast.Tuple, ast.List)):
            out = set()
            for e in v.elts:
                if isinstance(e, ast.Constant) and isinstance(e.value, int):
                    out.add(e.value)
            return frozenset(out)
    return frozenset()


# ---------------------------------------------------------------------------
# the per-function interpreter
# ---------------------------------------------------------------------------

_PARAM_COND_PASSTHROUGH = "passthrough"   # return carries arg i's tags
_PARAM_COND_DEVICE_PUT = "device_put"     # DEVICE_ALIAS if arg i HOST_VIEW
_PARAM_COND_ASARRAY = "asarray"           # HOST_OF_DEVICE if arg i DEVICE


class _Interp:
    """Forward walk over one function body.

    ``summary_mode``: params are symbolic (tag ``("param", i)`` carried
    in ``cond`` as passthrough markers) and effects are recorded into a
    :class:`Summary` instead of findings. ``report_mode``: params start
    at bottom (callers' facts arrive via summaries at their call sites,
    not here) and the registered passes observe events.
    """

    def __init__(self, project: Project, fn: FunctionInfo,
                 passes: Sequence[DataflowPass],
                 summary_mode: bool) -> None:
        self.project = project
        self.fn = fn
        self.passes = passes
        self.summary_mode = summary_mode
        self.ctx = FnContext(project, fn)
        self.findings: List[Finding] = []
        self.summary = Summary()
        self.env: Dict[str, AbstractValue] = {}
        self._ret: Optional[AbstractValue] = None
        self._loop_depth = 0
        self._hot_loop = False       # current loop dispatches device work
        self._exiting_block = False  # remaining stmts end in return/break
        self.globals = project.module_env.get(fn.module.path, {})
        if summary_mode:
            for i, p in enumerate(fn.params):
                self.env[p] = AbstractValue(
                    cond=frozenset(((_PARAM_COND_PASSTHROUGH, i),)))

    # -- driving --------------------------------------------------------

    def run(self) -> None:
        body = getattr(self.fn.node, "body", [])
        self._block(body)
        if self._ret is not None:
            self.summary.returns = self._ret
        self.summary.resolved = True

    # -- statements -----------------------------------------------------

    def _block(self, stmts: Sequence[ast.stmt]) -> None:
        for i, stmt in enumerate(stmts):
            prev_exiting = self._exiting_block
            if self._loop_depth:
                rest = stmts[i:]
                self._exiting_block = _block_exits_loop(rest)
            self._stmt(stmt)
            self._exiting_block = prev_exiting

    def _stmt(self, node: ast.stmt) -> None:
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                             ast.ClassDef)):
            return  # nested defs analyzed in their own right (module level)
        if isinstance(node, ast.Assign):
            val = self._expr(node.value)
            for tgt in node.targets:
                self._assign(tgt, val)
            return
        if isinstance(node, ast.AnnAssign):
            if node.value is not None:
                self._assign(node.target, self._expr(node.value))
            return
        if isinstance(node, ast.AugAssign):
            self._expr(node.value)
            if isinstance(node.target, ast.Name):
                self._use(node.target, node.target.id)
            return
        if isinstance(node, ast.Return):
            val = self._expr(node.value) if node.value is not None else BOTTOM
            self._ret = val if self._ret is None else merge_values(
                self._ret, val)
            return
        if isinstance(node, ast.Expr):
            self._expr(node.value)
            return
        if isinstance(node, ast.If):
            tval = self._expr(node.test)
            if tval.tags & frozenset((DEVICE, DEVICE_ALIAS)):
                self._report_d2h(node.test, tval, "bool(<device value>)")
            base = dict(self.env)
            self._block(node.body)
            then_env = self.env
            self.env = dict(base)
            self._block(node.orelse)
            else_env = self.env
            self.env = _merge_envs(then_env, else_env)
            return
        if isinstance(node, (ast.For, ast.AsyncFor)):
            self._expr(node.iter)
            self._assign(node.target, BOTTOM)
            self._loop(node.body)
            self._block(node.orelse)
            return
        if isinstance(node, ast.While):
            tval = self._expr(node.test)
            if tval.tags & frozenset((DEVICE, DEVICE_ALIAS)):
                self._report_d2h(node.test, tval, "bool(<device value>)")
            self._loop(node.body)
            self._block(node.orelse)
            return
        if isinstance(node, (ast.With, ast.AsyncWith)):
            for item in node.items:
                v = self._expr(item.context_expr)
                if item.optional_vars is not None:
                    self._assign(item.optional_vars, v)
            self._block(node.body)
            return
        if isinstance(node, ast.Try):
            base = dict(self.env)
            self._block(node.body)
            for handler in node.handlers:
                self.env = dict(base)
                self._block(handler.body)
            self.env = dict(base)
            self._block(node.orelse)
            self._block(node.finalbody)
            return
        if isinstance(node, ast.Delete):
            for tgt in node.targets:
                if isinstance(tgt, ast.Name):
                    self.env.pop(tgt.id, None)
            return
        # fallback: evaluate child expressions for their side effects
        for child in ast.iter_child_nodes(node):
            if isinstance(child, ast.expr):
                self._expr(child)

    def _loop(self, body: Sequence[ast.stmt]) -> None:
        """Walk twice: facts from iteration N (a donation, a device
        value) meet their uses in iteration N+1. ``hot`` = the body
        dispatches device work (a call yielding DEVICE)."""
        self._loop_depth += 1
        prev_hot = self._hot_loop
        probe = _HotLoopProbe(self)
        self._hot_loop = probe.scan(body)
        seen = len(self.findings)
        self._block(body)
        self._block(body)
        # dedup findings duplicated by the double walk
        tail = self.findings[seen:]
        del self.findings[seen:]
        added: Set[Tuple[str, str, int, str]] = set()
        for f in tail:
            k = (f.rule, f.path, f.line, f.symbol)
            if k not in added:
                added.add(k)
                self.findings.append(f)
        self._hot_loop = prev_hot
        self._loop_depth -= 1

    # -- assignment / use ------------------------------------------------

    def _assign(self, tgt: ast.AST, val: AbstractValue) -> None:
        if isinstance(tgt, ast.Name):
            self.env[tgt.id] = val
            return
        if isinstance(tgt, (ast.Tuple, ast.List)):
            elts = val.elts
            # unpacking a structured value without element info: the
            # components of a device tuple are device values too
            spill = AbstractValue(
                val.tags & frozenset((DEVICE, DEVICE_ALIAS, HOST_VIEW,
                                      HOST_OF_DEVICE)), val.origins)
            for i, sub in enumerate(tgt.elts):
                if isinstance(sub, ast.Starred):
                    self._assign(sub.value, spill)
                    continue
                self._assign(sub,
                             elts[i] if elts is not None
                             and i < len(elts) else spill)
            return
        if isinstance(tgt, (ast.Attribute, ast.Subscript)):
            self._expr(tgt.value)
            # attribute/container state is out of scope (conservative)
            return

    def _use(self, node: ast.AST, name: str) -> AbstractValue:
        if name in self.env:
            val = self.env[name]
        else:
            val = self.globals.get(name, BOTTOM)
        if not self.summary_mode and val.tags:
            for p in self.passes:
                p.on_use(self.ctx, node, name, val, self.findings)
        return val

    # -- expressions -----------------------------------------------------

    def _expr(self, node: Optional[ast.expr]) -> AbstractValue:
        if node is None:
            return BOTTOM
        if isinstance(node, ast.Name):
            return self._use(node, node.id)
        if isinstance(node, ast.Constant):
            return BOTTOM
        if isinstance(node, ast.Call):
            return self._call(node)
        if isinstance(node, (ast.Tuple, ast.List)):
            elts = tuple(self._expr(e) for e in node.elts
                         if not isinstance(e, ast.Starred))
            return AbstractValue(elts=elts)
        if isinstance(node, ast.Dict):
            for k in node.keys:
                if k is not None:
                    self._expr(k)
            vals = [self._expr(v) for v in node.values]
            tags: FrozenSet[str] = frozenset()
            for v in vals:
                tags |= v.tags & frozenset((DEVICE, DEVICE_ALIAS, DONATED))
            return AbstractValue(tags)
        if isinstance(node, ast.Subscript):
            base = self._expr(node.value)
            self._expr(node.slice)
            # indexing a device container yields a device-ish value
            keep = base.tags & frozenset((DEVICE, DEVICE_ALIAS, DONATED,
                                          HOST_VIEW, HOST_OF_DEVICE))
            return AbstractValue(keep, base.origins, cond=base.cond)
        if isinstance(node, ast.Attribute):
            base = self._expr(node.value)
            keep = base.tags & frozenset((DEVICE, DEVICE_ALIAS, DONATED))
            return AbstractValue(keep, base.origins)
        if isinstance(node, ast.BinOp):
            l, r = self._expr(node.left), self._expr(node.right)
            tags = (l.tags | r.tags) & frozenset((DEVICE,))
            return AbstractValue(tags)
        if isinstance(node, ast.BoolOp):
            vals = [self._expr(v) for v in node.values]
            out = BOTTOM
            for v in vals:
                out = merge_values(out, v) if out is not BOTTOM else v
            return out
        if isinstance(node, ast.UnaryOp):
            return self._expr(node.operand)
        if isinstance(node, ast.Compare):
            self._expr(node.left)
            for c in node.comparators:
                self._expr(c)
            return BOTTOM
        if isinstance(node, ast.IfExp):
            self._expr(node.test)
            return merge_values(self._expr(node.body),
                                self._expr(node.orelse))
        if isinstance(node, (ast.ListComp, ast.SetComp, ast.GeneratorExp,
                             ast.DictComp)):
            for gen in node.generators:
                self._expr(gen.iter)
            return BOTTOM
        if isinstance(node, ast.Lambda):
            return BOTTOM
        if isinstance(node, ast.Starred):
            return self._expr(node.value)
        if isinstance(node, ast.JoinedStr):
            for v in node.values:
                if isinstance(v, ast.FormattedValue):
                    self._expr(v.value)
            return BOTTOM
        if isinstance(node, ast.FormattedValue):
            return self._expr(node.value)
        if isinstance(node, ast.Await):
            return self._expr(node.value)
        if isinstance(node, ast.NamedExpr):
            v = self._expr(node.value)
            self._assign(node.target, v)
            return v
        for child in ast.iter_child_nodes(node):
            if isinstance(child, ast.expr):
                self._expr(child)
        return BOTTOM

    # -- calls -----------------------------------------------------------

    def _call(self, call: ast.Call) -> AbstractValue:
        callee = _dotted(call.func)
        arg_vals = [self._expr(a) for a in call.args]
        kw_vals = {kw.arg: self._expr(kw.value) for kw in call.keywords}
        path = self.fn.module.path

        if not self.summary_mode:
            for p in self.passes:
                p.on_call(self.ctx, call, callee, arg_vals, kw_vals,
                          self.findings)

        # -- callee is a tracked VALUE (a built step fn, a partial) ------
        fn_val = None
        if isinstance(call.func, ast.Name):
            name = call.func.id
            fn_val = self.env.get(name, self.globals.get(name))
        elif isinstance(call.func, ast.Call):
            # immediate form: jax.jit(f, donate_argnums=...)(args)
            fn_val = self._call(call.func)
        if fn_val is not None and (fn_val.donates or fn_val.fn_target):
            return self._invoke_value(call, fn_val, arg_vals)
        if fn_val is not None and DEVICE in fn_val.tags:
            # calling a (non-donating) jit wrapper: XLA allocates fresh
            # output buffers — the result is owned device memory
            return AbstractValue(frozenset((DEVICE,)))

        if not callee:
            return BOTTOM
        short = callee.rsplit(".", 1)[-1]
        if callee.startswith("self.") and "." not in callee[5:]:
            # method call on the enclosing class only (a global search by
            # simple name would cross class boundaries)
            qual = self.fn.qualname.rsplit("::", 1)[-1]
            if "." in qual:
                cls = qual.split(".", 1)[0]
                key = "%s::%s.%s" % (self.fn.module.path, cls, callee[5:])
                if key in self.project.functions:
                    return self._apply_summary(call, key, arg_vals, callee)
            return BOTTOM

        # -- builtins with known semantics -------------------------------
        suffix2 = ".".join(callee.split(".")[-2:])
        if suffix2 in _VIEW_SOURCES or callee in _VIEW_SOURCES:
            what = _VIEW_SOURCES.get(suffix2) or _VIEW_SOURCES[callee]
            return AbstractValue(frozenset((HOST_VIEW,))).with_origin(
                path, call.lineno, what)
        if suffix2 in _COPY_CALLS or callee in _COPY_CALLS:
            return AbstractValue(frozenset((HOST_OWNED,)))
        if suffix2 in _ASARRAY_CALLS or callee in _ASARRAY_CALLS \
                or suffix2 in _DEVICE_GET or callee in _DEVICE_GET:
            src = arg_vals[0] if arg_vals else BOTTOM
            what = "np.asarray" if short.startswith("as") else "device_get"
            if DEVICE in src.tags or DEVICE_ALIAS in src.tags:
                self._report_d2h(call, src, what)
                return AbstractValue(
                    frozenset((HOST_VIEW, HOST_OF_DEVICE)),
                    src.origins).with_origin(
                        path, call.lineno, "%s of a device buffer" % what)
            if HOST_VIEW in src.tags:
                return src  # view of a view
            out = AbstractValue(frozenset((HOST_OWNED,)))
            # summary-mode conditional: HOST_OF_DEVICE iff arg is DEVICE
            for kind, idx in src.cond:
                if kind == _PARAM_COND_PASSTHROUGH:
                    out = AbstractValue(
                        out.tags, out.origins,
                        cond=out.cond | {(_PARAM_COND_ASARRAY, idx)})
            return out
        if suffix2 in _DEVICE_PUT or callee in _DEVICE_PUT:
            src = arg_vals[0] if arg_vals else BOTTOM
            tags = {DEVICE}
            if HOST_VIEW in src.tags:
                tags.add(DEVICE_ALIAS)
            out = AbstractValue(frozenset(tags), src.origins)
            if DEVICE_ALIAS in tags:
                out = out.with_origin(path, call.lineno,
                                      "device_put of a zero-copy host view")
            for kind, idx in src.cond:
                if kind == _PARAM_COND_PASSTHROUGH:
                    out = AbstractValue(
                        out.tags, out.origins,
                        cond=out.cond | {(_PARAM_COND_DEVICE_PUT, idx)})
            return out
        if callee in _JIT_NAMES:
            donates = _donate_positions(call)
            # the returned wrapper: calling it runs on device
            return AbstractValue(frozenset((DEVICE,)), donates=donates)
        if callee in _CACHED_JIT or suffix2 in _CACHED_JIT:
            donates = _donate_positions(call)
            return AbstractValue(frozenset((DEVICE,)), donates=donates)
        if short == "partial" and call.args:
            inner = call.args[0]
            inner_name = _dotted(inner)
            inner_val = arg_vals[0]
            if inner_val.donates or inner_val.fn_target:
                return inner_val
            target = self.project.resolve_call(self.fn.module, inner_name)
            if target is not None:
                return AbstractValue(fn_target=target.qualname)
            return BOTTOM
        if short in _MESH_BUILDERS or short == "Mesh":
            axes = self._static_axes(call)
            return AbstractValue(axes=axes)
        if callee.startswith(_JNP_PREFIXES):
            return AbstractValue(frozenset((DEVICE,)))

        # -- D2H coercions ----------------------------------------------
        if callee in _D2H_BUILTINS and arg_vals:
            self._report_d2h(call, arg_vals[0], callee)
            return BOTTOM
        if isinstance(call.func, ast.Attribute) \
                and call.func.attr in _D2H_METHODS:
            recv = self._expr(call.func.value)
            self._report_d2h(call, recv, ".%s()" % call.func.attr)
            return BOTTOM

        # -- persist sinks ----------------------------------------------
        sink_pos = None
        is_sink = False
        if suffix2 in _PERSIST_SINKS:
            sink_pos, is_sink = _PERSIST_SINKS[suffix2], True
        elif callee in _PERSIST_SINKS:
            sink_pos, is_sink = _PERSIST_SINKS[callee], True
        if is_sink:
            payloads = (arg_vals if sink_pos is None
                        else arg_vals[sink_pos:sink_pos + 1])
            if sink_pos is None:
                payloads = list(payloads) + list(kw_vals.values())
            for v in payloads:
                self._report_persist(call, v, callee)
            return BOTTOM

        # -- project functions: apply the summary ------------------------
        target = self.project.resolve_call(self.fn.module, callee)
        if target is not None:
            return self._apply_summary(call, target.qualname,
                                       arg_vals, callee)
        return BOTTOM

    def _invoke_value(self, call: ast.Call, fn_val: AbstractValue,
                      arg_vals: List[AbstractValue]) -> AbstractValue:
        """Call through a variable holding a known function value."""
        if fn_val.fn_target:
            return self._apply_summary(call, fn_val.fn_target, arg_vals,
                                       fn_val.fn_target)
        # a jit-built callable: donation signature applies
        for pos in sorted(fn_val.donates):
            if pos < len(arg_vals):
                self._report_donation(call, pos, arg_vals[pos],
                                      _dotted(call.func) or "<jit>")
                self._mark_donated(call.args[pos]
                                   if pos < len(call.args) else None,
                                   call)
        return AbstractValue(frozenset((DEVICE,)))

    def _apply_summary(self, call: ast.Call, key: str,
                       arg_vals: List[AbstractValue],
                       label: str) -> AbstractValue:
        summ = self.project.summary_of(key)
        for pos in sorted(summ.donates):
            if pos < len(arg_vals):
                self._report_donation(call, pos, arg_vals[pos], label)
                self._mark_donated(call.args[pos]
                                   if pos < len(call.args) else None, call)
        for kind, pos in sorted(summ.persists):
            if pos >= len(arg_vals):
                continue
            src = arg_vals[pos]
            if kind == _PARAM_COND_PASSTHROUGH:
                self._report_persist(call, src, label)
            elif kind == _PARAM_COND_ASARRAY:
                if self.summary_mode:
                    # thread the condition through to OUR params
                    for skind, sidx in src.cond:
                        if skind == _PARAM_COND_PASSTHROUGH:
                            self.summary.persists = (
                                self.summary.persists
                                | {(_PARAM_COND_ASARRAY, sidx)})
                elif DEVICE in src.tags or DEVICE_ALIAS in src.tags:
                    # the callee takes a zero-copy host view of our
                    # device arg and persists it
                    self._report_persist(call, AbstractValue(
                        frozenset((HOST_OF_DEVICE, HOST_VIEW)),
                        src.origins or ((self.fn.module.path, call.lineno,
                                         "device value viewed host-side "
                                         "inside %s" % label),)), label)
        ret = summ.returns
        # instantiate conditional effects against the real args
        tags = set(ret.tags)
        origins = ret.origins
        for kind, idx in ret.cond:
            src = arg_vals[idx] if idx < len(arg_vals) else BOTTOM
            fired = False
            if kind == _PARAM_COND_PASSTHROUGH:
                tags |= src.tags
                fired = bool(src.tags)
            elif kind == _PARAM_COND_DEVICE_PUT:
                tags.add(DEVICE)
                if HOST_VIEW in src.tags:
                    tags.add(DEVICE_ALIAS)
                    fired = True
            elif kind == _PARAM_COND_ASARRAY:
                if DEVICE in src.tags or DEVICE_ALIAS in src.tags:
                    tags |= {HOST_VIEW, HOST_OF_DEVICE}
                    fired = True
            if fired and src.origins and not origins:
                origins = src.origins
        cond: FrozenSet[Tuple[str, int]] = frozenset()
        if self.summary_mode:
            # re-express against OUR params for transitive summaries
            new_cond: Set[Tuple[str, int]] = set()
            for kind, idx in ret.cond:
                src = arg_vals[idx] if idx < len(arg_vals) else BOTTOM
                for skind, sidx in src.cond:
                    if skind == _PARAM_COND_PASSTHROUGH:
                        new_cond.add((kind, sidx))
            cond = frozenset(new_cond)
        return AbstractValue(frozenset(tags), origins, ret.elts,
                             ret.donates, ret.axes, cond, ret.fn_target)

    def _mark_donated(self, arg_node: Optional[ast.AST],
                      call: ast.Call) -> None:
        if isinstance(arg_node, ast.Name):
            cur = self.env.get(arg_node.id, BOTTOM)
            self.env[arg_node.id] = cur.with_tags(DONATED).with_origin(
                self.fn.module.path, call.lineno, "donated here")

    def _static_axes(self, call: ast.Call) -> Optional[FrozenSet[str]]:
        axes: Set[str] = set()
        nodes: List[ast.AST] = list(call.args) + [
            kw.value for kw in call.keywords]
        for n in nodes:
            if isinstance(n, ast.Dict):
                for k in n.keys:
                    if isinstance(k, ast.Constant) and isinstance(
                            k.value, str):
                        axes.add(k.value)
            elif isinstance(n, (ast.Tuple, ast.List)):
                for e in n.elts:
                    if isinstance(e, ast.Constant) and isinstance(
                            e.value, str):
                        axes.add(e.value)
        return frozenset(axes) if axes else None

    # -- event reporting -------------------------------------------------

    def _report_donation(self, call: ast.Call, pos: int,
                         value: AbstractValue, label: str) -> None:
        if self.summary_mode:
            # record: calling US donates OUR param (when the arg IS a
            # bare param passthrough)
            for kind, idx in value.cond:
                if kind == _PARAM_COND_PASSTHROUGH:
                    self.summary.donates = self.summary.donates | {idx}
            return
        for p in self.passes:
            p.on_donating_call(self.ctx, call, pos, value, label,
                               self.findings)

    def _report_persist(self, call: ast.Call, value: AbstractValue,
                        label: str) -> None:
        if self.summary_mode:
            for kind, idx in value.cond:
                if kind in (_PARAM_COND_PASSTHROUGH, _PARAM_COND_ASARRAY):
                    self.summary.persists = (
                        self.summary.persists | {(kind, idx)})
            return
        for p in self.passes:
            p.on_persist(self.ctx, call, value, label, self.findings)

    def _report_d2h(self, node: ast.AST, value: AbstractValue,
                    what: str) -> None:
        if self.summary_mode:
            return
        for p in self.passes:
            p.on_d2h(self.ctx, node, value, what,
                     self._hot_loop and self._loop_depth > 0,
                     self._exiting_block, self.findings)


class _HotLoopProbe:
    """Does this loop body dispatch device work? True when a call in the
    body resolves to a device-producing function (a jit value, a jnp/lax
    call, or a project function whose summary returns DEVICE)."""

    def __init__(self, interp: _Interp) -> None:
        self.interp = interp

    def scan(self, body: Sequence[ast.stmt]) -> bool:
        env = self.interp.env
        project = self.interp.project
        mod = self.interp.fn.module
        for stmt in body:
            for node in ast.walk(stmt):
                if not isinstance(node, ast.Call):
                    continue
                callee = _dotted(node.func)
                if callee.startswith(_JNP_PREFIXES):
                    return True
                if isinstance(node.func, ast.Name):
                    v = env.get(node.func.id)
                    if v is not None and (
                            v.donates or DEVICE in v.tags):
                        return True
                    target = project.resolve_call(mod, node.func.id)
                    if target is not None:
                        s = project.summary_of(target.qualname)
                        if DEVICE in s.returns.tags or s.donates:
                            return True
        return False


def _block_exits_loop(rest: Sequence[ast.stmt]) -> bool:
    """True when the remaining statements of the current block
    unconditionally leave the loop (return / break / raise) — a D2H
    there stalls nothing the loop will ever do again."""
    for stmt in rest:
        if isinstance(stmt, (ast.Return, ast.Break, ast.Raise)):
            return True
        if isinstance(stmt, ast.If):
            # an if whose BOTH arms exit also exits
            if stmt.orelse and _block_exits_loop(stmt.body) \
                    and _block_exits_loop(stmt.orelse):
                return True
    return False


def _merge_envs(a: Dict[str, AbstractValue],
                b: Dict[str, AbstractValue]) -> Dict[str, AbstractValue]:
    out: Dict[str, AbstractValue] = {}
    for name in set(a) | set(b):
        out[name] = merge_values(a.get(name), b.get(name))
    return out


# ---------------------------------------------------------------------------
# the analyzer driver
# ---------------------------------------------------------------------------

class Analyzer:
    """Two-phase interprocedural analysis: summaries to a fixpoint
    (bounded rounds — the lattice is tiny and call chains shallow), then
    a reporting walk with the registered passes.

    ``report_paths`` (incremental mode, ``analyze_all --changed``)
    restricts the REPORTING walk to functions of those modules while
    the parse, summaries, and whole-program models still cover the full
    tree — findings for a changed file are identical to a whole-tree
    run's findings for that file, just cheaper to produce."""

    ROUNDS = 3

    def __init__(self, project: Project,
                 passes: Sequence[DataflowPass],
                 report_paths: Optional[Set[str]] = None) -> None:
        self.project = project
        self.passes = list(passes)
        self.report_paths = report_paths

    def _module_envs(self) -> None:
        """Abstract-evaluate module-level code (the hoisted
        ``step = jax.jit(...)`` pattern) so functions see those names."""
        for mod in self.project.modules:
            pseudo = FunctionInfo("%s::<module>" % mod.path, mod,
                                  mod.tree, [])
            interp = _Interp(self.project, pseudo, (), summary_mode=True)
            try:
                interp.run()
            except RecursionError:  # pragma: no cover - degenerate tree
                continue
            self.project.module_env[mod.path] = interp.env

    def _summarize(self) -> None:
        keys = sorted(self.project.functions)
        for _ in range(self.ROUNDS):
            changed = False
            self._module_envs()
            for key in keys:
                fn = self.project.functions[key]
                interp = _Interp(self.project, fn, (), summary_mode=True)
                try:
                    interp.run()
                except RecursionError:  # pragma: no cover - degenerate tree
                    continue
                old = self.project.summaries.get(key)
                new = interp.summary
                if old is None or old.donates != new.donates \
                        or old.persists != new.persists \
                        or old.returns != new.returns:
                    changed = True
                self.project.summaries[key] = new
            if not changed:
                break

    def _in_report(self, path: str) -> bool:
        return self.report_paths is None or path in self.report_paths

    def run(self) -> List[Finding]:
        self._summarize()
        findings: List[Finding] = [f for f in self.project.errors
                                   if self._in_report(f.path)]
        for key in sorted(self.project.functions):
            fn = self.project.functions[key]
            if not self._in_report(fn.module.path):
                continue
            interp = _Interp(self.project, fn, self.passes,
                             summary_mode=False)
            try:
                interp.run()
            except RecursionError:  # pragma: no cover - degenerate tree
                continue
            findings.extend(interp.findings)
        # passes may also want a whole-module syntactic sweep (mesh/axis
        # checks need no dataflow env)
        for p in self.passes:
            sweep = getattr(p, "sweep_module", None)
            if sweep is None:
                continue
            for mod in self.project.modules:
                if not self._in_report(mod.path):
                    continue
                findings.extend(sweep(self.project, mod))
        uniq: Dict[Tuple[str, str, int, str, str], Finding] = {}
        for f in findings:
            uniq.setdefault((f.rule, f.path, f.line, f.symbol, f.message), f)
        return sorted(uniq.values(),
                      key=lambda f: (f.path, f.line, f.rule, f.symbol))


def analyze_paths(paths: Sequence[str], passes: Sequence[DataflowPass],
                  root: Optional[str] = None,
                  axis_paths: Sequence[str] = ()) -> List[Finding]:
    """Parse ``paths`` and run ``passes`` over the project. Findings are
    UNSUPPRESSED — callers (the engine) apply suppression comments and
    the baseline so all analysis families share one mechanism."""
    project = Project(paths, root=root, axis_paths=axis_paths)
    return Analyzer(project, passes).run()


def analyze_source(source: str, passes: Sequence[DataflowPass],
                   path: str = "fixture.py") -> List[Finding]:
    """Single-blob convenience for fixture tests. ``path`` must be a
    bare filename (it becomes the module's reported path)."""
    import tempfile

    with tempfile.TemporaryDirectory() as td:
        fpath = os.path.join(td, os.path.basename(path) or "fixture.py")
        with open(fpath, "w", encoding="utf-8") as fh:
            fh.write(source)
        project = Project([fpath], root=td)
        return Analyzer(project, passes).run()


# ---------------------------------------------------------------------------
# lockset lattice (the OPS9xx concurrency family, analysis/ops9xx.py)
# ---------------------------------------------------------------------------
#
# The abstract value here is a LOCKSET: the set of locks the current
# thread is known to hold at a program point. Locks are identified by
# their CREATION SITE — the ``self._lock = threading.Lock()`` line —
# because that is exactly the identity the runtime race detector
# (racedetect.py) keys its lock-order graph on, so a static OPS902
# cycle and a dynamic inversion report carry the same fingerprints and
# the two tools cross-check. Per function the walk is lexical
# (``with self._lock:`` scoping plus acquire()/release() pairs); across
# functions three interprocedural closures carry the lattice:
#
# * ``may_acquire``  — locks a call may take, any path (drives the
#   global acquisition-order graph OPS902 runs Tarjan over);
# * ``may_block``    — blocking operations a call may reach (OPS904
#   flags the call site that holds a lock across it);
# * ``entry_must``   — locks GUARANTEED held on entry to a private
#   helper, the intersection over all visible call sites (so a helper
#   only ever called under the lock needs no ``with`` of its own, and
#   a ``*_locked`` helper's claim is verified at every call site).
#
# Posture, as everywhere in this engine: unresolved callees, dynamic
# receivers, and callbacks contribute nothing — imprecision silences a
# finding, never invents one.

_LOCK_FACTORIES_STATIC = frozenset((
    "Lock", "RLock", "Condition", "Semaphore", "BoundedSemaphore",
    "InstrumentedLock", "InstrumentedRLock",
))
_THREAD_FACTORIES = frozenset(("Thread",))
_QUEUE_FACTORIES = frozenset(("Queue", "SimpleQueue", "LifoQueue",
                              "PriorityQueue"))

#: dotted call names that block the calling thread (OPS904 catalog);
#: receiver-dependent forms (Thread.join, Queue.get/put) are resolved
#: structurally in the walker, not by name
_BLOCKING_CALLS_STATIC = {
    "time.sleep": "time.sleep",
    "socket.create_connection": "socket.create_connection",
    "urllib.request.urlopen": "urlopen",
    "urlopen": "urlopen",
    "requests.get": "requests.get",
    "requests.post": "requests.post",
    "subprocess.run": "subprocess.run",
    "subprocess.check_call": "subprocess.check_call",
    "subprocess.check_output": "subprocess.check_output",
}

_EXEMPT_LOCK_FUNCS = frozenset(("__init__", "__del__", "__enter__",
                                "__exit__", "__new__"))


@dataclass(frozen=True)
class LockId:
    """One lock, identified the way racedetect identifies it: by the
    source line that creates it."""

    owner: str               # "<module path>::<Class>" | "<module path>"
    attr: str                # attribute / global name holding the lock
    site: Tuple[str, int]    # (module path, creation line) — the
    #                          fingerprint shared with racedetect

    def label(self) -> str:
        return "%s:%d" % self.site

    def name(self) -> str:
        short = self.owner.rsplit("::", 1)[-1]
        short = short.rsplit("/", 1)[-1]
        return "%s.%s" % (short, self.attr)


@dataclass
class ClassLocks:
    """Lock topology of one class: which attrs hold locks (with
    aliasing — ``Condition(self._lock)`` guards the same state), which
    hold threads/queues (OPS904 receivers), and which hold instances of
    other project classes (cross-object call resolution)."""

    key: str                                  # "<module path>::<Class>"
    locks: Dict[str, LockId] = field(default_factory=dict)
    alias: Dict[str, str] = field(default_factory=dict)  # attr -> canonical
    thread_attrs: Set[str] = field(default_factory=set)
    queue_attrs: Set[str] = field(default_factory=set)
    attr_types: Dict[str, str] = field(default_factory=dict)
    assign_lines: Dict[str, int] = field(default_factory=dict)

    def lock_for(self, attr: str) -> Optional[LockId]:
        canon = self.alias.get(attr, attr)
        return self.locks.get(canon)


@dataclass
class LockFacts:
    """Per-function lockset facts from one lexical walk."""

    key: str
    cls_key: Optional[str]
    simple: str
    acquires: Set[LockId] = field(default_factory=set)
    # (callee key, locks held at the site innermost-last, line)
    calls: List[Tuple[str, Tuple[LockId, ...], int]] = (
        field(default_factory=list))
    # (what, line, held) for unresolvable-but-known-blocking operations
    blocking: List[Tuple[str, int, Tuple[LockId, ...]]] = (
        field(default_factory=list))
    # (self-attr, line, held, is_write, with-block index or None)
    accesses: List[Tuple[str, int, Tuple[LockId, ...], bool,
                         Optional[int]]] = field(default_factory=list)
    # (index, lock, start line, end line) of each `with <lock>:` region
    lock_blocks: List[Tuple[int, LockId, int, int]] = (
        field(default_factory=list))
    # local = <expr containing self.attr read> inside block i:
    # (local name, attr, block index, line)
    reads_into: List[Tuple[str, str, int, int]] = (
        field(default_factory=list))
    # plain-name loads: name -> sorted lines (OPS903 staleness witness)
    name_loads: Dict[str, List[int]] = field(default_factory=dict)
    # (held, acquired) pairs observed lexically
    order_edges: Set[Tuple[LockId, LockId]] = field(default_factory=set)


class _LockHarvest:
    """Module sweep: class lock topology + module-level locks/threads/
    queues, built once per project."""

    def __init__(self, project: Project) -> None:
        self.project = project
        self.classes: Dict[str, ClassLocks] = {}   # "<path>::<Class>"
        self.module_locks: Dict[str, Dict[str, LockId]] = {}
        self.module_alias: Dict[str, Dict[str, str]] = {}
        self.module_threads: Dict[str, Set[str]] = {}
        self.module_queues: Dict[str, Set[str]] = {}
        # class simple name -> [class keys] (unique-name type resolution)
        self.class_by_name: Dict[str, List[str]] = {}
        for mod in project.modules:
            self._module(mod)
        for key in self.classes:
            self.class_by_name.setdefault(
                key.rsplit("::", 1)[-1], []).append(key)
        # attr types resolve after the class index exists
        for mod in project.modules:
            self._attr_types(mod)

    def _module(self, mod: ModuleInfo) -> None:
        locks: Dict[str, LockId] = {}
        alias: Dict[str, str] = {}
        threads: Set[str] = set()
        queues: Set[str] = set()
        for node in mod.tree.body:
            if isinstance(node, ast.ClassDef):
                self._class(mod, node)
                continue
            if not isinstance(node, ast.Assign) \
                    or not isinstance(node.value, ast.Call):
                continue
            short = _dotted(node.value.func).rsplit(".", 1)[-1]
            for tgt in node.targets:
                if not isinstance(tgt, ast.Name):
                    continue
                if short in _LOCK_FACTORIES_STATIC:
                    wrapped = None
                    for arg in node.value.args:
                        if isinstance(arg, ast.Name) and arg.id in locks:
                            wrapped = arg.id
                    if short == "Condition" and wrapped is not None:
                        alias[tgt.id] = alias.get(wrapped, wrapped)
                    else:
                        locks[tgt.id] = LockId(mod.path, tgt.id,
                                               (mod.path, node.lineno))
                elif short in _THREAD_FACTORIES:
                    threads.add(tgt.id)
                elif short in _QUEUE_FACTORIES:
                    queues.add(tgt.id)
        self.module_locks[mod.path] = locks
        self.module_alias[mod.path] = alias
        self.module_threads[mod.path] = threads
        self.module_queues[mod.path] = queues

    def _class(self, mod: ModuleInfo, cls: ast.ClassDef) -> None:
        key = "%s::%s" % (mod.path, cls.name)
        info = ClassLocks(key)
        for fn in cls.body:
            if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            for node in ast.walk(fn):
                if not isinstance(node, ast.Assign):
                    continue
                for tgt in node.targets:
                    attr = _is_self_attr_static(tgt)
                    if attr is None:
                        continue
                    info.assign_lines.setdefault(attr, node.lineno)
                    if not isinstance(node.value, ast.Call):
                        continue
                    short = _dotted(node.value.func).rsplit(".", 1)[-1]
                    if short in _LOCK_FACTORIES_STATIC:
                        wrapped = None
                        for arg in node.value.args:
                            w = _is_self_attr_static(arg)
                            if w is not None:
                                wrapped = w
                        if short == "Condition" and wrapped is not None:
                            # either name guards the same state
                            info.alias[attr] = info.alias.get(wrapped,
                                                              wrapped)
                        elif attr not in info.locks:
                            info.locks[attr] = LockId(
                                key, attr, (mod.path, node.lineno))
                    elif short in _THREAD_FACTORIES:
                        info.thread_attrs.add(attr)
                    elif short in _QUEUE_FACTORIES:
                        info.queue_attrs.add(attr)
        self.classes[key] = info

    def _attr_types(self, mod: ModuleInfo) -> None:
        for node in mod.tree.body:
            if not isinstance(node, ast.ClassDef):
                continue
            key = "%s::%s" % (mod.path, node.name)
            info = self.classes.get(key)
            if info is None:
                continue
            for fn in node.body:
                if not isinstance(fn, (ast.FunctionDef,
                                       ast.AsyncFunctionDef)):
                    continue
                for sub in ast.walk(fn):
                    if not isinstance(sub, ast.Assign) \
                            or not isinstance(sub.value, ast.Call):
                        continue
                    short = _dotted(sub.value.func).rsplit(".", 1)[-1]
                    cands = self.class_by_name.get(short, [])
                    if len(cands) != 1:
                        continue
                    for tgt in sub.targets:
                        attr = _is_self_attr_static(tgt)
                        if attr is not None:
                            info.attr_types.setdefault(attr, cands[0])

    def declare_lock(self, cls_key: str, attr: str) -> LockId:
        """A lock the guard spec declares but no factory call assigns
        (a lock object passed in to the constructor): its
        identity anchors at the first ``self.<attr> = ...`` line."""
        info = self.classes.get(cls_key)
        if info is None:
            path = cls_key.split("::", 1)[0]
            return LockId(cls_key, attr, (path, 0))
        lid = info.lock_for(attr)
        if lid is not None:
            return lid
        path = cls_key.split("::", 1)[0]
        line = info.assign_lines.get(attr, 0)
        lid = LockId(cls_key, attr, (path, line))
        info.locks[attr] = lid
        return lid


def _is_self_attr_static(node: ast.AST) -> Optional[str]:
    if (isinstance(node, ast.Attribute)
            and isinstance(node.value, ast.Name)
            and node.value.id == "self"):
        return node.attr
    return None


class _LockWalker:
    """One function's lexical lockset walk, producing a
    :class:`LockFacts`. The held stack is a list (innermost last);
    ``with`` items push for their body, ``.acquire()`` pushes for the
    rest of the enclosing scope until a matching ``.release()``."""

    def __init__(self, harvest: _LockHarvest, fn: FunctionInfo) -> None:
        self.h = harvest
        self.fn = fn
        self.mod = fn.module
        qual = fn.qualname.rsplit("::", 1)[-1]
        first = qual.split(".", 1)[0]
        cls_key = "%s::%s" % (self.mod.path, first)
        self.cls = harvest.classes.get(cls_key)
        self.facts = LockFacts(
            fn.qualname, self.cls.key if self.cls else None,
            fn.simple_name)
        self.held: List[LockId] = []
        self.local_locks: Dict[str, LockId] = {}   # name aliases
        self.local_threads: Set[str] = set()
        self.local_queues: Set[str] = set()
        self._block_seq = 0

    # -- lock expression resolution -------------------------------------

    def _lock_expr(self, expr: ast.AST) -> Optional[LockId]:
        attr = _is_self_attr_static(expr)
        if attr is not None and self.cls is not None:
            return self.cls.lock_for(attr)
        if isinstance(expr, ast.Name):
            if expr.id in self.local_locks:
                return self.local_locks[expr.id]
            mlocks = self.h.module_locks.get(self.mod.path, {})
            malias = self.h.module_alias.get(self.mod.path, {})
            return mlocks.get(malias.get(expr.id, expr.id))
        return None

    def _push(self, lock: LockId) -> None:
        for h in self.held:
            if h is lock or h.site == lock.site:
                continue
            self.facts.order_edges.add((h, lock))
        self.held.append(lock)
        self.facts.acquires.add(lock)

    # -- driving ---------------------------------------------------------

    def run(self) -> LockFacts:
        for stmt in getattr(self.fn.node, "body", []):
            self._stmt(stmt)
        return self.facts

    def _stmt(self, node: ast.stmt) -> None:
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                             ast.ClassDef)):
            return  # nested defs walk in their own right, lockless
        if isinstance(node, (ast.With, ast.AsyncWith)):
            pushed: List[LockId] = []
            for item in node.items:
                self._expr(item.context_expr)
                lock = self._lock_expr(item.context_expr)
                if lock is not None:
                    self._push(lock)
                    pushed.append(lock)
            end = getattr(node, "end_lineno", None) or node.lineno
            for lock in pushed:
                self._block_seq += 1
                self.facts.lock_blocks.append(
                    (self._block_seq, lock, node.lineno, end))
            for stmt in node.body:
                self._stmt(stmt)
            # remove OUR pushed entries specifically, not the top of
            # the stack: a release() inside the block may already have
            # dropped one (blind pops would underflow), and an
            # acquire() inside must survive the with-exit — the with's
            # lock must not leak in its place
            for lock in pushed:
                for i in range(len(self.held) - 1, -1, -1):
                    if self.held[i] is lock:
                        del self.held[i]
                        break
            return
        if isinstance(node, ast.Assign):
            self._expr(node.value)
            self._track_assign(node)
            for tgt in node.targets:
                self._record_target(tgt)
            return
        if isinstance(node, ast.AnnAssign):
            if node.value is not None:
                self._expr(node.value)
            self._record_target(node.target)
            return
        if isinstance(node, ast.AugAssign):
            self._expr(node.value)
            self._record_target(node.target)
            attr = _is_self_attr_static(node.target)
            if attr is not None:
                self._access(attr, node.target.lineno, False)
            return
        if isinstance(node, ast.Expr):
            call = node.value
            if isinstance(call, ast.Call) \
                    and isinstance(call.func, ast.Attribute) \
                    and call.func.attr in ("acquire", "release"):
                lock = self._lock_expr(call.func.value)
                if lock is not None:
                    if call.func.attr == "acquire":
                        self._push(lock)
                    elif self.held and any(h is lock or h.site == lock.site
                                           for h in self.held):
                        for i in range(len(self.held) - 1, -1, -1):
                            if self.held[i].site == lock.site:
                                del self.held[i]
                                break
                    return
            self._expr(node.value)
            return
        # structured statements: walk expression children, then bodies
        for fname in ("test", "iter", "exc", "cause", "value"):
            sub = getattr(node, fname, None)
            if isinstance(sub, ast.expr):
                self._expr(sub)
        if isinstance(node, (ast.For, ast.AsyncFor)):
            self._record_target(node.target)
        for fname in ("body", "orelse", "finalbody"):
            sub = getattr(node, fname, None)
            if isinstance(sub, list):
                for stmt in sub:
                    if isinstance(stmt, ast.stmt):
                        self._stmt(stmt)
        for handler in getattr(node, "handlers", []) or []:
            for stmt in handler.body:
                self._stmt(stmt)

    def _track_assign(self, node: ast.Assign) -> None:
        """Local bookkeeping: lock aliases (``mu = self._lock``),
        locally created threads/queues, and OPS903 read-into-local
        records (a guarded attr read banked into a name inside a lock
        block)."""
        names = [t.id for t in node.targets if isinstance(t, ast.Name)]
        if not names:
            return
        lock = self._lock_expr(node.value)
        if lock is not None:
            for n in names:
                self.local_locks[n] = lock
            return
        if isinstance(node.value, ast.Call):
            short = _dotted(node.value.func).rsplit(".", 1)[-1]
            if short in _THREAD_FACTORIES:
                self.local_threads.update(names)
            elif short in _QUEUE_FACTORIES:
                self.local_queues.update(names)
        blk = self._innermost_block()
        if blk is None:
            return
        for sub in ast.walk(node.value):
            attr = _is_self_attr_static(sub)
            if attr is not None:
                for n in names:
                    self.facts.reads_into.append(
                        (n, attr, blk, node.lineno))

    def _innermost_block(self) -> Optional[int]:
        if not self.held:
            return None
        # the lock block entered last whose lock is the innermost held
        for idx, lock, _s, _e in reversed(self.facts.lock_blocks):
            if lock is self.held[-1]:
                return idx
        return None

    def _record_target(self, tgt: ast.AST) -> None:
        attr = _is_self_attr_static(tgt)
        if attr is not None:
            self._access(attr, tgt.lineno, True)
            return
        if isinstance(tgt, ast.Subscript):
            base = _is_self_attr_static(tgt.value)
            if base is not None:
                # self.d[k] = v writes through the container attr
                self._access(base, tgt.lineno, True)
            else:
                self._expr(tgt.value)
            self._expr(tgt.slice)
            return
        if isinstance(tgt, (ast.Tuple, ast.List)):
            for sub in tgt.elts:
                self._record_target(sub)
            return
        if isinstance(tgt, ast.Starred):
            self._record_target(tgt.value)
            return
        if isinstance(tgt, ast.Attribute):
            self._expr(tgt.value)

    def _access(self, attr: str, line: int, is_write: bool) -> None:
        self.facts.accesses.append(
            (attr, line, tuple(self.held), is_write,
             self._innermost_block()))

    # -- expressions -----------------------------------------------------

    def _expr(self, node: Optional[ast.AST]) -> None:
        """Pruned expression traversal: closures and nested defs are
        skipped ENTIRELY (they run later, on another thread as often as
        not, so the lexical lockset does not cover them — they are
        walked as functions in their own right, lockless)."""
        if node is None:
            return
        stack: List[ast.AST] = [node]
        while stack:
            sub = stack.pop()
            if isinstance(sub, (ast.FunctionDef, ast.AsyncFunctionDef,
                                ast.Lambda, ast.ClassDef)):
                continue
            if isinstance(sub, ast.Call):
                self._call(sub)
            elif isinstance(sub, ast.Name) \
                    and isinstance(sub.ctx, ast.Load):
                self.facts.name_loads.setdefault(
                    sub.id, []).append(sub.lineno)
            else:
                attr = _is_self_attr_static(sub)
                if attr is not None:
                    self._access(attr, sub.lineno,
                                 isinstance(getattr(sub, "ctx", None),
                                            (ast.Store, ast.Del)))
                    # the receiver Name ('self') needs no visit
                    continue
            stack.extend(ast.iter_child_nodes(sub))

    def _call(self, call: ast.Call) -> None:
        held = tuple(self.held)
        callee = _dotted(call.func)
        target = self._resolve(call, callee)
        if target is not None:
            self.facts.calls.append((target, held, call.lineno))
            return
        what = self._blocking_what(call, callee)
        if what is not None:
            self.facts.blocking.append((what, call.lineno, held))

    def _resolve(self, call: ast.Call, callee: str) -> Optional[str]:
        """Callee -> project function key. self-methods, typed-attribute
        methods (``self.capacity.snapshot`` when ``self.capacity =
        FleetCapacity(...)``), imported/module functions, then a
        project-unique trailing-name fallback; anything ambiguous stays
        unresolved (and therefore silent)."""
        parts = callee.split(".") if callee else []
        if len(parts) >= 2 and parts[0] == "self" \
                and self.cls is not None:
            if len(parts) == 2:
                key = "%s.%s" % (self.cls.key, parts[1])
                if key in self.h.project.functions:
                    return key
            elif len(parts) == 3:
                tkey = self.cls.attr_types.get(parts[1])
                if tkey is not None:
                    mkey = "%s.%s" % (tkey, parts[2])
                    if mkey in self.h.project.functions:
                        return mkey
        if callee and not callee.startswith("self."):
            target = self.h.project.resolve_call(self.mod, callee)
            if target is not None:
                return target.qualname
        # unique trailing-name fallback (methods included): a method
        # name defined exactly once project-wide binds through any
        # receiver — ambiguity stays silent
        simple = None
        if isinstance(call.func, ast.Attribute):
            simple = call.func.attr
        elif callee:
            simple = callee.rsplit(".", 1)[-1]
        if simple:
            cands = self.h.project.by_name.get(simple, [])
            if len(cands) == 1:
                return cands[0]
        return None

    def _blocking_what(self, call: ast.Call,
                       callee: str) -> Optional[str]:
        suffix2 = ".".join(callee.split(".")[-2:]) if callee else ""
        if callee in _BLOCKING_CALLS_STATIC:
            return _BLOCKING_CALLS_STATIC[callee]
        if suffix2 in _BLOCKING_CALLS_STATIC:
            return _BLOCKING_CALLS_STATIC[suffix2]
        if not isinstance(call.func, ast.Attribute):
            return None
        meth = call.func.attr
        recv = call.func.value
        recv_attr = _is_self_attr_static(recv)
        if meth == "join":
            if recv_attr is not None and self.cls is not None \
                    and (recv_attr in self.cls.thread_attrs
                         or "thread" in recv_attr.lower()):
                return "Thread.join"
            if isinstance(recv, ast.Name) \
                    and (recv.id in self.local_threads
                         or recv.id in self.h.module_threads.get(
                             self.mod.path, set())):
                return "Thread.join"
        elif meth in ("get", "put"):
            if recv_attr is not None and self.cls is not None \
                    and recv_attr in self.cls.queue_attrs:
                return "Queue.%s" % meth
            if isinstance(recv, ast.Name) \
                    and (recv.id in self.local_queues
                         or recv.id in self.h.module_queues.get(
                             self.mod.path, set())):
                return "Queue.%s" % meth
        return None


class LocksetModel:
    """The whole-project lockset analysis: harvest, per-function facts,
    and the three interprocedural closures. ``declared`` injects the
    guard spec — ``{module path: {class: [(lock_attr, fields)]}}`` —
    promoting declared fields to lock-owned even when no guarded write
    lets the analyzer infer it."""

    ROUNDS = 24

    def __init__(self, project: Project,
                 declared: Optional[Dict[str, Dict[str, List[
                     Tuple[str, Tuple[str, ...]]]]]] = None) -> None:
        self.project = project
        self.harvest = _LockHarvest(project)
        self.facts: Dict[str, LockFacts] = {}
        for key in sorted(project.functions):
            fn = project.functions[key]
            try:
                self.facts[key] = _LockWalker(self.harvest, fn).run()
            except RecursionError:  # pragma: no cover - degenerate tree
                continue
        self.declared = declared or {}
        # class key -> field attr -> owning LockId (spec wins over
        # inference; inference requires an unambiguous guarded write)
        self.owners: Dict[str, Dict[str, LockId]] = {}
        #: specs whose class/lock/field the tree does not have
        self.stale_specs: List[Tuple[str, str, str]] = []
        self._owners()
        self.call_sites: Dict[str, List[Tuple[str, Tuple[LockId, ...],
                                              int]]] = {}
        for key, f in self.facts.items():
            for callee, held, line in f.calls:
                self.call_sites.setdefault(callee, []).append(
                    (key, held, line))
        self.may_acquire: Dict[str, FrozenSet[LockId]] = {}
        self.may_block: Dict[str, Dict[str, Tuple[str, int]]] = {}
        self.entry_must: Dict[str, FrozenSet[LockId]] = {}
        self.uncalled_private: Set[str] = set()
        self._closures()
        # summaries carry the lockset lattice alongside the buffer
        # lattice (one engine, two abstract domains)
        for key, summ in project.summaries.items():
            summ.locks = self.facts.get(key)

    # -- ownership -------------------------------------------------------

    def _owners(self) -> None:
        inferred: Dict[str, Dict[str, Optional[LockId]]] = {}
        for key, f in self.facts.items():
            if f.cls_key is None:
                continue
            cls = self.harvest.classes.get(f.cls_key)
            if cls is None:
                continue
            if f.simple in _EXEMPT_LOCK_FUNCS:
                continue
            per = inferred.setdefault(f.cls_key, {})
            for attr, _line, held, is_write, _blk in f.accesses:
                if not is_write or not held or cls.lock_for(attr):
                    continue
                # written under two different locks: ambiguous, drop
                prev = per.get(attr, held[-1])
                per[attr] = held[-1] if prev is not None \
                    and prev.site == held[-1].site else None
        for cls_key, per in inferred.items():
            out = self.owners.setdefault(cls_key, {})
            for attr, lock in per.items():
                if lock is not None:
                    out[attr] = lock
        # declared specs override / extend inference
        for path, by_cls in sorted(self.declared.items()):
            in_tree = any(m.path == path for m in self.project.modules)
            for cls_name, entries in sorted(by_cls.items()):
                cls_key = "%s::%s" % (path, cls_name)
                info = self.harvest.classes.get(cls_key)
                if info is None:
                    if in_tree:
                        self.stale_specs.append(
                            (path, cls_name, "class missing"))
                    continue
                for lock_attr, fields in entries:
                    if in_tree and lock_attr not in info.assign_lines \
                            and info.lock_for(lock_attr) is None:
                        self.stale_specs.append(
                            (path, cls_name,
                             "lock %s never assigned" % lock_attr))
                        continue
                    lid = self.harvest.declare_lock(cls_key, lock_attr)
                    out = self.owners.setdefault(cls_key, {})
                    for fld in fields:
                        if in_tree and fld not in info.assign_lines \
                                and not self._field_seen(cls_key, fld):
                            self.stale_specs.append(
                                (path, cls_name,
                                 "field %s never touched" % fld))
                            continue
                        out[fld] = lid

    def _field_seen(self, cls_key: str, attr: str) -> bool:
        for key, f in self.facts.items():
            if f.cls_key != cls_key:
                continue
            for a, _line, _held, _w, _blk in f.accesses:
                if a == attr:
                    return True
        return False

    # -- closures --------------------------------------------------------

    def _closures(self) -> None:
        keys = sorted(self.facts)
        for key in keys:
            self.may_acquire[key] = frozenset(self.facts[key].acquires)
            blocks: Dict[str, Tuple[str, int]] = {}
            for what, line, _held in self.facts[key].blocking:
                blocks.setdefault(
                    what, (self.facts[key].key.split("::", 1)[0], line))
            self.may_block[key] = blocks
        for _ in range(self.ROUNDS):
            changed = False
            for key in keys:
                acq = set(self.may_acquire[key])
                blk = dict(self.may_block[key])
                for callee, _held, _line in self.facts[key].calls:
                    acq |= self.may_acquire.get(callee, frozenset())
                    for what, site in self.may_block.get(callee,
                                                         {}).items():
                        blk.setdefault(what, site)
                if len(acq) != len(self.may_acquire[key]):
                    self.may_acquire[key] = frozenset(acq)
                    changed = True
                if len(blk) != len(self.may_block[key]):
                    self.may_block[key] = blk
                    changed = True
            if not changed:
                break
        self._required_fixpoint(keys)
        self._entry_must(keys)

    def _required_fixpoint(self, keys: List[str]) -> None:
        """The transitive lock requirement a ``*_locked`` name claims:
        its own uncovered owned-field accesses, plus whatever any
        ``*_locked`` callee requires that the call site does not cover
        lexically — a thin wrapper around a locked helper carries the
        helper's obligation out to ITS callers."""
        self.required: Dict[str, FrozenSet[LockId]] = {
            key: self._own_required(key) for key in keys}
        locked_keys = [k for k in keys
                       if self.facts[k].simple.endswith("_locked")]
        for _ in range(self.ROUNDS):
            changed = False
            for key in locked_keys:
                cur = set(self.required[key])
                before = len(cur)
                for callee, held, _line in self.facts[key].calls:
                    cf = self.facts.get(callee)
                    if cf is None or not cf.simple.endswith("_locked"):
                        continue
                    for lock in self.required.get(callee, frozenset()):
                        if not any(h.site == lock.site for h in held):
                            cur.add(lock)
                if len(cur) != before:
                    self.required[key] = frozenset(cur)
                    changed = True
            if not changed:
                break

    def is_nested(self, key: str) -> bool:
        """A def inside another def: lexically unreachable from outside
        the project, so (like privates) its entry lockset is inferable
        from visible call sites — a closure invoked inline under a lock
        keeps the lock, one handed to a thread/callback has no visible
        call site and stays out of every proof."""
        path, qual = key.split("::", 1)
        if "." not in qual:
            return False
        head, rest = qual.split(".", 1)
        if ("%s::%s" % (path, head)) in self.harvest.classes:
            return "." in rest
        return True

    def _entry_must(self, keys: List[str]) -> None:
        """Locks guaranteed held at entry: `_locked` helpers ASSUME the
        locks their owned-field accesses require (call sites verify the
        claim, ops9xx); other private helpers (and nested defs) take
        the intersection over every visible call site; public names
        start empty."""
        TOP = None  # lattice top: intersection identity
        state: Dict[str, Optional[FrozenSet[LockId]]] = {}
        assumed: Dict[str, FrozenSet[LockId]] = {}
        for key in keys:
            f = self.facts[key]
            if f.simple.endswith("_locked"):
                req = self.required.get(key, frozenset())
                assumed[key] = req
                state[key] = req
            elif (f.simple.startswith("_")
                  and not f.simple.startswith("__")) \
                    or self.is_nested(key):
                if self.call_sites.get(key):
                    state[key] = TOP
                else:
                    state[key] = frozenset()
                    self.uncalled_private.add(key)
            else:
                state[key] = frozenset()
        for _ in range(self.ROUNDS):
            changed = False
            for key in keys:
                if key in assumed or state[key] == frozenset():
                    continue  # assumed, or already at bottom
                sites = self.call_sites.get(key, [])
                if not sites:
                    continue
                meet: Optional[FrozenSet[LockId]] = TOP
                for caller, held, _line in sites:
                    eff = state.get(caller, frozenset())
                    if eff is TOP:
                        continue  # caller unresolved: no constraint yet
                    site_set = frozenset(held) | eff
                    meet = site_set if meet is TOP else (meet & site_set)
                if meet is not TOP and meet != state[key]:
                    state[key] = meet
                    changed = True
            if not changed:
                break
        for key in keys:
            v = state.get(key)
            if v is TOP:
                # a private cluster no public path ever reaches: treat
                # as uncalled (no runtime path exists, so no finding)
                self.uncalled_private.add(key)
                v = frozenset()
            self.entry_must[key] = v if v is not None else frozenset()

    def required_locks(self, key: str) -> FrozenSet[LockId]:
        """What this function's entry must provide: the transitive
        ``*_locked`` claim when computed, else its own uncovered
        owned-field accesses."""
        got = getattr(self, "required", {}).get(key)
        if got is not None:
            return got
        return self._own_required(key)

    def _own_required(self, key: str) -> FrozenSet[LockId]:
        """Owned-field accesses in ``key`` with no lexical cover: the
        locks its entry must provide (what a ``*_locked`` name claims).
        For a ``*_locked`` method of a single-lock class that touches
        instance state, the name alone IS the claim — the class's one
        lock is required even when no guarded write taught the
        inference which lock owns which field."""
        f = self.facts.get(key)
        if f is None or f.cls_key is None:
            return frozenset()
        owners = self.owners.get(f.cls_key, {})
        out: Set[LockId] = set()
        for attr, _line, held, _w, _blk in f.accesses:
            lock = owners.get(attr)
            if lock is None:
                continue
            if not any(h.site == lock.site for h in held):
                out.add(lock)
        if not out and f.simple.endswith("_locked"):
            cls = self.harvest.classes.get(f.cls_key)
            if cls is not None and len(cls.locks) == 1:
                only = next(iter(cls.locks.values()))
                touches_state = any(
                    cls.lock_for(attr) is None
                    for attr, _l, _h, _w, _b in f.accesses)
                if touches_state:
                    out.add(only)
        return frozenset(out)

    def effective_entry(self, key: str) -> FrozenSet[LockId]:
        return self.entry_must.get(key, frozenset())

    # -- the global acquisition-order graph ------------------------------

    def order_graph(self) -> Tuple[Dict[Tuple[str, int],
                                        Set[Tuple[str, int]]],
                                   Dict[Tuple[Tuple[str, int],
                                              Tuple[str, int]], str]]:
        """Site graph + one example per edge, the same shape racedetect
        builds at runtime — edges from lexical nesting plus held-across-
        call composition with the may_acquire closure."""
        graph: Dict[Tuple[str, int], Set[Tuple[str, int]]] = {}
        example: Dict[Tuple[Tuple[str, int], Tuple[str, int]], str] = {}

        def add(src: LockId, dst: LockId, note: str) -> None:
            if src.site == dst.site:
                return
            succ = graph.setdefault(src.site, set())
            if dst.site not in succ:
                succ.add(dst.site)
                example[(src.site, dst.site)] = note
        for key in sorted(self.facts):
            f = self.facts[key]
            path = key.split("::", 1)[0]
            for src, dst in sorted(
                    f.order_edges,
                    key=lambda e: (e[0].site, e[1].site)):
                add(src, dst, "%s holds %s then takes %s"
                    % (f.simple, src.label(), dst.label()))
            for callee, held, line in f.calls:
                if not held:
                    continue
                for dst in sorted(self.may_acquire.get(callee,
                                                       frozenset()),
                                  key=lambda l: l.site):
                    for src in held:
                        add(src, dst,
                            "%s:%d holds %s and calls %s which may "
                            "acquire %s"
                            % (path, line, src.label(),
                               callee.rsplit("::", 1)[-1], dst.label()))
        return graph, example


def lock_cycles(graph: Dict[Tuple[str, int], Set[Tuple[str, int]]]
                ) -> List[List[Tuple[str, int]]]:
    """Cycles over a creation-site graph. LITERALLY the runtime
    detector's algorithm — one shared Tarjan (racedetect.tarjan_cycles)
    serves both checkers, so the static and dynamic reports can never
    drift on what counts as a cycle."""
    from .racedetect import tarjan_cycles

    return tarjan_cycles(graph)
