"""zoolint pass ``jit-host-sync``: jit-boundary host-sync escape analysis.

The hand-curated ``hot-path-sync`` table only protects functions someone
remembered to list — PRs 7, 8 and 11 each had to extend it by hand. This
pass *discovers* the traced surface automatically, so the next decode or
embedding PR is policed the day it lands:

* **traced roots** — every function decorated with or wrapped by a JAX
  tracing transform (``jax.jit``/``pjit``/``vmap``/``pmap``/``grad``/
  ``value_and_grad``/``remat``/``custom_vjp``/``custom_jvp``/
  ``shard_map``/``checkify.checkify``) or passed as a body to a structured
  control-flow primitive (``lax.scan``/``while_loop``/``fori_loop``/
  ``cond``/``switch``/``map``/``associative_scan``) or registered via
  ``.defvjp``/``.defjvp`` — including closures defined inside methods
  (``self._step_fn = jax.jit(_step)``);
* **the traced closure** — their transitive intra-package callees, resolved
  through an import-aware call graph: bare names through enclosing scopes
  and module/import tables, ``self.method`` through the class, and
  ``obj.method`` through a package-unique-method-name heuristic (skipped
  for ambiguous or generic names);
* **dispatch boundaries** — host functions that invoke a jit-wrapped
  callable (a ``self.X`` attribute assigned from ``jax.jit(...)`` or from
  a factory method returning one, a local jitted name, or a
  ``jax.device_put`` feed) — the loops that drive the device.

Inside the **traced closure** the pass bans host syncs (``float()``,
``.item()``, ``.tolist()``, ``np.asarray``, ``jax.device_get``,
``.block_until_ready()``), ``one_hot`` densification, host clock/RNG reads
(``time.*``, ``datetime.now``, stdlib/NumPy ``random``) — values that
constant-fold at trace time and silently freeze — and per-element Python
loops (``while``, iteration driven by array shapes, loops over
non-structure iterables), which unroll at trace time or re-serialize
vectorized work. Constant-trip *structure* loops (over ``self``
attributes, pytree containers, ``range(<constant>)``) are exempt.

Inside **dispatch boundaries** the pass bans host syncs in loop bodies
only — a sync per iteration re-serializes the async dispatch pipeline;
one drain after the loop is the supported pattern.

Host-side staging rules that no trace analysis can infer (``_gather``'s
zero-alloc ``np.take(out=)`` contract, ``masked_eval_batches``' cached
mask) remain table-driven in ``hot-path-sync``; this pass counts those
table rows as seeded roots so its coverage strictly dominates the legacy
hand-listed tables.
"""
from __future__ import annotations

import ast
import os
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Set, Tuple

from ..core import (Finding, LintPass, Project, REPO_ROOT, get_project,
                    register_pass)

PKG_NAME = "analytics_zoo_tpu"

#: fully-resolved callables that trace their function argument(s)
TRACE_WRAPPERS = {
    "jax.jit", "jax.pjit", "jax.vmap", "jax.pmap", "jax.grad",
    "jax.value_and_grad", "jax.checkpoint", "jax.remat",
    "jax.custom_vjp", "jax.custom_jvp",
    "jax.lax.scan", "jax.lax.while_loop", "jax.lax.fori_loop",
    "jax.lax.cond", "jax.lax.switch", "jax.lax.map",
    "jax.lax.associative_scan",
    "jax.experimental.pjit.pjit",
    "jax.shard_map", "jax.experimental.shard_map.shard_map",
    "jax.experimental.checkify.checkify",
}

#: attribute registrations that trace their arguments
TRACE_METHODS = {"defvjp", "defjvp"}

#: method names never resolved via the unique-name heuristic (generic or
#: collection-protocol names that would wire unrelated code together)
_COMMON_METHODS = {
    "get", "set", "put", "pop", "add", "append", "extend", "update",
    "items", "keys", "values", "copy", "clear", "close", "open", "read",
    "write", "join", "split", "strip", "encode", "decode", "reshape",
    "astype", "sum", "mean", "max", "min", "item", "tolist", "result",
    "submit", "apply", "run", "start", "stop", "init", "reset", "next",
    "send", "save", "load", "name", "shape", "size", "fit", "predict",
    "evaluate", "transform", "register", "observe", "inc", "dec",
}

_SYNC_NAMES = {"float"}
_HOST_CLOCKS = {
    "time.time", "time.monotonic", "time.perf_counter", "time.time_ns",
    "time.monotonic_ns", "time.perf_counter_ns", "time.process_time",
}


@dataclass
class FuncInfo:
    node: ast.AST
    path: str
    modname: str
    name: str
    class_name: Optional[str] = None
    parent: Optional["FuncInfo"] = None
    nested: Dict[str, "FuncInfo"] = field(default_factory=dict)

    @property
    def qualname(self) -> str:
        parts = [self.name]
        p = self.parent
        while p is not None:
            parts.append(p.name)
            p = p.parent
        if self.class_name:
            parts.append(self.class_name)
        return ".".join(reversed(parts))


@dataclass
class ModuleInfo:
    path: str
    modname: str
    imports: Dict[str, str] = field(default_factory=dict)
    funcs: Dict[str, FuncInfo] = field(default_factory=dict)
    classes: Dict[str, Dict[str, FuncInfo]] = field(default_factory=dict)
    all_funcs: List[FuncInfo] = field(default_factory=list)
    #: (call node, enclosing function or None) for every Call in the module
    calls: List[Tuple[ast.Call, Optional[FuncInfo]]] = field(
        default_factory=list)


class PackageIndex:
    """Import-aware symbol/call index over the package's modules."""

    def __init__(self, project: Project) -> None:
        self.project = project
        self.modules: Dict[str, ModuleInfo] = {}
        for path in project.package_files():
            rel = os.path.relpath(path, project.root)
            modname = rel[:-3].replace(os.sep, ".")
            if modname.endswith(".__init__"):
                modname = modname[: -len(".__init__")]
            if modname.startswith(f"{PKG_NAME}.lint"):
                continue  # the analyzer itself has no device code
            self.modules[modname] = self._index_module(path, modname)
        # unique-method-name resolution table (ambiguous names dropped)
        counts: Dict[str, List[FuncInfo]] = {}
        for mod in self.modules.values():
            for methods in mod.classes.values():
                for name, fi in methods.items():
                    counts.setdefault(name, []).append(fi)
        self.unique_methods = {
            name: fis[0] for name, fis in counts.items()
            if len(fis) == 1 and name not in _COMMON_METHODS}

    # -- module indexing ------------------------------------------------------

    def _index_module(self, path: str, modname: str) -> ModuleInfo:
        tree = self.project.ast_for(path)
        mod = ModuleInfo(path, modname)
        for node in ast.walk(tree):
            if isinstance(node, ast.Import):
                for a in node.names:
                    mod.imports[a.asname or a.name.split(".")[0]] = (
                        a.name if a.asname else a.name.split(".")[0])
            elif isinstance(node, ast.ImportFrom):
                base = node.module or ""
                if node.level:
                    parts = modname.split(".")
                    # drop one for the module itself + (level-1) parents
                    parts = parts[: len(parts) - node.level]
                    base = ".".join(parts + ([node.module]
                                             if node.module else []))
                for a in node.names:
                    if a.name == "*":
                        continue
                    mod.imports[a.asname or a.name] = f"{base}.{a.name}"

        def collect(body, cls: Optional[str], parent: Optional[FuncInfo]):
            for node in body:
                if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                    fi = FuncInfo(node, path, modname, node.name, cls, parent)
                    mod.all_funcs.append(fi)
                    if parent is not None:
                        parent.nested[node.name] = fi
                    elif cls is not None:
                        mod.classes.setdefault(cls, {})[node.name] = fi
                    else:
                        mod.funcs[node.name] = fi
                    self._collect_calls(node, fi, mod)
                    collect(node.body, cls, fi)
                elif isinstance(node, ast.ClassDef):
                    collect(node.body, node.name, None)
                else:
                    collect(getattr(node, "body", []) or [], cls, parent)
                    collect(getattr(node, "orelse", []) or [], cls, parent)
                    collect(getattr(node, "finalbody", []) or [], cls,
                            parent)
                    for h in getattr(node, "handlers", []) or []:
                        collect(h.body, cls, parent)

        collect(tree.body, None, None)
        # module-level calls (outside any function)
        in_fn: Set[int] = set()
        for fi in mod.all_funcs:
            for sub in ast.walk(fi.node):
                in_fn.add(id(sub))
        for node in ast.walk(tree):
            if isinstance(node, ast.Call) and id(node) not in in_fn:
                mod.calls.append((node, None))
        return mod

    def _collect_calls(self, fn_node, fi: FuncInfo, mod: ModuleInfo) -> None:
        """Attribute each Call to its INNERMOST enclosing function."""
        direct: List[ast.AST] = list(ast.iter_child_nodes(fn_node))
        stack = direct
        while stack:
            node = stack.pop()
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue  # nested fn's calls attributed when it is indexed
            if isinstance(node, ast.Call):
                mod.calls.append((node, fi))
            stack.extend(ast.iter_child_nodes(node))

    # -- name resolution ------------------------------------------------------

    def dotted(self, expr, imports: Dict[str, str]) -> Optional[str]:
        parts: List[str] = []
        while isinstance(expr, ast.Attribute):
            parts.append(expr.attr)
            expr = expr.value
        if not isinstance(expr, ast.Name):
            return None
        root = imports.get(expr.id, expr.id)
        return ".".join([root] + list(reversed(parts)))

    def is_wrapper_call(self, call: ast.Call, imports: Dict[str, str]
                        ) -> bool:
        d = self.dotted(call.func, imports)
        if d in TRACE_WRAPPERS:
            return True
        if (isinstance(call.func, ast.Attribute)
                and call.func.attr in TRACE_METHODS):
            return True
        return False

    def _unwrap_partial(self, expr, imports) -> Optional[str]:
        """Dotted path of a decorator, through ``partial(jax.jit, ...)``."""
        if isinstance(expr, ast.Call):
            d = self.dotted(expr.func, imports)
            if d in ("functools.partial", "partial"):
                return (self.dotted(expr.args[0], imports)
                        if expr.args else None)
            return d
        return self.dotted(expr, imports)

    def resolve(self, expr, mod: ModuleInfo, fi: Optional[FuncInfo]
                ) -> Optional[FuncInfo]:
        """Resolve a callee expression to a package FuncInfo, or None."""
        if isinstance(expr, ast.Name):
            scope = fi
            while scope is not None:
                if expr.id in scope.nested:
                    return scope.nested[expr.id]
                if scope.parent is not None and expr.id == scope.name:
                    pass
                # sibling closures live on the ENCLOSING function
                if (scope.parent is not None
                        and expr.id in scope.parent.nested):
                    return scope.parent.nested[expr.id]
                scope = scope.parent
            if expr.id in mod.funcs:
                return mod.funcs[expr.id]
            target = mod.imports.get(expr.id)
            if target and target.startswith(PKG_NAME + "."):
                owner, _, attr = target.rpartition(".")
                owned = self.modules.get(owner)
                if owned is not None:
                    return owned.funcs.get(attr)
            return None
        if isinstance(expr, ast.Attribute):
            base, attr = expr.value, expr.attr
            if isinstance(base, ast.Name):
                if base.id in ("self", "cls") and fi is not None:
                    cn = fi.class_name
                    if cn and attr in mod.classes.get(cn, {}):
                        return mod.classes[cn][attr]
                    return self.unique_methods.get(attr)
                target = mod.imports.get(base.id)
                if target:
                    if target.startswith(PKG_NAME):
                        owned = self.modules.get(target)
                        if owned is not None:
                            return owned.funcs.get(attr)
                    return None  # call into an external module
            return self.unique_methods.get(attr)
        return None


# -- discovery ----------------------------------------------------------------

@dataclass
class Discovery:
    traced: Dict[str, FuncInfo]          # qualpath -> info
    dispatch: Dict[str, FuncInfo]
    index: PackageIndex

    def traced_names(self) -> Set[str]:
        return {fi.name for fi in self.traced.values()}

    def dispatch_names(self) -> Set[str]:
        return {fi.name for fi in self.dispatch.values()}

    def discovered_names(self) -> Set[str]:
        """Automatically discovered function names plus the host-staging
        rows seeded from the hot-path table — the full policed surface."""
        from . import hot_path
        return (self.traced_names() | self.dispatch_names()
                | hot_path.policed_functions())


def _key(fi: FuncInfo) -> str:
    return f"{fi.modname}:{fi.qualname}"


def discover(project: Optional[Project] = None) -> Discovery:
    project = project or get_project()
    index = PackageIndex(project)

    roots: List[FuncInfo] = []
    # decorator roots
    for mod in index.modules.values():
        for fi in mod.all_funcs:
            for dec in getattr(fi.node, "decorator_list", []):
                d = index._unwrap_partial(dec, mod.imports)
                if d in TRACE_WRAPPERS:
                    roots.append(fi)
        # wrapper-call roots: every function-valued argument of a tracing
        # transform, resolved from the call's enclosing scope
        for call, enc in mod.calls:
            if not index.is_wrapper_call(call, mod.imports):
                continue
            args = list(call.args)
            d = index.dotted(call.func, mod.imports)
            if d in ("functools.partial", "partial") and args:
                args = args[1:]
            for arg in args:
                if isinstance(arg, ast.Call):
                    # shard_map(partial(_body, spec), ...) and friends
                    d2 = index.dotted(arg.func, mod.imports)
                    if d2 in ("functools.partial", "partial"):
                        for sub in arg.args:
                            target = index.resolve(sub, mod, enc)
                            if target is not None:
                                roots.append(target)
                    continue
                target = index.resolve(arg, mod, enc)
                if target is not None:
                    roots.append(target)

    # transitive closure over the intra-package call graph
    traced: Dict[str, FuncInfo] = {}
    stack = list(roots)
    while stack:
        fi = stack.pop()
        k = _key(fi)
        if k in traced:
            continue
        traced[k] = fi
        mod = index.modules[fi.modname]
        for call, enc in mod.calls:
            if enc is None:
                continue
            # calls made by fi itself or by closures nested inside it
            owner = enc
            while owner is not None and owner is not fi:
                owner = owner.parent
            if owner is None:
                continue
            target = index.resolve(call.func, mod, enc)
            if target is not None and _key(target) not in traced:
                stack.append(target)

    # dispatch boundaries: jit-valued attributes / locals / factories
    jit_like = {w for w in TRACE_WRAPPERS if not w.startswith("jax.lax.")}

    def _is_jit_call(expr, imports) -> bool:
        return (isinstance(expr, ast.Call)
                and index.dotted(expr.func, imports) in jit_like)

    factories: Set[str] = set()       # "mod:Class.method" returning a jit
    for mod in index.modules.values():
        for fi in mod.all_funcs:
            for sub in ast.walk(fi.node):
                if (isinstance(sub, ast.Return)
                        and _is_jit_call(sub.value, mod.imports)):
                    factories.add(_key(fi))

    jit_attrs: Dict[Tuple[str, str], Set[str]] = {}   # (mod, class) -> attrs
    for mod in index.modules.values():
        for fi in mod.all_funcs:
            if fi.class_name is None:
                continue
            for sub in ast.walk(fi.node):
                if not (isinstance(sub, ast.Assign)
                        and len(sub.targets) == 1):
                    continue
                t = sub.targets[0]
                if not (isinstance(t, ast.Attribute)
                        and isinstance(t.value, ast.Name)
                        and t.value.id == "self"):
                    continue
                if _is_jit_call(sub.value, mod.imports):
                    jit_attrs.setdefault(
                        (mod.modname, fi.class_name), set()).add(t.attr)
                elif isinstance(sub.value, ast.Call):
                    f = index.resolve(sub.value.func, mod, fi)
                    if f is not None and _key(f) in factories:
                        jit_attrs.setdefault(
                            (mod.modname, fi.class_name), set()).add(t.attr)
    all_jit_attr_names: Dict[str, int] = {}
    for attrs in jit_attrs.values():
        for a in attrs:
            all_jit_attr_names[a] = all_jit_attr_names.get(a, 0) + 1

    dispatch: Dict[str, FuncInfo] = {}
    for mod in index.modules.values():
        local_jit: Dict[str, Set[str]] = {}
        for fi in mod.all_funcs:
            for sub in ast.walk(fi.node):
                if (isinstance(sub, ast.Assign) and len(sub.targets) == 1
                        and isinstance(sub.targets[0], ast.Name)
                        and _is_jit_call(sub.value, mod.imports)):
                    local_jit.setdefault(_key(fi), set()).add(
                        sub.targets[0].id)
        for call, enc in mod.calls:
            if enc is None or _key(enc) in traced:
                continue
            f = call.func
            hit = False
            if isinstance(f, ast.Name):
                scope = enc
                while scope is not None and not hit:
                    hit = f.id in local_jit.get(_key(scope), set())
                    scope = scope.parent
            elif isinstance(f, ast.Attribute):
                if (isinstance(f.value, ast.Name) and f.value.id == "self"
                        and enc.class_name is not None):
                    hit = f.attr in jit_attrs.get(
                        (mod.modname, enc.class_name), set())
                if not hit and all_jit_attr_names.get(f.attr, 0) == 1:
                    hit = True  # unique jit attr accessed off another object
            if not hit:
                d = index.dotted(call.func, mod.imports)
                hit = d == "jax.device_put"
            if hit:
                # attribute to the nearest NAMED function (skip closures'
                # parents only when the closure itself is traced)
                dispatch.setdefault(_key(enc), enc)
    return Discovery(traced, dispatch, index)


# -- policing -----------------------------------------------------------------

def _sync_call(index: PackageIndex, call: ast.Call,
               imports: Dict[str, str]) -> str:
    f = call.func
    if isinstance(f, ast.Name) and f.id in _SYNC_NAMES:
        return f"{f.id}()"
    if isinstance(f, ast.Name) and f.id == "one_hot":
        return "one_hot()"
    if isinstance(f, ast.Attribute):
        if f.attr == "one_hot":
            return "one_hot()"
        if f.attr == "block_until_ready":
            return ".block_until_ready()"
        if f.attr in ("item", "tolist") and not call.args:
            return f".{f.attr}()"
        d = index.dotted(f, imports)
        if d == "numpy.asarray":
            return "np.asarray()"
        if d == "jax.device_get":
            return "jax.device_get()"
    return ""


def _host_effect(index: PackageIndex, call: ast.Call,
                 imports: Dict[str, str]) -> str:
    d = index.dotted(call.func, imports)
    if d is None:
        return ""
    if d in _HOST_CLOCKS:
        return f"host clock read {d}()"
    if d.startswith("datetime.") and d.split(".")[-1] in (
            "now", "utcnow", "today", "fromtimestamp"):
        return f"host clock read {d}()"
    if d.startswith("random.") or d.startswith("numpy.random."):
        return f"host RNG {d}()"
    return ""


def _structure_iter(it) -> bool:
    """Constant-trip structure iteration: pytree containers, ``self``
    attributes, ``range`` over non-shape values — trace-time unrolling
    over static structure, not per-element data work."""
    if isinstance(it, ast.Call) and isinstance(it.func, ast.Attribute):
        # dict-pytree iteration: state.items() / params.keys() / .values()
        if (it.func.attr in ("items", "keys", "values") and not it.args
                and _structure_iter(it.func.value)):
            return True
        return False
    if isinstance(it, ast.Call) and isinstance(it.func, ast.Name):
        if it.func.id in ("enumerate", "zip", "reversed", "list", "tuple",
                          "sorted"):
            return all(_structure_iter(a) for a in it.args)
        if it.func.id == "len":
            return True
        if it.func.id == "range":
            for a in it.args:
                for sub in ast.walk(a):
                    if isinstance(sub, ast.Attribute) and sub.attr == "shape":
                        return False
            return True
        return False
    if isinstance(it, (ast.Name, ast.Attribute, ast.Subscript, ast.Tuple,
                       ast.List, ast.Constant)):
        return True
    return False


def police_traced(index: PackageIndex, fi: FuncInfo) -> List[Finding]:
    mod = index.modules[fi.modname]
    out: List[Finding] = []
    where = f"traced code ({fi.qualname}, {os.path.basename(fi.path)})"
    for sub in ast.walk(fi.node):
        if isinstance(sub, ast.Call):
            what = _sync_call(index, sub, mod.imports)
            if what:
                out.append(Finding(
                    fi.path, sub.lineno, JitBoundaryPass.id,
                    f"{what} inside {where} — host syncs break tracing or "
                    f"stall the dispatch pipeline",
                    "keep the computation on device; drain results after "
                    "the jit boundary"))
                continue
            eff = _host_effect(index, sub, mod.imports)
            if eff:
                out.append(Finding(
                    fi.path, sub.lineno, JitBoundaryPass.id,
                    f"{eff} inside {where} — the value constant-folds at "
                    f"trace time and silently freezes",
                    "pass clocks/seeds in as arguments (jax.random for "
                    "in-trace RNG)"))
        elif isinstance(sub, (ast.While,)):
            out.append(Finding(
                fi.path, sub.lineno, JitBoundaryPass.id,
                f"while loop inside {where} — Python control flow "
                f"re-traces or unrolls",
                "use lax.while_loop / lax.scan"))
        elif isinstance(sub, (ast.For, ast.AsyncFor)):
            if not _structure_iter(sub.iter):
                out.append(Finding(
                    fi.path, sub.lineno, JitBoundaryPass.id,
                    f"per-element Python loop inside {where} — unrolls at "
                    f"trace time / re-serializes vectorized work",
                    "vectorize, or use lax.scan over a fixed-shape axis"))
    return out


def _own_loops(fn_node) -> List[ast.AST]:
    """Loops in the function's own body — nested helper defs (e.g. a
    ``drain()`` closure called every N steps) police separately if they
    are themselves boundaries."""
    out: List[ast.AST] = []
    stack = list(ast.iter_child_nodes(fn_node))
    while stack:
        node = stack.pop()
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        if isinstance(node, (ast.For, ast.While, ast.AsyncFor)):
            out.append(node)
        stack.extend(ast.iter_child_nodes(node))
    return out


def police_dispatch(index: PackageIndex, fi: FuncInfo) -> List[Finding]:
    mod = index.modules[fi.modname]
    out: List[Finding] = []
    for loop in _own_loops(fi.node):
        for stmt in loop.body + loop.orelse:
            for sub in ast.walk(stmt):
                if isinstance(sub, (ast.FunctionDef, ast.AsyncFunctionDef)):
                    continue
                if isinstance(sub, ast.Call):
                    what = _sync_call(index, sub, mod.imports)
                    if what:
                        out.append(Finding(
                            fi.path, sub.lineno, JitBoundaryPass.id,
                            f"{what} inside the dispatch loop of "
                            f"{fi.qualname} — a per-iteration host sync "
                            f"re-serializes the async dispatch pipeline",
                            "accumulate on device / fetch behind the "
                            "dispatch frontier, drain once after the "
                            "loop"))
    return out


@register_pass
class JitBoundaryPass(LintPass):
    id = "jit-host-sync"
    title = "jit-boundary host-sync escape analysis (auto-discovered)"
    rationale = (
        "trace-boundary regressions — host syncs, frozen clocks/RNG, "
        "per-element loops inside traced code, per-iteration syncs in "
        "dispatch loops — break no functional test; discovery polices "
        "code nobody hand-listed")

    def run(self, project: Project) -> List[Finding]:
        disc = discover(project)
        seen: Set[Tuple[str, int, str]] = set()
        out: List[Finding] = []
        for fi in disc.traced.values():
            for f in police_traced(disc.index, fi):
                k = (f.file, f.line, f.message.split(" inside ")[0])
                if k not in seen:
                    seen.add(k)
                    out.append(f)
        for key, fi in disc.dispatch.items():
            if key in disc.traced:
                continue
            for f in police_dispatch(disc.index, fi):
                k = (f.file, f.line, f.message.split(" inside ")[0])
                if k not in seen:
                    seen.add(k)
                    out.append(f)
        return out
