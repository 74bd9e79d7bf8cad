"""Jit-region resolver: which functions are (transitively) traced.

The resolver scans a set of Python files, indexes every function
definition (module-level, methods, nested closures), finds the **trace
entry points**, and propagates tracedness over a best-effort call graph.

Entry points recognized (the forms this repo actually uses):

- decorator forms: ``@jax.jit``, ``@jit``,
  ``@functools.partial(jax.jit, static_argnames=...)``,
  and ``@partial(jit, ...)``;
- call forms: ``jax.jit(fn, ...)``, ``vmap(fn)``, ``shard_map(fn,
  mesh=...)``,
  ``pl.pallas_call(kernel, ...)`` — ``fn`` resolved lexically (local
  defs of enclosing functions, then module scope, then imports);
- bindings: ``execute = jax.jit(_execute_impl, static_argnames=...,
  donate_argnums=...)`` records a `JitBinding` so call-site rules
  (unhashable statics, donation misuse) know each binding's static and
  donated parameters.

Call-graph edges are resolved conservatively:

- bare names: lexical scope chain, then module functions, then
  from-imports into other scanned modules;
- ``self.m(...)`` / ``cls.m(...)``: methods of the enclosing class;
- ``alias.f(...)`` where ``alias`` imports a scanned module: that
  module's top-level ``f``;
- ``obj.m(...)`` otherwise: every scanned class method named ``m``,
  but only when the name is specific — at most `ATTR_CANDIDATE_CAP`
  candidate definitions and not in `COMMON_METHOD_NAMES` (``get``,
  ``update``, ...), so dict/list idioms don't drag host code into the
  traced set.

The traced set is the BFS closure of the entry points over these edges;
every function lexically nested inside a traced function is traced too
(closures jitted with their parent). Rules receive, per traced
function, the chain of resolution (`trace_via`) as evidence.
"""
from __future__ import annotations

import ast
import dataclasses
import os
from typing import Dict, List, Optional, Sequence, Set, Tuple

# Dotted-name suffixes that make a call a trace entry point when a
# function reference is passed as the first argument.
JIT_NAMES = {"jax.jit", "jit"}
VMAP_NAMES = {"jax.vmap", "vmap"}
SHARD_MAP_SUFFIX = "shard_map"
PALLAS_CALL_SUFFIX = "pallas_call"
PARTIAL_NAMES = {"functools.partial", "partial"}
# Attribute-call resolution guards (see module docstring).
ATTR_CANDIDATE_CAP = 4
COMMON_METHOD_NAMES = {
    "get", "items", "keys", "values", "append", "extend", "update",
    "copy", "pop", "add", "remove", "clear", "join", "split", "strip",
    "format", "replace", "sort", "setdefault", "record", "count",
    "stats", "close", "write", "read", "put", "run",
}


def dotted_name(node: ast.AST) -> Optional[str]:
    """`a.b.c` -> "a.b.c" for Name/Attribute chains, else None."""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


@dataclasses.dataclass
class JitBinding:
    """A name bound to a jitted callable (decorator or call form)."""
    name: str
    module_path: str
    target: Optional["FunctionInfo"]
    static_argnames: Tuple[str, ...] = ()
    static_argnums: Tuple[int, ...] = ()
    donate_argnums: Tuple[int, ...] = ()
    line: int = 0


@dataclasses.dataclass
class FunctionInfo:
    qualname: str            # "<relpath>::Outer.<locals>.inner"
    name: str
    path: str
    node: ast.AST            # FunctionDef / AsyncFunctionDef
    line: int
    class_name: Optional[str]
    parent: Optional["FunctionInfo"]
    params: Tuple[str, ...]     # positional params then kwonly params
    n_positional: int = 0
    is_root: bool = False
    root_via: Optional[str] = None
    static_argnames: Tuple[str, ...] = ()
    static_argnums: Tuple[int, ...] = ()
    traced: bool = False
    trace_via: Optional[str] = None
    # resolved call sites reaching this function from traced callers:
    # (caller, Call node) — rules use these for inter-procedural
    # argument taint (a param is traced only if some reaching call
    # binds a traced value to it)
    call_sites: List[Tuple["FunctionInfo", ast.Call]] = dataclasses.field(
        default_factory=list)

    def static_params(self) -> Set[str]:
        s = set(self.static_argnames)
        for i in self.static_argnums:
            if 0 <= i < len(self.params):
                s.add(self.params[i])
        return s


@dataclasses.dataclass
class ModuleInfo:
    path: str                # as given (relative to cwd in the CLI)
    tree: ast.Module
    source: str
    lines: List[str]
    # import alias -> dotted module ("np" -> "numpy",
    # "_morton" -> "repro.devtree.morton")
    imports: Dict[str, str] = dataclasses.field(default_factory=dict)
    # from-import local name -> (module, attr)
    from_imports: Dict[str, Tuple[str, str]] = dataclasses.field(
        default_factory=dict)
    functions: List[FunctionInfo] = dataclasses.field(default_factory=list)
    bindings: Dict[str, JitBinding] = dataclasses.field(default_factory=dict)

    def numpy_aliases(self) -> Set[str]:
        return {a for a, m in self.imports.items() if m == "numpy"} | {
            a for a, (m, attr) in self.from_imports.items()
            if m == "numpy" and attr == "*"}

    def alias_for(self, dotted_module: str) -> Optional[str]:
        for a, m in self.imports.items():
            if m == dotted_module:
                return a
        return None


def _collect_imports(mod: ModuleInfo) -> None:
    for node in ast.walk(mod.tree):
        if isinstance(node, ast.Import):
            for al in node.names:
                mod.imports[al.asname or al.name.split(".")[0]] = al.name
        elif isinstance(node, ast.ImportFrom) and node.module:
            for al in node.names:
                mod.from_imports[al.asname or al.name] = (node.module,
                                                          al.name)


def parse_module(path: str, source: Optional[str] = None) -> ModuleInfo:
    if source is None:
        with open(path, "r", encoding="utf-8") as f:
            source = f.read()
    tree = ast.parse(source, filename=path)
    mod = ModuleInfo(path=path, tree=tree, source=source,
                     lines=source.splitlines())
    _collect_imports(mod)
    _index_functions(mod)
    return mod


def scan_paths(paths: Sequence[str]) -> List[ModuleInfo]:
    """Parse every ``.py`` file under the given files/directories."""
    files: List[str] = []
    for p in paths:
        if os.path.isdir(p):
            for root, _dirs, names in os.walk(p):
                for n in sorted(names):
                    if n.endswith(".py"):
                        files.append(os.path.join(root, n))
        elif p.endswith(".py"):
            files.append(p)
    mods = []
    for f in sorted(set(files)):
        try:
            mods.append(parse_module(f))
        except SyntaxError:
            continue  # not our job; leave to the interpreter/CI
    return mods


def _index_functions(mod: ModuleInfo) -> None:
    """Fill mod.functions with qualnames, class and nesting context."""

    def visit(node, qual_prefix, class_name, parent):
        for child in ast.iter_child_nodes(node):
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef)):
                qual = (f"{qual_prefix}.{child.name}" if qual_prefix
                        else child.name)
                pos = [a.arg for a in (child.args.posonlyargs
                                       + child.args.args)]
                params = tuple(pos + [a.arg
                                      for a in child.args.kwonlyargs])
                info = FunctionInfo(
                    qualname=f"{mod.path}::{qual}", name=child.name,
                    path=mod.path, node=child, line=child.lineno,
                    class_name=class_name, parent=parent, params=params,
                    n_positional=len(pos))
                mod.functions.append(info)
                visit(child, f"{qual}.<locals>", class_name, info)
            elif isinstance(child, ast.ClassDef):
                qual = (f"{qual_prefix}.{child.name}" if qual_prefix
                        else child.name)
                visit(child, qual, child.name, parent)
            else:
                visit_stmts(child, qual_prefix, class_name, parent)

    def visit_stmts(node, qual_prefix, class_name, parent):
        # descend into non-def statements looking for nested defs
        for child in ast.iter_child_nodes(node):
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef,
                                  ast.ClassDef)):
                # re-dispatch through visit for proper qualnaming
                fake = ast.Module(body=[child], type_ignores=[])
                visit(fake, qual_prefix, class_name, parent)
            else:
                visit_stmts(child, qual_prefix, class_name, parent)

    visit(mod.tree, "", None, None)


def _const_str_tuple(node) -> Tuple[str, ...]:
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return (node.value,)
    if isinstance(node, (ast.Tuple, ast.List)):
        out = []
        for e in node.elts:
            if isinstance(e, ast.Constant) and isinstance(e.value, str):
                out.append(e.value)
        return tuple(out)
    return ()


def _const_int_tuple(node) -> Tuple[int, ...]:
    if isinstance(node, ast.Constant) and isinstance(node.value, int):
        return (node.value,)
    if isinstance(node, (ast.Tuple, ast.List)):
        return tuple(e.value for e in node.elts
                     if isinstance(e, ast.Constant)
                     and isinstance(e.value, int))
    return ()


def _module_const(mod: Optional["ModuleInfo"], name: str):
    """Module-level `NAME = (...)` assignment value, if any."""
    if mod is None:
        return None
    for stmt in mod.tree.body:
        if isinstance(stmt, ast.Assign):
            for t in stmt.targets:
                if isinstance(t, ast.Name) and t.id == name:
                    return stmt.value
    return None


def _jit_kwargs(call: ast.Call, mod: Optional["ModuleInfo"] = None):
    names = nums = dons = ()
    for kw in call.keywords:
        val = kw.value
        if isinstance(val, ast.Name):
            # e.g. static_argnames=_EXEC_OPTS with the tuple defined at
            # module level
            val = _module_const(mod, val.id) or val
        if kw.arg == "static_argnames":
            names = _const_str_tuple(val)
        elif kw.arg == "static_argnums":
            nums = _const_int_tuple(val)
        elif kw.arg == "donate_argnums":
            dons = _const_int_tuple(val)
    return names, nums, dons


def _is_jit_callable(node) -> bool:
    d = dotted_name(node)
    return d in JIT_NAMES or (d is not None and d.endswith(".jit"))


def _entry_call_kind(call: ast.Call) -> Optional[str]:
    """Classify a Call as a trace entry point ("jit"/"vmap"/"shard_map"
    /"pallas_call") when its first positional arg is a function ref."""
    d = dotted_name(call.func)
    if d is None:
        return None
    if d in JIT_NAMES or d.endswith(".jit"):
        return "jit"
    if d in VMAP_NAMES or d.endswith(".vmap"):
        return "vmap"
    if d == SHARD_MAP_SUFFIX or d.endswith("." + SHARD_MAP_SUFFIX):
        return "shard_map"
    if d == PALLAS_CALL_SUFFIX or d.endswith("." + PALLAS_CALL_SUFFIX):
        return "pallas_call"
    return None


class TraceResolver:
    """Resolve trace roots and propagate tracedness over the call graph."""

    def __init__(self, modules: Sequence[ModuleInfo]):
        self.modules = list(modules)
        self.by_path: Dict[str, ModuleInfo] = {m.path: m for m in modules}
        # dotted module name guess: src/repro/a/b.py -> repro.a.b
        self.module_dotted: Dict[str, str] = {}
        for m in modules:
            dotted = m.path.replace("\\", "/").rsplit(".py", 1)[0]
            dotted = dotted.replace("/", ".")
            for prefix in ("src.",):
                if dotted.startswith(prefix):
                    dotted = dotted[len(prefix):]
            self.module_dotted[m.path] = dotted
        self.dotted_to_mod = {d: self.by_path[p]
                              for p, d in self.module_dotted.items()}
        # method name -> FunctionInfos (class methods only)
        self.methods: Dict[str, List[FunctionInfo]] = {}
        for m in modules:
            for fn in m.functions:
                if fn.class_name is not None and fn.parent is None:
                    self.methods.setdefault(fn.name, []).append(fn)
        self._find_roots()
        self._propagate()

    # -- root discovery ------------------------------------------------

    def _find_roots(self) -> None:
        for mod in self.modules:
            fn_by_node = {f.node: f for f in mod.functions}
            # decorator forms
            for fn in mod.functions:
                for dec in getattr(fn.node, "decorator_list", []):
                    via = self._decorator_root(dec)
                    if via is None:
                        continue
                    names, nums, dons = ((), (), ())
                    if isinstance(dec, ast.Call):
                        inner = (dec.args[0]
                                 if (dotted_name(dec.func) in PARTIAL_NAMES
                                     and dec.args) else dec)
                        if isinstance(inner, ast.Call):
                            names, nums, dons = _jit_kwargs(inner, mod)
                        if isinstance(dec, ast.Call) and dec is not inner:
                            n2, m2, d2 = _jit_kwargs(dec, mod)
                            names, nums, dons = (names or n2, nums or m2,
                                                 dons or d2)
                    self._mark_root(fn, via, names, nums)
                    if fn.class_name is None and fn.parent is None:
                        mod.bindings[fn.name] = JitBinding(
                            fn.name, mod.path, fn, names, nums, dons,
                            fn.line)
            # call forms + bindings
            for node in ast.walk(mod.tree):
                if not isinstance(node, ast.Call):
                    continue
                kind = _entry_call_kind(node)
                if kind is None or not node.args:
                    continue
                target = self._resolve_ref(mod, node.args[0], fn_by_node)
                names, nums, dons = _jit_kwargs(node, mod)
                if target is not None:
                    self._mark_root(
                        target,
                        f"{kind}({target.name}) @ {mod.path}:{node.lineno}",
                        names if kind == "jit" else (),
                        nums if kind == "jit" else ())
                if kind == "jit":
                    self._record_binding(mod, node, target, names, nums,
                                         dons)

    def _decorator_root(self, dec) -> Optional[str]:
        # @jax.jit / @jit
        if _is_jit_callable(dec):
            return f"@{dotted_name(dec)}"
        if isinstance(dec, ast.Call):
            dc = dotted_name(dec.func)
            if _is_jit_callable(dec.func):
                return f"@{dc}(...)"
            if dc in PARTIAL_NAMES and dec.args \
                    and _is_jit_callable(dec.args[0]):
                return f"@partial({dotted_name(dec.args[0])}, ...)"
        return None

    def _mark_root(self, fn: FunctionInfo, via: str,
                   names: Tuple[str, ...] = (),
                   nums: Tuple[int, ...] = ()) -> None:
        fn.is_root = True
        fn.root_via = fn.root_via or via
        fn.static_argnames = fn.static_argnames or names
        fn.static_argnums = fn.static_argnums or nums

    def _record_binding(self, mod, call, target, names, nums, dons):
        """`name = jax.jit(f, ...)` at module level -> JitBinding."""
        parent = getattr(call, "_lint_parent", None)
        # find the Assign wrapping this call at module level
        for stmt in mod.tree.body:
            if isinstance(stmt, ast.Assign) and stmt.value is call:
                for tgt in stmt.targets:
                    if isinstance(tgt, ast.Name):
                        mod.bindings[tgt.id] = JitBinding(
                            tgt.id, mod.path, target, names, nums, dons,
                            call.lineno)
        _ = parent

    # -- reference/call resolution --------------------------------------

    def _resolve_ref(self, mod: ModuleInfo, node,
                     fn_by_node) -> Optional[FunctionInfo]:
        """Resolve a function *reference* expression to a FunctionInfo."""
        if isinstance(node, ast.Name):
            return self._resolve_name(mod, node.id, node)
        if isinstance(node, ast.Attribute):
            d = dotted_name(node)
            if d is None:
                return None
            head, _, rest = d.partition(".")
            target_mod = self._imported_module(mod, head)
            if target_mod is not None and rest and "." not in rest:
                return self._module_level(target_mod, rest)
        return None

    def _imported_module(self, mod: ModuleInfo,
                         alias: str) -> Optional[ModuleInfo]:
        dotted = mod.imports.get(alias)
        if dotted is None and alias in mod.from_imports:
            src, attr = mod.from_imports[alias]
            dotted = f"{src}.{attr}"
        if dotted is None:
            return None
        return self.dotted_to_mod.get(dotted)

    def _module_level(self, mod: ModuleInfo,
                      name: str) -> Optional[FunctionInfo]:
        for fn in mod.functions:
            if fn.name == name and fn.parent is None \
                    and fn.class_name is None:
                return fn
        return None

    def _resolve_name(self, mod: ModuleInfo, name: str,
                      at_node) -> Optional[FunctionInfo]:
        """Lexical: enclosing functions' local defs, then module level,
        then from-imports into scanned modules."""
        line = getattr(at_node, "lineno", 0)
        enclosing = [f for f in mod.functions
                     if f.node.lineno <= line
                     <= max(f.node.lineno,
                            getattr(f.node, "end_lineno", f.node.lineno))]
        enclosing.sort(key=lambda f: f.node.lineno)
        for outer in reversed(enclosing):
            for fn in mod.functions:
                if fn.parent is outer and fn.name == name:
                    return fn
        top = self._module_level(mod, name)
        if top is not None:
            return top
        if name in mod.from_imports:
            src, attr = mod.from_imports[name]
            tmod = self.dotted_to_mod.get(src)
            if tmod is not None:
                return self._module_level(tmod, attr)
        return None

    def resolve_call(self, mod: ModuleInfo, caller: FunctionInfo,
                     call: ast.Call) -> List[FunctionInfo]:
        """Best-effort callee set for one call site (see module doc)."""
        func = call.func
        if isinstance(func, ast.Name):
            t = self._resolve_name(mod, func.id, call)
            return [t] if t is not None else []
        if isinstance(func, ast.Attribute):
            base = func.value
            meth = func.attr
            if isinstance(base, ast.Name):
                if base.id in ("self", "cls") and caller.class_name:
                    for fn in self.methods.get(meth, []):
                        if (fn.class_name == caller.class_name
                                and fn.path == mod.path):
                            return [fn]
                tmod = self._imported_module(mod, base.id)
                if tmod is not None:
                    t = self._module_level(tmod, meth)
                    return [t] if t is not None else []
            # generic obj.m(...): all scanned class methods named m,
            # when the name is specific enough
            if meth in COMMON_METHOD_NAMES:
                return []
            cands = self.methods.get(meth, [])
            if 0 < len(cands) <= ATTR_CANDIDATE_CAP:
                return list(cands)
        return []

    # -- propagation -----------------------------------------------------

    def _propagate(self) -> None:
        queue: List[FunctionInfo] = []
        for mod in self.modules:
            for fn in mod.functions:
                if fn.is_root:
                    fn.traced = True
                    fn.trace_via = fn.root_via
                    queue.append(fn)
        # lexically nested defs of traced functions are traced
        children: Dict[int, List[FunctionInfo]] = {}
        for mod in self.modules:
            for fn in mod.functions:
                if fn.parent is not None:
                    children.setdefault(id(fn.parent), []).append(fn)
        while queue:
            fn = queue.pop()
            for kid in children.get(id(fn), []):
                if not kid.traced:
                    kid.traced = True
                    kid.trace_via = f"nested in {fn.qualname}"
                    queue.append(kid)
            mod = self.by_path[fn.path]
            for node in ast.walk(fn.node):
                if not isinstance(node, ast.Call):
                    continue
                for callee in self.resolve_call(mod, fn, node):
                    callee.call_sites.append((fn, node))
                    if not callee.traced:
                        callee.traced = True
                        callee.trace_via = (f"called from {fn.qualname}:"
                                            f"{node.lineno}")
                        queue.append(callee)

    # -- queries ---------------------------------------------------------

    def traced_functions(self) -> List[FunctionInfo]:
        seen: Set[int] = set()
        out = []
        for mod in self.modules:
            for fn in mod.functions:
                if fn.traced and id(fn) not in seen:
                    seen.add(id(fn))
                    out.append(fn)
        return out

    def donating_bindings(self) -> Dict[str, JitBinding]:
        """name -> binding, for every jit binding with donate_argnums
        (plus the `*_donating` naming convention)."""
        out: Dict[str, JitBinding] = {}
        for mod in self.modules:
            for name, b in mod.bindings.items():
                if b.donate_argnums or name.endswith("_donating"):
                    out[name] = b
        return out
