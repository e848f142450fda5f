"""graftlint project model: modules, imports, functions, jit-reachability.

The rules need three whole-program facts no single-node visitor can
supply:

1. **what a dotted name means** — ``np.asarray`` vs a local ``np``;
   resolved through each module's import aliases so rules match
   canonical names (``numpy.asarray``, ``jax.random.split``) instead of
   spellings;
2. **which functions are traced** ("hot") — jit/pmap/vmap decorated,
   passed into ``lax.scan``/``shard_map``/``pallas_call``/… as a body,
   or (transitively) called from such a body. The serving decode loop
   is covered by the same mechanism: ``jax.jit(decode, ...)`` inside
   ``Engine._build_executables`` marks ``decode`` hot, and the ``row``
   fn it vmaps inherits;
3. **where jit call-sites bind** — ``self._decode = jax.jit(decode,
   donate_argnums=...)`` associates the donating wrapper with the
   attribute name the engine loop later calls.

Resolution is best-effort and *underclaiming by design*: an edge the
model can't see means a missed finding, never a false one. The
``# graftlint: hot -- reason`` marker (core.py) patches the holes the
call graph can't reach.
"""

from __future__ import annotations

import ast
import dataclasses
from typing import Dict, List, Optional, Sequence, Set, Tuple

from apex1_tpu.lint.core import Finding, ModuleSource, parse_module

#: Callables whose function-valued arguments become traced bodies.
TRACE_ENTRIES = frozenset({
    "jax.jit", "jax.pmap", "jax.vmap", "jax.grad", "jax.value_and_grad",
    "jax.checkpoint", "jax.remat", "jax.custom_vjp", "jax.custom_jvp",
    "jax.jvp", "jax.vjp", "jax.linearize", "jax.shard_map",
    "jax.experimental.shard_map.shard_map",
    "jax.lax.scan", "jax.lax.fori_loop", "jax.lax.while_loop",
    "jax.lax.cond", "jax.lax.switch", "jax.lax.map",
    "jax.lax.associative_scan", "jax.lax.custom_root",
    "jax.experimental.pallas.pallas_call",
    "apex1_tpu.ops._common.kernel_call",
    "flax.linen.remat", "flax.linen.jit", "flax.linen.scan",
})

#: Host-callback escapes: a function handed to these runs on the HOST,
#: so hotness must NOT propagate through them.
CALLBACK_ENTRIES = frozenset({
    "jax.pure_callback", "jax.experimental.io_callback",
    "jax.debug.callback", "jax.debug.print",
})


@dataclasses.dataclass
class FunctionInfo:
    mod: ModuleSource
    node: ast.AST                       # FunctionDef/AsyncFunctionDef/Lambda
    scope: Tuple[str, ...]              # nesting path incl. own name
    cls: Optional[str]                  # innermost enclosing class
    params: List[str]

    @property
    def name(self) -> str:
        return self.scope[-1]

    @property
    def qualname(self) -> str:
        return ".".join(self.scope)

    @property
    def line(self) -> int:
        return getattr(self.node, "lineno", 0)

    def def_line_range(self) -> Tuple[int, int]:
        """Lines a hot/cold marker may sit on: first decorator through
        the signature (i.e. up to the first body statement)."""
        node = self.node
        start = getattr(node, "lineno", 0)
        for dec in getattr(node, "decorator_list", []):
            start = min(start, dec.lineno)
        body = getattr(node, "body", None)
        end = body[0].lineno if isinstance(body, list) and body else start
        return start, end


@dataclasses.dataclass
class JitSite:
    """One ``jax.jit(...)`` call: its target (when resolvable), its
    static/donate annotations (when constant), and the local / ``self.``
    names the wrapper is bound to."""

    mod: ModuleSource
    call: ast.Call
    target: Optional[FunctionInfo]
    static_argnums: Optional[Tuple[int, ...]]
    static_argnames: Optional[Tuple[str, ...]]
    donate_argnums: Optional[Tuple[int, ...]]
    bound_names: List[str]              # "step_fn", "self._decode", ...
    in_scope: Tuple[str, ...]           # scope the jit call appears in


def _const_argnums(node: Optional[ast.AST]) -> Optional[Tuple[int, ...]]:
    """Evaluate an argnums expression to a tuple of ints. An ``IfExp``
    with literal arms (the engine's CPU-donation toggle) resolves to the
    UNION — code must be donation-correct on the branch where donation
    is on."""
    if node is None:
        return None
    try:
        val = ast.literal_eval(node)
    except (ValueError, SyntaxError):
        if isinstance(node, ast.IfExp):
            a = _const_argnums(node.body)
            b = _const_argnums(node.orelse)
            if a is not None and b is not None:
                return tuple(sorted(set(a) | set(b)))
        return None
    if isinstance(val, int):
        return (val,)
    if isinstance(val, (tuple, list)) and all(
            isinstance(v, int) for v in val):
        return tuple(val)
    return None


def _const_argnames(node: Optional[ast.AST]) -> Optional[Tuple[str, ...]]:
    if node is None:
        return None
    try:
        val = ast.literal_eval(node)
    except (ValueError, SyntaxError):
        return None
    if isinstance(val, str):
        return (val,)
    if isinstance(val, (tuple, list)) and all(
            isinstance(v, str) for v in val):
        return tuple(val)
    return None


def _param_names(node: ast.AST) -> List[str]:
    a = getattr(node, "args", None)
    if a is None:
        return []
    names = [p.arg for p in a.posonlyargs] + [p.arg for p in a.args]
    if a.vararg:
        names.append(a.vararg.arg)
    names += [p.arg for p in a.kwonlyargs]
    if a.kwarg:
        names.append(a.kwarg.arg)
    return names


def own_body_walk(node: ast.AST):
    """Walk a function's OWN statements: descend everywhere except into
    nested function/class/lambda bodies (those are separate scopes with
    their own hotness)."""
    if isinstance(node, ast.Lambda):
        roots = [node.body]
    else:
        roots = list(getattr(node, "body", []))
    stack = list(roots)
    while stack:
        n = stack.pop()
        yield n
        for child in ast.iter_child_nodes(n):
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef,
                                  ast.Lambda, ast.ClassDef)):
                continue
            stack.append(child)


class Project:
    """Whole-program index over a set of parsed modules."""

    def __init__(self, modules: Sequence[ModuleSource]):
        self.modules: List[ModuleSource] = list(modules)
        self.by_name: Dict[str, ModuleSource] = {
            m.modname: m for m in self.modules if m.modname}
        # per module: import alias -> dotted target
        self.aliases: Dict[str, Dict[str, str]] = {}
        # (modname, local name) -> (defining modname, function name)
        self.imported_funcs: Dict[Tuple[str, str], Tuple[str, str]] = {}
        # (modname, scope tuple) -> FunctionInfo
        self.functions: Dict[Tuple[str, Tuple[str, ...]], FunctionInfo] = {}
        self.jit_sites: List[JitSite] = []
        self.jit_site_by_call: Dict[int, JitSite] = {}  # id(Call) -> site
        self.hot: Set[int] = set()        # id(FunctionInfo.node)
        self._cold: Set[int] = set()
        self._edges: Dict[int, List[FunctionInfo]] = {}
        self._info_by_node: Dict[int, FunctionInfo] = {}

        for mod in self.modules:
            if mod.tree is not None:
                self._index_imports(mod)
        for mod in self.modules:
            if mod.tree is not None:
                self._index_functions(mod)
        for mod in self.modules:
            if mod.tree is not None:
                self._index_calls(mod)
        self._apply_markers()
        self._propagate_hot()

    # ---- imports --------------------------------------------------------

    def _index_imports(self, mod: ModuleSource) -> None:
        amap: Dict[str, str] = {}
        for node in ast.walk(mod.tree):
            if isinstance(node, ast.Import):
                for al in node.names:
                    # `import a.b as c` binds c -> a.b; plain
                    # `import a.b` binds only the root name a
                    if al.asname:
                        amap[al.asname] = al.name
                    else:
                        root = al.name.split(".")[0]
                        amap[root] = root
            elif isinstance(node, ast.ImportFrom) and node.module:
                if node.level:
                    base = self._resolve_relative(mod, node)
                    if base is None:
                        continue
                else:
                    base = node.module
                for al in node.names:
                    if al.name == "*":
                        continue
                    local = al.asname or al.name
                    amap[local] = f"{base}.{al.name}"
                    if mod.modname:
                        self.imported_funcs[(mod.modname, local)] = (
                            base, al.name)
        self.aliases[mod.modname or mod.path] = amap

    @staticmethod
    def _resolve_relative(mod: ModuleSource,
                          node: ast.ImportFrom) -> Optional[str]:
        if not mod.modname:
            return None
        parts = mod.modname.split(".")
        # level 1 = current package. For a plain module that means
        # dropping its own name; a package __init__ (modname already
        # IS the package) drops one component fewer.
        drop = node.level
        if mod.path.endswith("__init__.py"):
            drop -= 1
        if drop > len(parts) or drop < 0:
            return None
        base_parts = parts[:len(parts) - drop] if drop else parts
        if node.module:
            base_parts = base_parts + [node.module]
        return ".".join(base_parts) if base_parts else None

    def alias_map(self, mod: ModuleSource) -> Dict[str, str]:
        return self.aliases.get(mod.modname or mod.path, {})

    def resolve_dotted(self, mod: ModuleSource,
                       node: ast.AST) -> Optional[str]:
        """Canonical dotted name of a Name/Attribute chain, with the
        base translated through the module's import aliases.
        ``self.x.y`` resolves to ``"self.x.y"`` (callers special-case
        it); a chain rooted at an unimported local returns None."""
        parts: List[str] = []
        cur = node
        while isinstance(cur, ast.Attribute):
            parts.append(cur.attr)
            cur = cur.value
        if not isinstance(cur, ast.Name):
            return None
        parts.append(cur.id)
        parts.reverse()
        base = parts[0]
        amap = self.alias_map(mod)
        if base in ("self", "cls"):
            return ".".join(parts)
        if base in amap:
            return ".".join([amap[base]] + parts[1:])
        if len(parts) == 1:
            return None
        return None

    # ---- functions ------------------------------------------------------

    def _index_functions(self, mod: ModuleSource) -> None:
        def visit(node, scope: Tuple[str, ...], cls: Optional[str]):
            for child in ast.iter_child_nodes(node):
                if isinstance(child, (ast.FunctionDef,
                                      ast.AsyncFunctionDef)):
                    sub = scope + (child.name,)
                    self._register(mod, child, sub, cls)
                    visit(child, sub, cls)
                elif isinstance(child, ast.ClassDef):
                    visit(child, scope + (child.name,), child.name)
                elif isinstance(child, ast.Lambda):
                    sub = scope + (f"<lambda:{child.lineno}>",)
                    self._register(mod, child, sub, cls)
                    visit(child, sub, cls)
                else:
                    visit(child, scope, cls)

        visit(mod.tree, (), None)

    def _register(self, mod, node, scope, cls) -> FunctionInfo:
        info = FunctionInfo(mod=mod, node=node, scope=scope, cls=cls,
                            params=_param_names(node))
        self.functions[(mod.modname or mod.path, scope)] = info
        self._info_by_node[id(node)] = info
        return info

    def info_for(self, node: ast.AST) -> Optional[FunctionInfo]:
        return self._info_by_node.get(id(node))

    def lookup_function(self, mod: ModuleSource, scope: Tuple[str, ...],
                        name: str) -> Optional[FunctionInfo]:
        """Lexical lookup of a bare name from inside ``scope``."""
        key = mod.modname or mod.path
        for k in range(len(scope), -1, -1):
            info = self.functions.get((key, scope[:k] + (name,)))
            if info is not None:
                return info
        imp = self.imported_funcs.get((mod.modname, name))
        if imp is not None:
            return self.functions.get((imp[0], (imp[1],)))
        return None

    def _resolve_func_arg(self, mod: ModuleSource, scope: Tuple[str, ...],
                          arg: ast.AST) -> Optional[FunctionInfo]:
        """A function-valued argument: bare name, lambda, self-method,
        or another trace-entry call wrapping one (``jax.jit(
        jax.shard_map(step, ...), ...)`` reaches ``step``)."""
        if isinstance(arg, ast.Name):
            return self.lookup_function(mod, scope, arg.id)
        if isinstance(arg, ast.Lambda):
            return self.info_for(arg)
        if isinstance(arg, ast.Attribute):
            dotted = self.resolve_dotted(mod, arg)
            if dotted and dotted.startswith(("self.", "cls.")):
                parts = dotted.split(".")
                if len(parts) == 2:
                    info = self._method_lookup(mod, scope, parts[1])
                    if info is not None:
                        return info
            return None
        if isinstance(arg, ast.Call):
            callee = self.resolve_dotted(mod, arg.func)
            if callee in TRACE_ENTRIES or (
                    isinstance(arg.func, ast.Name)
                    and arg.func.id in ("partial",)):
                for sub in list(arg.args):
                    info = self._resolve_func_arg(mod, scope, sub)
                    if info is not None:
                        return info
        return None

    def _method_lookup(self, mod: ModuleSource, scope: Tuple[str, ...],
                       name: str) -> Optional[FunctionInfo]:
        key = mod.modname or mod.path
        # innermost enclosing class on the scope path
        for k in range(len(scope), 0, -1):
            info = self.functions.get((key, scope[:k - 1] + (name,)))
            if info is not None and info.cls is not None:
                return info
        return None

    # ---- calls: hot roots, edges, jit sites -----------------------------

    def _index_calls(self, mod: ModuleSource) -> None:
        for (mkey, scope), info in list(self.functions.items()):
            if mkey != (mod.modname or mod.path):
                continue
            edges: List[FunctionInfo] = []
            for n in own_body_walk(info.node):
                if isinstance(n, ast.Call):
                    self._one_call(mod, scope, n, edges)
            self._edges[id(info.node)] = edges
            # decorators evaluate in the ENCLOSING scope but describe
            # this function
            for dec in getattr(info.node, "decorator_list", []):
                self._decorator(mod, info, dec)
        # module top level: calls outside any def. They run at import
        # time (host) so the edge list is discarded — but _one_call
        # still registers jit sites and hot roots (`step = jax.jit(f,
        # ...)` at module scope).
        edges = []
        for n in own_body_walk_module(mod.tree):
            if isinstance(n, ast.Call):
                self._one_call(mod, (), n, edges)

    def _one_call(self, mod: ModuleSource, scope: Tuple[str, ...],
                  call: ast.Call, edges: List[FunctionInfo]) -> None:
        callee = self.resolve_dotted(mod, call.func)
        if callee in CALLBACK_ENTRIES:
            return  # args run host-side; no edge, no hotness
        if callee in TRACE_ENTRIES:
            for arg in call.args:
                target = self._resolve_func_arg(mod, scope, arg)
                if target is not None:
                    self.hot.add(id(target.node))
            if callee == "jax.jit":
                self._record_jit_site(mod, scope, call)
            return
        if callee == "functools.partial" or (
                isinstance(call.func, ast.Name)
                and call.func.id == "partial"):
            inner = call.args[0] if call.args else None
            if inner is not None and self.resolve_dotted(
                    mod, inner) in TRACE_ENTRIES:
                for arg in call.args[1:]:
                    target = self._resolve_func_arg(mod, scope, arg)
                    if target is not None:
                        self.hot.add(id(target.node))
                if self.resolve_dotted(mod, inner) == "jax.jit":
                    self._record_jit_site(mod, scope, call,
                                          partial_form=True)
            return
        # plain call: call-graph edge for hot propagation
        if isinstance(call.func, ast.Name):
            target = self.lookup_function(mod, scope, call.func.id)
            if target is not None:
                edges.append(target)
        elif isinstance(call.func, ast.Attribute):
            dotted = self.resolve_dotted(mod, call.func)
            if dotted is None:
                return
            if dotted.startswith(("self.", "cls.")):
                parts = dotted.split(".")
                if len(parts) == 2:
                    target = self._method_lookup(mod, scope, parts[1])
                    if target is not None:
                        edges.append(target)
                return
            # alias.func where alias is a project module
            head, _, fname = dotted.rpartition(".")
            if head in self.by_name:
                target = self.functions.get((head, (fname,)))
                if target is not None:
                    edges.append(target)

    def _decorator(self, mod: ModuleSource, info: FunctionInfo,
                   dec: ast.AST) -> None:
        dotted = self.resolve_dotted(mod, dec) if not isinstance(
            dec, ast.Call) else self.resolve_dotted(mod, dec.func)
        if dotted in TRACE_ENTRIES:
            self.hot.add(id(info.node))
            if dotted == "jax.jit" and isinstance(dec, ast.Call):
                self._record_jit_site(mod, info.scope[:-1], dec,
                                      decorator_of=info)
            return
        if isinstance(dec, ast.Call) and (
                self.resolve_dotted(mod, dec.func) == "functools.partial"
                or (isinstance(dec.func, ast.Name)
                    and dec.func.id == "partial")):
            inner = dec.args[0] if dec.args else None
            if inner is not None and self.resolve_dotted(
                    mod, inner) in TRACE_ENTRIES:
                self.hot.add(id(info.node))
                if self.resolve_dotted(mod, inner) == "jax.jit":
                    self._record_jit_site(mod, info.scope[:-1], dec,
                                          partial_form=True,
                                          decorator_of=info)

    def _record_jit_site(self, mod: ModuleSource, scope: Tuple[str, ...],
                         call: ast.Call, partial_form: bool = False,
                         decorator_of: Optional[FunctionInfo] = None
                         ) -> None:
        kw = {k.arg: k.value for k in call.keywords if k.arg}
        target = decorator_of
        if target is None:
            pos = call.args[1:] if partial_form else call.args
            if pos:
                target = self._resolve_func_arg(mod, scope, pos[0])
        site = JitSite(
            mod=mod, call=call, target=target,
            static_argnums=_const_argnums(kw.get("static_argnums")),
            static_argnames=_const_argnames(kw.get("static_argnames")),
            donate_argnums=_const_argnums(kw.get("donate_argnums")),
            bound_names=[], in_scope=scope)
        self.jit_sites.append(site)
        self.jit_site_by_call[id(call)] = site

    # ---- markers + propagation ------------------------------------------

    def _apply_markers(self) -> None:
        """Bind each hot/cold marker to the INNERMOST function whose
        decorator-to-first-statement span contains its target line —
        when a nested def is an enclosing function's first statement,
        both spans contain the def line and only the nested function is
        the marker's subject. Detached markers (binding to nothing)
        become APX000 findings: a marker that silently stops binding
        would silently drop gate coverage."""
        per_marker: Dict[Tuple[int, int, str], FunctionInfo] = {}
        for info in self.functions.values():
            lo, hi = info.def_line_range()
            for kind, table in (("cold", info.mod.cold_lines),
                                ("hot", info.mod.hot_lines)):
                for target in table:
                    if not lo <= target <= hi:
                        continue
                    key = (id(info.mod), target, kind)
                    prev = per_marker.get(key)
                    if prev is None or info.def_line_range()[0] >= \
                            prev.def_line_range()[0]:
                        per_marker[key] = info
        bound: Set[Tuple[int, int, str]] = set()
        for (mod_id, target, kind), info in per_marker.items():
            bound.add((mod_id, target, kind))
            if kind == "cold":
                self._cold.add(id(info.node))
            else:
                self.hot.add(id(info.node))
        for mod in self.modules:
            for kind, table in (("hot", mod.hot_lines),
                                ("cold", mod.cold_lines)):
                for target, comment_line in table.items():
                    if (id(mod), target, kind) not in bound:
                        mod.errors.append(Finding(
                            "APX000", mod.path, comment_line, 0,
                            f"detached '{kind}' marker: no function "
                            f"definition spans line {target} — the "
                            f"marker binds to nothing (gate coverage "
                            f"would silently change)"))

    def _propagate_hot(self) -> None:
        self.hot -= self._cold
        work = list(self.hot)
        while work:
            nid = work.pop()
            for callee in self._edges.get(nid, []):
                cid = id(callee.node)
                if cid in self._cold or cid in self.hot:
                    continue
                self.hot.add(cid)
                work.append(cid)

    def is_hot(self, node: ast.AST) -> bool:
        return id(node) in self.hot

    def hot_functions(self) -> List[FunctionInfo]:
        return [i for i in self.functions.values()
                if id(i.node) in self.hot]


def own_body_walk_module(tree: ast.Module):
    """Module top-level statements, not descending into defs/classes."""
    stack = list(tree.body)
    while stack:
        n = stack.pop()
        yield n
        for child in ast.iter_child_nodes(n):
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef,
                                  ast.Lambda, ast.ClassDef)):
                continue
            stack.append(child)


def build_project(named_sources: Dict[str, Tuple[str, str]]) -> Project:
    """``{path: (modname, text)}`` -> Project."""
    mods = [parse_module(path, text, modname)
            for path, (modname, text) in named_sources.items()]
    return Project(mods)
