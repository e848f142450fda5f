"""Kernel-body extraction: from AST to analyzable structures.

Two extractors live here:

1. :func:`pallas_sites` — every ``pallas_call`` call site in a module,
   with its kernel function resolved (through ``functools.partial``),
   its grid / ``num_scalar_prefetch`` / in_specs / out_shape /
   scratch_shapes parsed as far as they are static. The budget and
   binding passes (APX208/APX209) consume these.

2. :class:`ScheduleExtractor` — a micro-interpreter over a kernel
   function's body that, for a CONCRETE ring size ``n`` and grid step
   ``t``, evaluates ``pl.when`` predicates and slot arithmetic and
   emits the kernel's semaphore/DMA **event schedule**: buffer
   reads/writes, ``semaphore_signal``/``semaphore_wait``,
   ``make_async_remote_copy`` starts and their send/recv waits. The
   protocol model checker (APX201–203) simulates these schedules
   exhaustively.

The modelable fragment (documented in docs/lint.md): a protocol kernel
must take its ring size as a kw-only parameter named ``n`` (or
``ring_size``/``n_devices``) and its ring axis as ``axis_name``/
``axis``; slot indices and ``pl.when`` predicates must be arithmetic
over ``pl.program_id``, that ``n``, and integer constants. Everything
data-dependent is abstracted: an unsupported construct raises
:class:`ExtractError` and surfaces as an APX201 "unmodelable" finding —
a protocol kernel that cannot be machine-checked must be simplified or
suppressed with a reason, never silently passed.

Like the rest of graftlint this is stdlib-``ast`` only: no jax import,
runs on the no-TPU CI image in ~milliseconds per (kernel, n).
"""

from __future__ import annotations

import ast
import dataclasses
from typing import Dict, List, Optional, Tuple

from apex1_tpu.lint.project import FunctionInfo, ModuleSource, Project

PALLAS_CALL = "jax.experimental.pallas.pallas_call"
#: call sites: the primitive, and the repo's naming wrapper around it
#: (same signature; `name=` is metadata)
PALLAS_CALLS = (PALLAS_CALL, "apex1_tpu.ops._common.kernel_call")
PL = "jax.experimental.pallas"
PLTPU = "jax.experimental.pallas.tpu"

#: kw-only kernel params the checker binds to the trial ring size
RING_PARAMS = ("n", "ring_size", "n_devices")
#: kw-only kernel params bound to an (inert) axis token
AXIS_PARAMS = ("axis_name", "axis")

#: callables that make a kernel a "protocol kernel"
_PROTOCOL_OPS = (
    f"{PLTPU}.semaphore_signal",
    f"{PLTPU}.semaphore_wait",
    f"{PLTPU}.make_async_remote_copy",
)


# ---------------------------------------------------------------------------
# pallas_call site parsing (budget / binding passes)
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class ScratchEntry:
    """One ``scratch_shapes`` element, as static as the AST allows."""

    kind: str                 # "vmem" | "sem_dma" | "sem_regular" |
    #                           "sem_barrier" | "unknown"
    shape: Optional[Tuple]    # ints where static, None elsewhere
    dtype: Optional[str]      # "float32", ... when written literally
    line: int

    def static_bytes(self) -> Optional[int]:
        if self.kind != "vmem" or self.shape is None:
            return None
        total = 1
        for d in self.shape:
            if not isinstance(d, int):
                return None
            total *= d
        es = _DTYPE_BYTES.get(self.dtype or "", None)
        return None if es is None else total * es


_DTYPE_BYTES = {
    "float32": 4, "int32": 4, "uint32": 4, "bfloat16": 2, "float16": 2,
    "int8": 1, "uint8": 1, "bool_": 1, "float64": 8, "int64": 8,
}


@dataclasses.dataclass
class BlockSpecInfo:
    shape: Optional[Tuple]        # block shape, ints where static
    index_map_arity: Optional[int]
    line: int


@dataclasses.dataclass
class PallasSite:
    mod: ModuleSource
    call: ast.Call
    enclosing: Optional[FunctionInfo]   # the dispatch function
    kernel: Optional[FunctionInfo]      # resolved kernel body
    kernel_bindings: Dict[str, ast.AST]  # partial(...) kw bindings
    n_bound_pos: int                     # partial(...) positional args
    grid_len: Optional[int]
    num_scalar_prefetch: int
    n_inputs: Optional[int]
    n_outputs: Optional[int]
    scratch: List[ScratchEntry]
    in_specs: List[BlockSpecInfo]
    out_specs: List[BlockSpecInfo]

    @property
    def line(self) -> int:
        return self.call.lineno


def _static_int(node) -> Optional[int]:
    if isinstance(node, ast.Constant) and isinstance(node.value, int) \
            and not isinstance(node.value, bool):
        return node.value
    if isinstance(node, ast.UnaryOp) and isinstance(node.op, ast.USub):
        inner = _static_int(node.operand)
        return None if inner is None else -inner
    return None


def _static_shape(node) -> Optional[Tuple]:
    """A tuple/list literal -> tuple with ints where static and None
    placeholders elsewhere; non-sequence -> None."""
    if not isinstance(node, (ast.Tuple, ast.List)):
        return None
    return tuple(_static_int(el) for el in node.elts)


def _dtype_name(project: Project, mod: ModuleSource,
                node) -> Optional[str]:
    dotted = project.resolve_dotted(mod, node)
    if dotted:
        tail = dotted.rsplit(".", 1)[-1]
        if tail in _DTYPE_BYTES:
            return tail
    return None


def _parse_scratch(project: Project, mod: ModuleSource,
                   node) -> List[ScratchEntry]:
    out: List[ScratchEntry] = []
    if not isinstance(node, (ast.List, ast.Tuple)):
        return out
    for el in node.elts:
        line = el.lineno
        if isinstance(el, ast.Call):
            dotted = project.resolve_dotted(mod, el.func) or ""
            if dotted == f"{PLTPU}.VMEM":
                shape = _static_shape(el.args[0]) if el.args else None
                dt = (_dtype_name(project, mod, el.args[1])
                      if len(el.args) > 1 else None)
                out.append(ScratchEntry("vmem", shape, dt, line))
                continue
            if dotted == f"{PLTPU}.SemaphoreType.DMA":
                out.append(ScratchEntry("sem_dma", None, None, line))
                continue
            if dotted == f"{PLTPU}.SemaphoreType.BARRIER":
                out.append(ScratchEntry("sem_barrier", None, None, line))
                continue
        else:
            dotted = project.resolve_dotted(mod, el) or ""
            if dotted == f"{PLTPU}.SemaphoreType.REGULAR":
                out.append(ScratchEntry("sem_regular", None, None, line))
                continue
            if dotted == f"{PLTPU}.SemaphoreType.DMA":
                out.append(ScratchEntry("sem_dma", None, None, line))
                continue
            if dotted == f"{PLTPU}.SemaphoreType.BARRIER":
                out.append(ScratchEntry("sem_barrier", None, None, line))
                continue
        out.append(ScratchEntry("unknown", None, None, line))
    return out


def _parse_blockspec(project: Project, mod: ModuleSource,
                     node) -> Optional[BlockSpecInfo]:
    if not isinstance(node, ast.Call):
        return None
    dotted = project.resolve_dotted(mod, node.func) or ""
    if not dotted.endswith(".BlockSpec"):
        return None
    shape = _static_shape(node.args[0]) if node.args else None
    arity = None
    imap = node.args[1] if len(node.args) > 1 else None
    for kw in node.keywords:
        if kw.arg == "index_map":
            imap = kw.value
    if isinstance(imap, ast.Lambda):
        a = imap.args
        arity = len(a.posonlyargs) + len(a.args)
    return BlockSpecInfo(shape, arity, node.lineno)


def _parse_specs(project, mod, node) -> List[BlockSpecInfo]:
    out: List[BlockSpecInfo] = []
    if isinstance(node, (ast.List, ast.Tuple)):
        for el in node.elts:
            bs = _parse_blockspec(project, mod, el)
            if bs is not None:
                out.append(bs)
    else:
        bs = _parse_blockspec(project, mod, node)
        if bs is not None:
            out.append(bs)
    return out


def _count_out_shape(node) -> Optional[int]:
    """Number of outputs when the out_shape expression is statically a
    list/tuple (each element one output) or a single struct call."""
    if isinstance(node, (ast.List, ast.Tuple)):
        return len(node.elts)
    if isinstance(node, ast.Call):
        return 1
    return None


def _resolve_kernel(project: Project, mod: ModuleSource,
                    scope: Tuple[str, ...], node
                    ) -> Tuple[Optional[FunctionInfo],
                               Dict[str, ast.AST], int]:
    """First positional arg of pallas_call -> (kernel FunctionInfo,
    partial KW bindings, count of partial-bound POSITIONAL args)."""
    bindings: Dict[str, ast.AST] = {}
    if isinstance(node, ast.Call):
        dotted = project.resolve_dotted(mod, node.func) or ""
        is_partial = dotted == "functools.partial" or (
            isinstance(node.func, ast.Name)
            and node.func.id == "partial")
        if is_partial and node.args:
            for kw in node.keywords:
                if kw.arg:
                    bindings[kw.arg] = kw.value
            inner, more, n_pos = _resolve_kernel(project, mod, scope,
                                                 node.args[0])
            bindings.update(more)
            return inner, bindings, n_pos + len(node.args) - 1
        return None, bindings, 0
    if isinstance(node, ast.Name):
        return project.lookup_function(mod, scope, node.id), bindings, 0
    return None, bindings, 0


def pallas_sites(project: Project) -> List[PallasSite]:
    # innermost enclosing function per call node: a call inside a
    # nested def is reached by ast.walk of every enclosing function,
    # so keep the deepest scope only
    best: Dict[int, Tuple[int, ModuleSource, FunctionInfo, ast.Call]] = {}
    for info in project.functions.values():
        mod = info.mod
        for node in ast.walk(info.node):
            if isinstance(node, ast.Call) and (
                    project.resolve_dotted(mod, node.func)
                    in PALLAS_CALLS):
                prev = best.get(id(node))
                if prev is None or len(info.scope) > prev[0]:
                    best[id(node)] = (len(info.scope), mod, info, node)
    return [_parse_site(project, mod, info, node)
            for _, mod, info, node in best.values()]


def _parse_site(project: Project, mod: ModuleSource,
                enclosing: Optional[FunctionInfo],
                call: ast.Call) -> PallasSite:
    kw = {k.arg: k.value for k in call.keywords if k.arg}
    kernel, bindings, n_bound_pos = _resolve_kernel(
        project, mod, enclosing.scope if enclosing else (),
        call.args[0] if call.args else None)

    grid_len = None
    prefetch = 0
    in_specs: List[BlockSpecInfo] = []
    out_specs: List[BlockSpecInfo] = []
    scratch: List[ScratchEntry] = []

    grid_node = kw.get("grid")
    gs = kw.get("grid_spec")
    if isinstance(gs, ast.Call):
        gdotted = project.resolve_dotted(mod, gs.func) or ""
        if gdotted.endswith("PrefetchScalarGridSpec") or \
                gdotted.endswith("GridSpec"):
            gkw = {k.arg: k.value for k in gs.keywords if k.arg}
            grid_node = gkw.get("grid", grid_node)
            pf = _static_int(gkw.get("num_scalar_prefetch"))
            prefetch = pf if pf is not None else 0
            if "in_specs" in gkw:
                in_specs = _parse_specs(project, mod, gkw["in_specs"])
                kw.setdefault("in_specs", gkw["in_specs"])
            if "out_specs" in gkw:
                out_specs = _parse_specs(project, mod, gkw["out_specs"])
            if "scratch_shapes" in gkw:
                scratch = _parse_scratch(project, mod,
                                         gkw["scratch_shapes"])
    if isinstance(grid_node, (ast.Tuple, ast.List)):
        grid_len = len(grid_node.elts)
    elif _static_int(grid_node) is not None:
        grid_len = 1

    n_inputs = None
    if "in_specs" in kw:
        if not in_specs:
            in_specs = _parse_specs(project, mod, kw["in_specs"])
        if isinstance(kw["in_specs"], (ast.List, ast.Tuple)):
            n_inputs = len(kw["in_specs"].elts)
    if "out_specs" in kw and not out_specs:
        out_specs = _parse_specs(project, mod, kw["out_specs"])
    if "scratch_shapes" in kw and not scratch:
        scratch = _parse_scratch(project, mod, kw["scratch_shapes"])
    n_outputs = _count_out_shape(kw.get("out_shape"))

    return PallasSite(mod=mod, call=call, enclosing=enclosing,
                      kernel=kernel, kernel_bindings=bindings,
                      n_bound_pos=n_bound_pos,
                      grid_len=grid_len, num_scalar_prefetch=prefetch,
                      n_inputs=n_inputs, n_outputs=n_outputs,
                      scratch=scratch, in_specs=in_specs,
                      out_specs=out_specs)


def is_protocol_kernel(project: Project, info: FunctionInfo) -> bool:
    """Does this function body (incl. nested ``pl.when`` defs) touch the
    semaphore/DMA layer?"""
    for node in ast.walk(info.node):
        if isinstance(node, ast.Call):
            dotted = project.resolve_dotted(info.mod, node.func)
            if dotted in _PROTOCOL_OPS:
                return True
    return False


def uses_remote_dma(project: Project, info: FunctionInfo) -> bool:
    for node in ast.walk(info.node):
        if isinstance(node, ast.Call):
            dotted = project.resolve_dotted(info.mod, node.func)
            if dotted == f"{PLTPU}.make_async_remote_copy":
                return True
    return False


# ---------------------------------------------------------------------------
# schedule extraction: the micro-interpreter
# ---------------------------------------------------------------------------

class ExtractError(Exception):
    """Kernel falls outside the modelable fragment."""

    def __init__(self, msg: str, line: int = 0):
        super().__init__(msg)
        self.line = line


@dataclasses.dataclass(frozen=True)
class SlotRef:
    ref: str
    slot: Optional[int]       # None = the whole (unsliced) ref

    def key(self) -> Tuple[str, int]:
        return (self.ref, 0 if self.slot is None else self.slot)


@dataclasses.dataclass(frozen=True)
class Desc:
    src: SlotRef
    dst: SlotRef
    send_sem: SlotRef
    recv_sem: SlotRef
    off: int                  # ring offset of the target device
    line: int


@dataclasses.dataclass
class Event:
    kind: str                 # "read" | "write" | "signal" | "wait" |
    #                           "dma"
    line: int
    t: int = 0
    ref: Optional[SlotRef] = None      # read/write/signal/wait subject
    count: int = 1                     # signal inc / wait count
    off: int = 0                       # signal target ring offset
    desc: Optional[Desc] = None        # dma


class _Ref:
    __slots__ = ("name",)

    def __init__(self, name):
        self.name = name


class _RefAt:
    __slots__ = ("ref",)

    def __init__(self, ref):
        self.ref = ref


class _Data:
    """Opaque traced value; ``derived`` carries the concrete ints it was
    built from (the ``dev(i)`` provenance trick)."""

    __slots__ = ("derived",)

    def __init__(self, derived=frozenset()):
        self.derived = frozenset(derived)


class _Closure:
    __slots__ = ("node", "env")

    def __init__(self, node, env):
        self.node = node
        self.env = env


class _Method:
    __slots__ = ("desc", "op")

    def __init__(self, desc, op):
        self.desc = desc
        self.op = op


class _Axis:
    __slots__ = ()


_UNSET = object()


class ScheduleExtractor:
    """Interpret one kernel body for concrete (n, t); ``events`` is the
    program-order schedule of that grid step on any device (the ring is
    SPMD-symmetric; the interpreter runs as device 0, neighbor targets
    become signed ring offsets)."""

    def __init__(self, project: Project, mod: ModuleSource,
                 info: FunctionInfo, n: int, t: int):
        self.project = project
        self.mod = mod
        self.info = info
        self.n = n
        self.t = t
        self.events: List[Event] = []
        self._barrier = _Ref("<barrier>")

    # -- entry ------------------------------------------------------------

    def run(self) -> List[Event]:
        env: Dict[str, object] = {}
        node = self.info.node
        args = node.args
        for p in args.posonlyargs + args.args:
            env[p.arg] = _Ref(p.arg)
        for p in args.kwonlyargs:
            if p.arg in RING_PARAMS:
                env[p.arg] = self.n
            elif p.arg in AXIS_PARAMS:
                env[p.arg] = _Axis()
            else:
                raise ExtractError(
                    f"unmodelable kw-only kernel parameter {p.arg!r} "
                    f"(the checker binds only {RING_PARAMS} and "
                    f"{AXIS_PARAMS})", node.lineno)
        if args.vararg or args.kwarg:
            raise ExtractError("*args/**kwargs kernels are unmodelable",
                               node.lineno)
        self._exec_body(node.body, [env])
        for ev in self.events:
            ev.t = self.t
        return self.events

    # -- statements -------------------------------------------------------

    def _exec_body(self, body, envs) -> object:
        for st in body:
            r = self._exec_stmt(st, envs)
            if r is not _UNSET:
                return r
        return _UNSET

    def _exec_stmt(self, st, envs) -> object:
        if isinstance(st, ast.Assign):
            val = self._eval(st.value, envs)
            for tgt in st.targets:
                self._assign(tgt, val, envs)
            return _UNSET
        if isinstance(st, ast.AnnAssign):
            if st.value is not None:
                self._assign(st.target, self._eval(st.value, envs), envs)
            return _UNSET
        if isinstance(st, ast.AugAssign):
            cur = self._eval(ast.BinOp(
                left=_load_of(st.target), op=st.op, right=st.value,
                lineno=st.lineno, col_offset=st.col_offset), envs)
            self._assign(st.target, cur, envs)
            return _UNSET
        if isinstance(st, ast.Expr):
            self._eval(st.value, envs)
            return _UNSET
        if isinstance(st, ast.FunctionDef):
            when = self._when_cond(st, envs)
            if when is None:
                envs[-1][st.name] = _Closure(st, list(envs))
            elif when:
                self._exec_body(st.body, envs + [{}])
            return _UNSET
        if isinstance(st, ast.Return):
            return (self._eval(st.value, envs)
                    if st.value is not None else None)
        if isinstance(st, (ast.Import, ast.ImportFrom)):
            for al in st.names:
                envs[-1][al.asname or al.name.split(".")[0]] = \
                    _Data()
            return _UNSET
        if isinstance(st, ast.If):
            cond = self._eval(st.test, envs)
            if isinstance(cond, _Data):
                raise ExtractError(
                    "python `if` on a traced value in a protocol "
                    "kernel", st.lineno)
            if cond:
                return self._exec_body(st.body, envs)
            return self._exec_body(st.orelse, envs)
        if isinstance(st, ast.Pass):
            return _UNSET
        raise ExtractError(
            f"unmodelable statement {type(st).__name__}", st.lineno)

    def _when_cond(self, st: ast.FunctionDef, envs) -> Optional[bool]:
        """``@pl.when(cond)`` decorator -> bool; None if not a when-def."""
        if len(st.decorator_list) != 1:
            if st.decorator_list:
                raise ExtractError(
                    "unmodelable kernel decorator", st.lineno)
            return None
        dec = st.decorator_list[0]
        if isinstance(dec, ast.Call) and (
                self.project.resolve_dotted(self.mod, dec.func)
                == f"{PL}.when"):
            cond = self._eval(dec.args[0], envs)
            if isinstance(cond, _Data):
                raise ExtractError(
                    "pl.when predicate depends on traced data "
                    "(unmodelable)", dec.lineno)
            return bool(cond)
        raise ExtractError("unmodelable kernel decorator", st.lineno)

    def _assign(self, tgt, val, envs) -> None:
        if isinstance(tgt, ast.Name):
            envs[-1][tgt.id] = val
            return
        if isinstance(tgt, ast.Tuple) and isinstance(val, tuple) \
                and len(tgt.elts) == len(val):
            for el, v in zip(tgt.elts, val):
                self._assign(el, v, envs)
            return
        if isinstance(tgt, ast.Subscript):
            obj = self._eval(tgt.value, envs)
            if isinstance(obj, _Ref):
                self.events.append(Event(
                    "write", tgt.lineno,
                    ref=SlotRef(obj.name, self._slot(tgt.slice, envs))))
                return
        raise ExtractError(
            f"unmodelable assignment target {type(tgt).__name__}",
            tgt.lineno)

    # -- expressions ------------------------------------------------------

    def _slot(self, node, envs) -> Optional[int]:
        if isinstance(node, ast.Constant) and node.value is Ellipsis:
            return None
        if isinstance(node, ast.Slice):
            return None
        v = self._eval(node, envs)
        if isinstance(v, bool):
            v = int(v)
        if isinstance(v, int):
            return v
        raise ExtractError("slot index is not statically evaluable",
                           getattr(node, "lineno", 0))

    def _eval(self, node, envs):
        if isinstance(node, ast.Constant):
            return node.value
        if isinstance(node, ast.Name):
            for env in reversed(envs):
                if node.id in env:
                    return env[node.id]
            const = self._module_const(node.id)
            if const is not _UNSET:
                return const
            raise ExtractError(f"unresolvable name {node.id!r}",
                              node.lineno)
        if isinstance(node, ast.Tuple):
            return tuple(self._eval(el, envs) for el in node.elts)
        if isinstance(node, ast.List):
            return [self._eval(el, envs) for el in node.elts]
        if isinstance(node, ast.BinOp):
            return self._binop(node, envs)
        if isinstance(node, ast.UnaryOp):
            v = self._eval(node.operand, envs)
            if isinstance(v, _Data):
                return _Data(v.derived)
            if isinstance(node.op, ast.USub):
                return -v
            if isinstance(node.op, ast.Not):
                return not v
            return v
        if isinstance(node, ast.Compare):
            return self._compare(node, envs)
        if isinstance(node, ast.BoolOp):
            vals = [self._eval(v, envs) for v in node.values]
            if any(isinstance(v, _Data) for v in vals):
                return _Data()
            if isinstance(node.op, ast.And):
                out = True
                for v in vals:
                    out = out and v
                return out
            out = False
            for v in vals:
                out = out or v
            return out
        if isinstance(node, ast.IfExp):
            cond = self._eval(node.test, envs)
            if isinstance(cond, _Data):
                return _Data(self._free_ints(node, envs))
            return self._eval(node.body if cond else node.orelse, envs)
        if isinstance(node, ast.Call):
            return self._call(node, envs)
        if isinstance(node, ast.Attribute):
            return self._attribute(node, envs)
        if isinstance(node, ast.Subscript):
            return self._subscript(node, envs)
        if isinstance(node, (ast.GeneratorExp, ast.ListComp,
                             ast.SetComp)):
            return self._comprehension(node, envs)
        if isinstance(node, ast.JoinedStr):
            return _Data()
        raise ExtractError(
            f"unmodelable expression {type(node).__name__}",
            getattr(node, "lineno", 0))

    def _module_const(self, name: str):
        """Module-level literal constant (``_SOME_ID = 7``)."""
        tree = self.mod.tree
        if tree is None:
            return _UNSET
        for st in tree.body:
            if isinstance(st, ast.Assign):
                for tgt in st.targets:
                    if isinstance(tgt, ast.Name) and tgt.id == name:
                        try:
                            return ast.literal_eval(st.value)
                        except (ValueError, SyntaxError):
                            return _UNSET
        return _UNSET

    def _binop(self, node, envs):
        a = self._eval(node.left, envs)
        b = self._eval(node.right, envs)
        if isinstance(a, _Data) or isinstance(b, _Data):
            der = frozenset()
            for v in (a, b):
                if isinstance(v, _Data):
                    der |= v.derived
            return _Data(der)
        try:
            if isinstance(node.op, ast.Add):
                return a + b
            if isinstance(node.op, ast.Sub):
                return a - b
            if isinstance(node.op, ast.Mult):
                return a * b
            if isinstance(node.op, ast.Mod):
                return a % b
            if isinstance(node.op, ast.FloorDiv):
                return a // b
            if isinstance(node.op, ast.Div):
                return a / b
            if isinstance(node.op, ast.Pow):
                return a ** b
        except Exception as e:
            raise ExtractError(f"arithmetic failed: {e}", node.lineno)
        raise ExtractError(
            f"unmodelable operator {type(node.op).__name__}",
            node.lineno)

    def _compare(self, node, envs):
        left = self._eval(node.left, envs)
        out = True
        for op, rhs in zip(node.ops, node.comparators):
            right = self._eval(rhs, envs)
            if isinstance(left, (_Data, _Axis)) or \
                    isinstance(right, (_Data, _Axis)):
                return _Data()
            if isinstance(op, ast.Eq):
                ok = left == right
            elif isinstance(op, ast.NotEq):
                ok = left != right
            elif isinstance(op, ast.Lt):
                ok = left < right
            elif isinstance(op, ast.LtE):
                ok = left <= right
            elif isinstance(op, ast.Gt):
                ok = left > right
            elif isinstance(op, ast.GtE):
                ok = left >= right
            elif isinstance(op, ast.Is):
                ok = left is right
            elif isinstance(op, ast.IsNot):
                ok = left is not right
            else:
                raise ExtractError("unmodelable comparison", node.lineno)
            out = out and ok
            left = right
        return out

    def _free_ints(self, node, envs) -> frozenset:
        """Concrete ints bound to names referenced under ``node`` — the
        provenance that survives abstraction (``dev(i)``'s ``i``)."""
        out = set()
        for sub in ast.walk(node):
            if isinstance(sub, ast.Name):
                for env in reversed(envs):
                    if sub.id in env:
                        v = env[sub.id]
                        if isinstance(v, int) and not isinstance(v, bool):
                            out.add(v)
                        break
        return frozenset(out)

    def _comprehension(self, node, envs):
        gen = node.generators[0]
        it = self._eval(gen.iter, envs)
        if isinstance(it, _Data) or not isinstance(
                it, (list, tuple, range)):
            # abstract iteration: keep the provenance of any concrete
            # ints the element expression closes over
            return _Data(self._free_ints(node, envs))
        out = []
        for item in it:
            child = dict()
            self._assign(gen.target, item, envs + [child])
            keep = True
            for cond in gen.ifs:
                c = self._eval(cond, envs + [child])
                if isinstance(c, _Data):
                    raise ExtractError(
                        "comprehension filter on traced data",
                        node.lineno)
                keep = keep and bool(c)
            if keep:
                out.append(self._eval(node.elt, envs + [child]))
        return out

    def _subscript(self, node, envs):
        obj = self._eval(node.value, envs)
        if isinstance(obj, _Ref):
            slot = self._slot(node.slice, envs)
            self.events.append(Event(
                "read", node.lineno, ref=SlotRef(obj.name, slot)))
            return _Data()
        if isinstance(obj, _RefAt):
            return SlotRef(obj.ref.name, self._slot(node.slice, envs))
        if isinstance(obj, (list, tuple, range)):
            idx = self._eval(node.slice, envs)
            if isinstance(idx, int):
                return obj[idx]
        if isinstance(obj, _Data):
            return _Data(obj.derived)
        raise ExtractError("unmodelable subscript", node.lineno)

    def _attribute(self, node, envs):
        # dotted module names first (jnp.float32, pltpu.X, ...)
        dotted = self.project.resolve_dotted(self.mod, node)
        if dotted is not None and not dotted.startswith(("self.",
                                                         "cls.")):
            return _Data()
        obj = self._eval(node.value, envs)
        if isinstance(obj, _Ref):
            if node.attr == "at":
                return _RefAt(obj)
            if node.attr in ("ndim", "shape", "dtype", "size"):
                return _Data()
            raise ExtractError(
                f"unmodelable ref attribute .{node.attr}", node.lineno)
        if isinstance(obj, Desc):
            if node.attr in ("start", "wait", "wait_send", "wait_recv"):
                return _Method(obj, node.attr)
            raise ExtractError(
                f"unmodelable descriptor attribute .{node.attr}",
                node.lineno)
        if isinstance(obj, _Data):
            return _Data(obj.derived)
        raise ExtractError(f"unmodelable attribute .{node.attr}",
                          node.lineno)

    # -- calls ------------------------------------------------------------

    def _call(self, node: ast.Call, envs):
        dotted = self.project.resolve_dotted(self.mod, node.func)
        if dotted is not None:
            handler = self._DOTTED.get(dotted)
            if handler is not None:
                return handler(self, node, envs)
            if dotted.startswith(("jax.numpy.", "jax.nn.", "numpy.",
                                  "jax.lax.", "jax.random.")):
                # generic traced math: evaluate args for their read
                # events, return opaque data
                self._eval_args(node, envs)
                return _Data()
            # project-module helper called through an alias
            head, _, fname = dotted.rpartition(".")
            target = self.project.functions.get((head, (fname,)))
            if target is not None:
                return self._call_value(_Closure(target.node, [{}]),
                                        node, envs)
            raise ExtractError(f"unmodelable call to {dotted}",
                              node.lineno)
        if isinstance(node.func, ast.Name):
            name = node.func.id
            fn = None
            for env in reversed(envs):
                if name in env:
                    fn = env[name]
                    break
            if fn is None:
                if name in self._BUILTINS:
                    args, _ = self._eval_args(node, envs)
                    return self._builtin(name, args, node.lineno)
                target = self.project.lookup_function(
                    self.mod, self.info.scope, name)
                if target is not None:
                    fn = _Closure(target.node, [{}])
            if fn is None:
                raise ExtractError(f"unmodelable call to {name!r}",
                                  node.lineno)
            return self._call_value(fn, node, envs)
        fnval = self._eval(node.func, envs)
        return self._call_value(fnval, node, envs)

    _BUILTINS = frozenset({"tuple", "list", "range", "len", "min",
                           "max", "int", "abs", "sum", "sorted",
                           "float", "bool"})

    def _builtin(self, name, args, line):
        if any(isinstance(a, _Data) for a in args):
            der = frozenset()
            for a in args:
                if isinstance(a, _Data):
                    der |= a.derived
            return _Data(der)
        try:
            return {"tuple": tuple, "list": list, "range": range,
                    "len": len, "min": min, "max": max, "int": int,
                    "abs": abs, "sum": sum, "sorted": sorted,
                    "float": float, "bool": bool}[name](*args)
        except Exception as e:
            raise ExtractError(f"builtin {name} failed: {e}", line)

    def _eval_args(self, node, envs):
        args = [self._eval(a, envs) for a in node.args]
        kwargs = {k.arg: self._eval(k.value, envs)
                  for k in node.keywords if k.arg}
        return args, kwargs

    def _call_value(self, fn, node, envs):
        args, kwargs = self._eval_args(node, envs)
        if isinstance(fn, _Closure):
            return self._invoke(fn, args, kwargs, node)
        if isinstance(fn, _Method):
            return self._dma_method(fn, node)
        raise ExtractError("unmodelable callable", node.lineno)

    def _invoke(self, clo: _Closure, args, kwargs, node):
        fnode = clo.node
        a = fnode.args
        local: Dict[str, object] = {}
        params = [p.arg for p in a.posonlyargs + a.args]
        for name, val in zip(params, args):
            local[name] = val
        if len(args) > len(params):
            raise ExtractError("too many call args", node.lineno)
        defaults = a.defaults
        if defaults:
            for p, d in zip(params[-len(defaults):], defaults):
                if p not in local:
                    local[p] = self._eval(d, clo.env + [local])
        for p, d in zip(a.kwonlyargs, a.kw_defaults):
            if p.arg in kwargs:
                local[p.arg] = kwargs[p.arg]
            elif d is not None:
                local[p.arg] = self._eval(d, clo.env + [local])
        for k, v in kwargs.items():
            if k in params:
                local[k] = v
        missing = [p for p in params if p not in local]
        if missing:
            raise ExtractError(
                f"call leaves parameters unbound: {missing}",
                node.lineno)
        r = self._exec_body(fnode.body, clo.env + [local])
        return None if r is _UNSET else r

    def _dma_method(self, m: _Method, node):
        d = m.desc
        if m.op == "start":
            self.events.append(Event("dma", node.lineno, desc=d))
        elif m.op == "wait_send":
            self.events.append(Event("wait", node.lineno,
                                     ref=d.send_sem, count=1))
        elif m.op == "wait_recv":
            self.events.append(Event("wait", node.lineno,
                                     ref=d.recv_sem, count=1))
        elif m.op == "wait":
            self.events.append(Event("wait", node.lineno,
                                     ref=d.send_sem, count=1))
            self.events.append(Event("wait", node.lineno,
                                     ref=d.recv_sem, count=1))
        return None

    def _ring_offset(self, val, line) -> int:
        """device_id value -> signed ring offset (interpreter runs as
        device 0)."""
        cands = set()
        if isinstance(val, int) and not isinstance(val, bool):
            cands = {val}
        elif isinstance(val, _Data):
            cands = set(val.derived)
        elif isinstance(val, tuple):
            for v in val:
                if isinstance(v, int) and not isinstance(v, bool) \
                        and v != 0:
                    cands.add(v)
                elif isinstance(v, _Data):
                    cands |= {x for x in v.derived if x != 0}
        cands = {c % self.n for c in cands if 0 <= c % self.n}
        cands.discard(0)
        if not cands:
            return 0
        if len(cands) > 1:
            raise ExtractError(
                f"ambiguous device_id (candidates {sorted(cands)})",
                line)
        v = cands.pop()
        return v if v <= self.n // 2 else v - self.n

    def _slotref(self, val, line) -> SlotRef:
        if isinstance(val, SlotRef):
            return val
        if isinstance(val, _Ref):
            return SlotRef(val.name, None)
        raise ExtractError("expected a ref or ref.at[slot]", line)

    # dotted-name handlers -------------------------------------------------

    def _h_program_id(self, node, envs):
        return self.t

    def _h_num_programs(self, node, envs):
        return self.n

    def _h_axis_index(self, node, envs):
        return 0

    def _h_axis_size(self, node, envs):
        return self.n

    def _h_rem(self, node, envs):
        a = self._eval(node.args[0], envs)
        b = self._eval(node.args[1], envs)
        if isinstance(a, _Data) or isinstance(b, _Data):
            return _Data()
        # non-negative operands in the modelable fragment: % == rem
        return a % b

    def _h_when(self, node, envs):
        raise ExtractError(
            "pl.when(...) used outside a decorator (unmodelable)",
            node.lineno)

    def _h_barrier(self, node, envs):
        return self._barrier

    def _h_signal(self, node, envs):
        args, kwargs = self._eval_args(node, envs)
        sem = self._slotref(args[0], node.lineno)
        inc = kwargs.get("inc", args[1] if len(args) > 1 else 1)
        if not isinstance(inc, int):
            raise ExtractError("non-static semaphore inc", node.lineno)
        off = self._ring_offset(kwargs.get("device_id", 0), node.lineno)
        self.events.append(Event("signal", node.lineno, ref=sem,
                                 count=inc, off=off))
        return None

    def _h_sem_wait(self, node, envs):
        args, _ = self._eval_args(node, envs)
        sem = self._slotref(args[0], node.lineno)
        count = args[1] if len(args) > 1 else 1
        if not isinstance(count, int):
            raise ExtractError("non-static semaphore count",
                              node.lineno)
        self.events.append(Event("wait", node.lineno, ref=sem,
                                 count=count))
        return None

    def _h_remote_copy(self, node, envs):
        args, kwargs = self._eval_args(node, envs)
        if len(args) < 4:
            raise ExtractError(
                "make_async_remote_copy needs (src, dst, send_sem, "
                "recv_sem)", node.lineno)
        off = self._ring_offset(kwargs.get("device_id", 0), node.lineno)
        return Desc(src=self._slotref(args[0], node.lineno),
                    dst=self._slotref(args[1], node.lineno),
                    send_sem=self._slotref(args[2], node.lineno),
                    recv_sem=self._slotref(args[3], node.lineno),
                    off=off, line=node.lineno)

    def _h_local_copy(self, node, envs):
        # local async copy: same descriptor, no ring hop
        args, _ = self._eval_args(node, envs)
        if len(args) < 3:
            raise ExtractError(
                "make_async_copy needs (src, dst, sem)", node.lineno)
        sem = self._slotref(args[2], node.lineno)
        return Desc(src=self._slotref(args[0], node.lineno),
                    dst=self._slotref(args[1], node.lineno),
                    send_sem=sem, recv_sem=sem, off=0,
                    line=node.lineno)

    _DOTTED = {
        f"{PL}.program_id": _h_program_id,
        f"{PL}.num_programs": _h_num_programs,
        f"{PL}.when": _h_when,
        "jax.lax.axis_index": _h_axis_index,
        "jax.lax.axis_size": _h_axis_size,
        "jax.lax.rem": _h_rem,
        f"{PLTPU}.get_barrier_semaphore": _h_barrier,
        f"{PLTPU}.semaphore_signal": _h_signal,
        f"{PLTPU}.semaphore_wait": _h_sem_wait,
        f"{PLTPU}.make_async_remote_copy": _h_remote_copy,
        f"{PLTPU}.make_async_copy": _h_local_copy,
    }


def _load_of(node):
    new = ast.copy_location(ast.Subscript(
        value=node.value, slice=node.slice, ctx=ast.Load()), node) \
        if isinstance(node, ast.Subscript) else ast.copy_location(
            ast.Name(id=node.id, ctx=ast.Load()), node)
    return new


def extract_schedule(project: Project, mod: ModuleSource,
                     info: FunctionInfo, n: int) -> List[List[Event]]:
    """Per-grid-step event schedules for ring size ``n``: the protocol
    kernels in this repo walk the ring with a grid of exactly ``n``
    steps, which is also the modelable-fragment contract."""
    return [ScheduleExtractor(project, mod, info, n, t).run()
            for t in range(n)]
