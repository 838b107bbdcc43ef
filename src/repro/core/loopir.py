"""LoopIR: a small loop-forest IR for irregular streaming programs.

This is the input language of the dynamic-loop-fusion compiler (the
paper's benchmarks in §7.2 are all expressible in it). Design mirrors
what the paper's passes see in LLVM IR:

  * a *forest* of loop nests executed in program (topological) order,
  * explicit induction variables (``IVar``) whose add/mul updates are
    exactly what SCEV turns into chains of recurrences,
  * memory operations (``Load``/``Store``) against named arrays; arrays
    read through ``Read`` expressions are *unprotected* read-only data
    (index arrays such as CSR ``row_ptr`` — the paper protects one base
    pointer per DU, read-only inputs need no protection),
  * optional ``guard`` predicates on stores (the §6 control-flow /
    speculation case),
  * user monotonicity assertions for data-dependent addresses (§3.3).

The module also provides the **sequential oracle**: a reference
interpreter whose final memory state defines correctness for every
executor (cycle simulator, fused JAX executor, Pallas kernels).
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Optional, Sequence, Union

import numpy as np

from repro.core import cr as crlib

# ---------------------------------------------------------------------------
# Expressions
# ---------------------------------------------------------------------------


class Expr:
    def __add__(self, o):
        return Bin("+", self, wrap(o))

    def __radd__(self, o):
        return Bin("+", wrap(o), self)

    def __sub__(self, o):
        return Bin("-", self, wrap(o))

    def __rsub__(self, o):
        return Bin("-", wrap(o), self)

    def __mul__(self, o):
        return Bin("*", self, wrap(o))

    def __rmul__(self, o):
        return Bin("*", wrap(o), self)

    def __truediv__(self, o):
        return Bin("/", self, wrap(o))

    def __rtruediv__(self, o):
        return Bin("/", wrap(o), self)

    def __floordiv__(self, o):
        return Bin("//", self, wrap(o))

    def __mod__(self, o):
        return Bin("%", self, wrap(o))

    def __lt__(self, o):
        return Bin("<", self, wrap(o))

    def __le__(self, o):
        return Bin("<=", self, wrap(o))

    def __gt__(self, o):
        return Bin(">", self, wrap(o))

    def __ge__(self, o):
        return Bin(">=", self, wrap(o))

    def eq(self, o):
        return Bin("==", self, wrap(o))

    def ne(self, o):
        return Bin("!=", self, wrap(o))


@dataclasses.dataclass(frozen=True)
class Const(Expr):
    v: float


@dataclasses.dataclass(frozen=True)
class Param(Expr):
    """Runtime scalar parameter, with a conservative range for analysis."""

    name: str
    lo: int = 0
    hi: int = crlib.INF


@dataclasses.dataclass(frozen=True)
class Var(Expr):
    """Induction variable of an enclosing loop (the canonical 0,1,2,...
    counter) or a declared auxiliary IVar."""

    name: str


@dataclasses.dataclass(frozen=True)
class Local(Expr):
    """A loop-carried scalar local (defined by SetLocal)."""

    name: str


@dataclasses.dataclass(frozen=True)
class Read(Expr):
    """Read-only (unprotected) array read, e.g. CSR row_ptr/col_idx."""

    array: str
    index: Expr
    # optional user range assertion for the values read (helps analysis)
    lo: int = -crlib.INF
    hi: int = crlib.INF


@dataclasses.dataclass(frozen=True)
class LoadVal(Expr):
    """Value of the protected Load statement with the given id, in the
    current iteration."""

    load_id: str


@dataclasses.dataclass(frozen=True)
class Bin(Expr):
    op: str
    a: Expr
    b: Expr


@dataclasses.dataclass(frozen=True)
class Un(Expr):
    op: str  # tanh | relu | neg | abs | sign | exp
    a: Expr


def wrap(v: Union[int, float, Expr]) -> Expr:
    return v if isinstance(v, Expr) else Const(v)


_UN_FNS: dict[str, Callable] = {
    "tanh": np.tanh,
    "relu": lambda x: np.maximum(x, 0),
    "neg": lambda x: -x,
    "abs": np.abs,
    "sign": np.sign,
    "exp": np.exp,
}

# public alias: the numpy ufuncs above are already elementwise, so the
# oracle's scalar table IS the vectorized table (core/optable's
# closures use it directly — one source, nothing to keep in sync)
NP_UN_FNS: dict[str, Callable] = _UN_FNS


# vectorized counterparts of _binop, used by the affine trace compiler
# (core/affine.py); numpy's //, % match Python's semantics on ints and
# floats, / is IEEE float64 true division as _fdiv's, min/max become
# elementwise minimum/maximum. Keep the two tables in sync: every op
# here must behave elementwise exactly like _binop does on scalars.
NP_BINOPS: dict[str, Callable] = {
    "+": np.add,
    "-": np.subtract,
    "*": np.multiply,
    "/": np.true_divide,
    "//": np.floor_divide,
    "%": np.mod,
    "min": np.minimum,
    "max": np.maximum,
    "<": np.less,
    "<=": np.less_equal,
    ">": np.greater,
    ">=": np.greater_equal,
    "==": np.equal,
    "!=": np.not_equal,
}


def _fdiv(a, b):
    """IEEE float64 true division: a zero divisor gives +-inf or nan, as
    numpy's does, where Python's ``/`` raises."""
    if b == 0:
        with np.errstate(divide="ignore", invalid="ignore"):
            return float(np.float64(a) / np.float64(b))
    return a / b


def _binop(op: str, a, b):
    if op == "+":
        return a + b
    if op == "-":
        return a - b
    if op == "*":
        return a * b
    if op == "/":
        return _fdiv(a, b)
    if op == "//":
        return a // b
    if op == "%":
        return a % b
    if op == "min":
        return min(a, b)
    if op == "max":
        return max(a, b)
    if op == "<":
        return a < b
    if op == "<=":
        return a <= b
    if op == ">":
        return a > b
    if op == ">=":
        return a >= b
    if op == "==":
        return a == b
    if op == "!=":
        return a != b
    raise ValueError(f"unknown binop {op}")


# ---------------------------------------------------------------------------
# Statements
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class MonotonicHint:
    """User assertion (§3.3): the address is monotonically non-decreasing
    in the innermost loop. ``non_monotonic_outer`` lists 1-indexed outer
    depths that reset the address (None = assume *all* outer depths are
    non-monotonic — maximally conservative)."""

    innermost_monotonic: bool = True
    non_monotonic_outer: Optional[frozenset[int]] = None


@dataclasses.dataclass(frozen=True)
class Load:
    id: str
    array: str
    addr: Expr
    hint: Optional[MonotonicHint] = None

    @property
    def is_store(self) -> bool:
        return False


@dataclasses.dataclass(frozen=True)
class Store:
    id: str
    array: str
    addr: Expr
    value: Expr
    guard: Optional[Expr] = None  # §6: store under an if-condition
    hint: Optional[MonotonicHint] = None

    @property
    def is_store(self) -> bool:
        return True


@dataclasses.dataclass(frozen=True)
class IVar:
    """Auxiliary induction variable of a loop: ``name = init`` before the
    loop, ``name = name (op) step`` at the end of each iteration. This is
    the source-level origin of non-affine CRs, e.g. FFT's stride *= 2
    gives the paper's {2, ×, 2} recurrence."""

    name: str
    init: Expr
    op: str  # '+' or '*'
    step: Expr


@dataclasses.dataclass(frozen=True)
class SetLocal:
    """Assign a loop-carried scalar local (reduction accumulators etc.)."""

    name: str
    value: Expr


@dataclasses.dataclass(frozen=True)
class Loop:
    var: str
    trip: Expr
    body: tuple  # of Load | Store | SetLocal | Loop
    ivars: tuple[IVar, ...] = ()
    # False models loops whose exit predicate cannot be computed one
    # iteration in advance (paper §4.2(3): lastIter hint degrades to 0).
    predictable: bool = True

    def __post_init__(self):
        object.__setattr__(self, "body", tuple(self.body))
        object.__setattr__(self, "ivars", tuple(self.ivars))


Stmt = Union[Load, Store, SetLocal, Loop]


@dataclasses.dataclass(frozen=True)
class Program:
    name: str
    loops: tuple[Loop, ...]  # the forest, in program order
    # arrays written/read via protected Load/Store and Read
    params: tuple[str, ...] = ()

    def __post_init__(self):
        object.__setattr__(self, "loops", tuple(self.loops))
        object.__setattr__(self, "params", tuple(self.params))

    # -- structural helpers -------------------------------------------------

    def mem_ops(self) -> list[tuple[Union[Load, Store], tuple[Loop, ...]]]:
        """All memory ops in topological (program) order, each with its
        enclosing loop path (outermost first)."""
        out = []

        def walk(stmts, path):
            for s in stmts:
                if isinstance(s, Loop):
                    walk(s.body, path + (s,))
                elif isinstance(s, (Load, Store)):
                    out.append((s, path))

        walk(self.loops, ())
        return out

    def op_index(self) -> dict[str, int]:
        """Topological order index for each memory op id."""
        return {op.id: i for i, (op, _) in enumerate(self.mem_ops())}

    def find_op(self, op_id: str) -> tuple[Union[Load, Store], tuple[Loop, ...]]:
        for op, path in self.mem_ops():
            if op.id == op_id:
                return op, path
        raise KeyError(op_id)

    def fingerprint(self) -> str:
        """Stable structural hash of the program (hex sha256).

        Canonical recursive encoding of the IR forest — statement kinds,
        op ids, expression trees, trips, ivars, guards, hints — so two
        structurally identical programs hash equal across processes and
        sessions (``repr``/``hash`` of nested dataclasses are not stable
        enough to key an on-disk cache). Array *contents* and parameter
        *values* are deliberately excluded: the DSE result cache
        (``repro.dse.cache``) hashes those separately. Computed once per
        program object (the program is immutable).
        """
        cached = self.__dict__.get("_fingerprint")
        if cached is not None:
            return cached
        import hashlib

        h = hashlib.sha256()

        def put(x):
            h.update(repr(x).encode())
            h.update(b"\x00")

        def enc(node):
            if node is None or isinstance(node, (str, int, float, bool)):
                put(node)
            elif isinstance(node, frozenset):
                put("{")
                for x in sorted(node):
                    enc(x)
                put("}")
            elif isinstance(node, (tuple, list)):
                put("(")
                for x in node:
                    enc(x)
                put(")")
            elif dataclasses.is_dataclass(node):
                put(type(node).__name__)
                for f in dataclasses.fields(node):
                    enc(getattr(node, f.name))
            else:  # pragma: no cover
                raise TypeError(f"cannot fingerprint {node!r}")

        enc(self)
        object.__setattr__(self, "_fingerprint", h.hexdigest())
        return self._fingerprint

    def static_positions(self) -> tuple[dict[int, int], dict[str, int]]:
        """(loop object id -> index in parent body, op id -> index in its
        body). Together with per-depth counters these give a global
        lexicographic program order — the polyhedral 2d+1 schedule."""
        loop_pos: dict[int, int] = {}
        op_pos: dict[str, int] = {}

        def walk(stmts):
            for idx, s in enumerate(stmts):
                if isinstance(s, Loop):
                    loop_pos[id(s)] = idx
                    walk(s.body)
                elif isinstance(s, (Load, Store)):
                    op_pos[s.id] = idx

        walk(self.loops)
        return loop_pos, op_pos


def set_locals(program: Program) -> tuple[SetLocal, ...]:
    """Every SetLocal of the program in program order, one entry per
    place it takes in the forest: the position of an entry is the number
    ``local_exprs`` and ``local_hook`` give that statement."""
    out: list = []

    def walk(stmts):
        for s in stmts:
            if isinstance(s, Loop):
                walk(s.body)
            elif isinstance(s, SetLocal):
                out.append(s)

    walk(program.loops)
    return tuple(out)


def _n_set_locals(s, memo: dict) -> int:
    """SetLocal places inside statement ``s`` (1 for a SetLocal)."""
    if isinstance(s, SetLocal):
        return 1
    if not isinstance(s, Loop):
        return 0
    n = memo.get(id(s))
    if n is None:
        n = memo[id(s)] = sum(_n_set_locals(t, memo) for t in s.body)
    return n


# ---------------------------------------------------------------------------
# Sequential oracle interpreter
# ---------------------------------------------------------------------------


class UnavailableLoadValue(KeyError):
    """A ``LoadVal`` consumed before its ``Load`` produced a value —
    e.g. a trip reading a load of the loop it bounds. Distinguished
    from other ``KeyError``s (typo'd arrays/params) so the speculative
    AGU (``core/speculate.py``) converts only genuine
    use-before-availability into its auto-reject diagnostic."""


class _Env:
    """Chained mutable scopes for loop vars / ivars / locals."""

    def __init__(self, parent: Optional["_Env"] = None):
        self.parent = parent
        self.vals: dict[str, float] = {}

    def get(self, name: str):
        e = self
        while e is not None:
            if name in e.vals:
                return e.vals[name]
            e = e.parent
        raise KeyError(name)

    def set_existing(self, name: str, v) -> bool:
        e = self
        while e is not None:
            if name in e.vals:
                e.vals[name] = v
                return True
            e = e.parent
        return False

    def define(self, name: str, v):
        self.vals[name] = v


def _eval(e: Expr, env: _Env, arrays, params, loadvals) -> float:
    if isinstance(e, Const):
        return e.v
    if isinstance(e, Param):
        return params[e.name]
    if isinstance(e, (Var, Local)):
        return env.get(e.name)
    if isinstance(e, Read):
        idx = int(_eval(e.index, env, arrays, params, loadvals))
        return arrays[e.array][idx]
    if isinstance(e, LoadVal):
        try:
            return loadvals[e.load_id]
        except KeyError:
            raise UnavailableLoadValue(e.load_id) from None
    if isinstance(e, Bin):
        return _binop(
            e.op,
            _eval(e.a, env, arrays, params, loadvals),
            _eval(e.b, env, arrays, params, loadvals),
        )
    if isinstance(e, Un):
        return _UN_FNS[e.op](_eval(e.a, env, arrays, params, loadvals))
    raise TypeError(f"cannot eval {e!r}")


def interpret(
    program: Program,
    arrays: dict[str, np.ndarray],
    params: Optional[dict[str, int]] = None,
    trace_hook: Optional[Callable] = None,
    aux_exprs: Optional[dict[str, tuple]] = None,
    aux_hook: Optional[Callable] = None,
    loop_hook: Optional[Callable] = None,
    local_exprs: Optional[dict[int, tuple]] = None,
    local_hook: Optional[Callable] = None,
) -> dict[str, np.ndarray]:
    """Run the program sequentially; returns the final array state.

    This is THE semantics. Every executor must reproduce it bit-for-bit
    (modulo float associativity, which we avoid by executing in the same
    per-element order).

    ``trace_hook(op_id, addr, is_store, valid, value)`` is called for
    every memory operation *in program order*, including mis-speculated
    stores (guard false -> valid=False, value=None) — the request exists
    in the decoupled machine even when the effect doesn't (§6).

    ``aux_exprs`` maps an op id to a tuple of extra expressions; when
    that op fires, each is evaluated in the op's environment and the
    results are passed to ``aux_hook(op_id, values_tuple)`` *before* the
    trace hook — for guarded stores the aux values are produced even
    when the guard fails (the CU-side operand stream exists regardless
    of the §6 valid bit). This is how ``core/optable`` captures the
    environment slots of its partially-evaluated compute bodies without
    leaking memory (LoadVal) values out of the oracle.

    ``loop_hook(loop, phase, reader)`` is called at every loop
    *instance* boundary — ``phase="enter"`` before the instance's
    ivars/trip are evaluated, ``phase="exit"`` after its last iteration
    (a zero-trip instance fires both) — with ``reader(name)`` exposing
    the enclosing environment's locals. This is how the FIFO token
    protocol (``core/fifo.py``, DESIGN.md §11) observes the
    one-token-per-leaf-instance push/pop stream without re-deriving
    loop structure.

    ``local_exprs`` maps a SetLocal's number (its position in
    ``set_locals(program)``) to a tuple of extra expressions; each time
    that statement runs, they are evaluated in its environment before
    the assignment and the results passed to ``local_hook(number,
    values_tuple)``. This is how ``core/optable`` captures the operands
    of a register accumulator's fold (``compile_local_tables``).

    Load values are visible downstream of their ``Load`` within the
    enclosing body *and* inside nested loops of that body — including
    loop trip counts and ivar updates. Load-dependent trips (the §6
    speculation workloads, ``core/speculate.py``) are therefore plain
    programs to the oracle; only the decoupled machine needs the
    speculative AGU to run them.
    """
    params = params or {}
    arrays = {k: np.array(v, copy=True) for k, v in arrays.items()}

    def run_aux(op_id, env, loadvals, guard_ok=True):
        # guard-false rows (§6) still need an aux row for per-op
        # ordinal alignment, but the guard may be the very bounds check
        # that makes the value operands evaluable — evaluate those
        # defensively and emit NaN placeholders (the backend masks the
        # whole row by its recomputed valid bit)
        if aux_exprs is not None and op_id in aux_exprs:
            vals = []
            for e in aux_exprs[op_id]:
                if guard_ok:
                    vals.append(_eval(e, env, arrays, params, loadvals))
                else:
                    try:
                        vals.append(_eval(e, env, arrays, params, loadvals))
                    except Exception:
                        vals.append(np.nan)
            aux_hook(op_id, tuple(vals))

    n_sets: dict[int, int] = {}

    def run_body(stmts: Sequence[Stmt], env: _Env, outer_loadvals, at):
        # chained visibility: loads of enclosing iterations stay readable
        loadvals: dict[str, float] = dict(outer_loadvals)
        # ``at``: the number of the next SetLocal place in program order
        for s in stmts:
            if isinstance(s, Load):
                a = int(_eval(s.addr, env, arrays, params, loadvals))
                v = arrays[s.array][a]
                run_aux(s.id, env, loadvals)
                if trace_hook is not None:
                    trace_hook(s.id, a, False, True, float(v))
                loadvals[s.id] = v
            elif isinstance(s, Store):
                a = int(_eval(s.addr, env, arrays, params, loadvals))
                guard_ok = s.guard is None or _eval(
                    s.guard, env, arrays, params, loadvals
                )
                run_aux(s.id, env, loadvals, guard_ok=guard_ok)
                if not guard_ok:
                    if trace_hook is not None:
                        trace_hook(s.id, a, True, False, None)
                    continue
                v = _eval(s.value, env, arrays, params, loadvals)
                if trace_hook is not None:
                    trace_hook(s.id, a, True, True, float(v))
                arrays[s.array][a] = v
            elif isinstance(s, SetLocal):
                if local_exprs is not None and at in local_exprs:
                    local_hook(at, tuple(
                        _eval(e, env, arrays, params, loadvals)
                        for e in local_exprs[at]
                    ))
                at += 1
                v = _eval(s.value, env, arrays, params, loadvals)
                if not env.set_existing(s.name, v):
                    env.define(s.name, v)
            elif isinstance(s, Loop):
                run_loop(s, env, loadvals, at)
                at += _n_set_locals(s, n_sets)
            else:
                raise TypeError(f"unknown stmt {s!r}")

    def run_loop(loop: Loop, env: _Env, loadvals, at):
        if loop_hook is not None:
            loop_hook(loop, "enter", env.get)
        outer = _Env(env)
        for iv in loop.ivars:
            outer.define(iv.name, _eval(iv.init, env, arrays, params, loadvals))
        trip = int(_eval(loop.trip, env, arrays, params, loadvals))
        for i in range(trip):
            inner = _Env(outer)
            inner.define(loop.var, i)
            run_body(loop.body, inner, loadvals, at)
            for iv in loop.ivars:
                cur = outer.get(iv.name)
                step = _eval(iv.step, inner, arrays, params, loadvals)
                outer.vals[iv.name] = cur + step if iv.op == "+" else cur * step
        if loop_hook is not None:
            loop_hook(loop, "exit", env.get)
        return

    top = _Env()
    at = 0
    for lp in program.loops:
        run_loop(lp, top, {}, at)
        at += _n_set_locals(lp, n_sets)
    return arrays


# ---------------------------------------------------------------------------
# The oracle walk compiled to Python source
# ---------------------------------------------------------------------------

_CMP_OPS = frozenset(("<", "<=", ">", ">=", "==", "!="))
_ARITH_OPS = frozenset(("+", "-", "*", "/", "//", "%"))


class _Uncompilable(Exception):
    """The program needs ``interpret``'s run-time checks (see
    ``compile_walk``)."""


def _loops_in_order(program: Program) -> tuple:
    """Every Loop of the program, outermost first, in program order."""
    out: list = []

    def walk(stmts):
        for s in stmts:
            if isinstance(s, Loop):
                out.append(s)
                walk(s.body)

    walk(program.loops)
    return tuple(out)


class _WalkSource:
    """One pass of the code generator behind ``compile_walk``.

    Names resolve statically, scope by scope, exactly as ``_Env`` and the
    ``loadvals`` dicts resolve them at run time: each binding (a loop
    var, an ivar, a local defined in one scope, a Load's value) becomes
    one Python local. Alongside it tracks, per binding, whether its
    value has the same Python type under both walkers (``interpret``
    reads numpy scalars where this walker reads list items) and whether
    it may be a comparison's result; ``disagree`` and ``boolish`` are
    the bindings known so far not to agree or to be boolean, and a pass
    that learns more asks for another (``grown``).
    """

    def __init__(self, program, aux_exprs, with_loop_hook, disagree, boolish,
                 local_exprs):
        self.aux = aux_exprs
        self.local = local_exprs
        self.n_set = 0  # SetLocal places met so far, in program order
        self.hooked = with_loop_hook
        self.disagree = disagree
        self.boolish = boolish
        self.grown = False
        self.lines: list[str] = []
        self.consts: list = []
        self.arrays: dict[str, int] = {}
        self.params: dict[str, int] = {}
        self.stored: set[str] = set()
        self.n_bind = 0
        self.n_loop = 0
        self.uns: dict[str, str] = {}
        # loop vars no SetLocal assigns: Python ints, which index and
        # address without int()
        self.counters: set[int] = set()
        self.set_names = {
            s.name for lp in _loops_in_order(program) for s in lp.body
            if isinstance(s, SetLocal)
        }
        for lp in program.loops:
            if not isinstance(lp, Loop):
                raise _Uncompilable(f"top-level {lp!r} is not a Loop")
            self.loop(lp, [{}], {}, 1)

    # -- bindings and values ------------------------------------------------

    def bind(self) -> int:
        self.n_bind += 1
        return self.n_bind - 1

    def assign(self, b: int, agree: bool, boolish: bool):
        if not agree and b not in self.disagree:
            self.disagree.add(b)
            self.grown = True
        if boolish and b not in self.boolish:
            self.boolish.add(b)
            self.grown = True

    def value(self, b: int):
        return (f"b{b}", b not in self.disagree, b in self.boolish,
                b in self.counters)

    @staticmethod
    def resolve(scope, name):
        for frame in reversed(scope):
            if name in frame:
                return frame[name]
        raise _Uncompilable(f"'{name}' is not defined where it is read")

    def const(self, v) -> str:
        self.consts.append(v)
        return f"c{len(self.consts) - 1}"

    def array(self, name) -> str:
        return f"A{self.arrays.setdefault(name, len(self.arrays))}"

    @staticmethod
    def as_int(src, isint) -> str:
        return src if isint else f"int_({src})"

    # -- expressions ------------------------------------------------------------

    def expr(self, e, scope, loads):
        """(source, agrees, may be a comparison's result, is a Python
        int) of ``e``."""
        if isinstance(e, Const):
            return self.const(e.v), True, False, type(e.v) is int
        if isinstance(e, Param):
            j = self.params.setdefault(e.name, len(self.params))
            return f"p{j}", True, False, False
        if isinstance(e, (Var, Local)):
            return self.value(self.resolve(scope, e.name))
        if isinstance(e, LoadVal):
            if e.load_id not in loads:
                raise _Uncompilable(f"LoadVal('{e.load_id}') read before its Load")
            return self.value(loads[e.load_id])
        if isinstance(e, Read):
            idx = self.as_int(*self.expr(e.index, scope, loads)[::3])
            return f"{self.array(e.array)}[{idx}]", False, False, False
        if isinstance(e, Bin):
            a, aa, ba, ia = self.expr(e.a, scope, loads)
            b, ab, bb, ib = self.expr(e.b, scope, loads)
            if e.op in _CMP_OPS:
                return f"({a} {e.op} {b})", aa and ab, True, False
            if e.op in ("min", "max"):
                return f"{e.op}_({a}, {b})", aa and ab, ba or bb, ia and ib
            if e.op not in _ARITH_OPS:
                raise _Uncompilable(f"unknown binop {e.op}")
            self.arith(aa, ba, ab, bb)
            nonzero = isinstance(e.b, Const) and type(e.b.v) in (int, float) \
                and e.b.v
            if e.op == "/":
                # Python's / raises on a zero divisor: fdiv_ gives IEEE's
                if nonzero:
                    return f"({a} / {b})", aa and ab, False, False
                return f"fdiv_({a}, {b})", aa and ab, False, False
            if e.op in ("//", "%") and not (aa and ab) and not nonzero:
                # a zero divisor raises on Python numbers and gives inf
                # or nan on numpy scalars
                raise _Uncompilable(f"'{e.op}' by a value that may be 0")
            return f"({a} {e.op} {b})", aa and ab, False, ia and ib
        if isinstance(e, Un):
            a, aa, ba, ia = self.expr(e.a, scope, loads)
            if e.op not in _UN_FNS:
                raise _Uncompilable(f"unknown unop {e.op}")
            self.arith(aa, ba, True, False)
            if e.op == "neg":
                return f"(-{a})", aa, False, ia
            fn = self.uns.setdefault(e.op, f"u{len(self.uns)}")
            return f"{fn}({a})", True, False, False
        raise _Uncompilable(f"cannot eval {e!r}")

    @staticmethod
    def arith(aa, ba, ab, bb):
        # arithmetic on numpy booleans is logical, on Python's integral
        if (ba and not aa) or (bb and not ab):
            raise _Uncompilable("arithmetic on a comparison's result")

    # -- statements ------------------------------------------------------------

    def emit(self, depth, line):
        self.lines.append("    " * depth + line)

    def reader(self, scope) -> str:
        names = {}
        for frame in scope:
            names.update(frame)
        items = ", ".join(f"{n!r}: b{b}" for n, b in sorted(names.items()))
        return f"{{{items}}}.__getitem__"

    def loop(self, lp, scope, loads, d):
        k = self.n_loop
        self.n_loop += 1
        if self.hooked:
            self.emit(d, f"loop_hook(L{k}, 'enter', {self.reader(scope)})")
        ivars: dict[str, int] = {}
        for iv in lp.ivars:
            src, agree, boolish, _ = self.expr(iv.init, scope, loads)
            b = ivars.setdefault(iv.name, self.bind())
            self.assign(b, agree, boolish)
            self.emit(d, f"b{b} = {src}")
        trip = self.as_int(*self.expr(lp.trip, scope, loads)[::3])
        var = self.bind()
        if lp.var not in self.set_names:
            self.counters.add(var)
        inner = {lp.var: var}
        body_scope = scope + [ivars, inner]
        self.emit(d, f"for b{var} in range_({trip}):")
        n_lines = len(self.lines)
        self.body(lp.body, body_scope, dict(loads), d + 1)
        for iv in lp.ivars:
            b = ivars[iv.name]
            cur, ca, cb, _ = self.value(b)
            step, sa, sb, _ = self.expr(iv.step, body_scope, loads)
            self.arith(ca, cb, sa, sb)
            self.assign(b, ca and sa, False)
            op = "+" if iv.op == "+" else "*"
            self.emit(d + 1, f"{cur} = {cur} {op} {step}")
        if len(self.lines) == n_lines:
            self.emit(d + 1, "pass")
        if self.hooked:
            self.emit(d, f"loop_hook(L{k}, 'exit', {self.reader(scope)})")

    def run_aux(self, op_id, scope, loads, d, strict):
        exprs = self.aux.get(op_id)
        if not exprs:
            return
        srcs = [self.expr(e, scope, loads)[0] for e in exprs]
        if strict:
            self.emit(d, f"aux_hook({op_id!r}, ({''.join(s + ', ' for s in srcs)}))")
            return
        # guard-false rows: operands the guard protected become NaN
        for j, src in enumerate(srcs):
            self.emit(d, "try:")
            self.emit(d + 1, f"x{j} = {src}")
            self.emit(d, "except Exception:")
            self.emit(d + 1, f"x{j} = nan_")
        names = "".join(f"x{j}, " for j in range(len(srcs)))
        self.emit(d, f"aux_hook({op_id!r}, ({names}))")

    def body(self, stmts, scope, loads, d):
        inner = scope[-1]
        for s in stmts:
            if isinstance(s, Load):
                addr = self.as_int(*self.expr(s.addr, scope, loads)[::3])
                b = self.bind()
                self.emit(d, f"a_ = {addr}")
                self.emit(d, f"b{b} = {self.array(s.array)}[a_]")
                self.run_aux(s.id, scope, loads, d, strict=True)
                self.emit(d, f"trace_hook({s.id!r}, a_, False, True, float_(b{b}))")
                self.assign(b, False, False)
                loads[s.id] = b
            elif isinstance(s, Store):
                arr = self.array(s.array)
                self.stored.add(s.array)
                addr = self.as_int(*self.expr(s.addr, scope, loads)[::3])
                self.emit(d, f"a_ = {addr}")
                dv = d
                if s.guard is not None:
                    guard = self.expr(s.guard, scope, loads)[0]
                    self.emit(d, f"g_ = {guard}")
                    self.emit(d, "if g_:")
                    dv = d + 1
                self.run_aux(s.id, scope, loads, dv, strict=True)
                val = self.expr(s.value, scope, loads)[0]
                self.emit(dv, f"v_ = {val}")
                self.emit(dv, f"trace_hook({s.id!r}, a_, True, True, float_(v_))")
                self.emit(dv, f"{arr}[a_] = K(v_)")
                if s.guard is not None:
                    self.emit(d, "else:")
                    self.run_aux(s.id, scope, loads, d + 1, strict=False)
                    self.emit(d + 1, f"trace_hook({s.id!r}, a_, True, False, None)")
            elif isinstance(s, SetLocal):
                exprs = self.local.get(self.n_set)
                self.n_set += 1
                if exprs is not None:
                    srcs = "".join(
                        self.expr(e, scope, loads)[0] + ", " for e in exprs
                    )
                    self.emit(d, f"local_hook({self.n_set - 1}, ({srcs}))")
                src, agree, boolish, _ = self.expr(s.value, scope, loads)
                try:
                    b = self.resolve(scope, s.name)
                except _Uncompilable:
                    b = inner[s.name] = self.bind()
                self.assign(b, agree, boolish)
                self.emit(d, f"b{b} = {src}")
            elif isinstance(s, Loop):
                self.loop(s, scope, loads, d)
            else:
                raise _Uncompilable(f"unknown stmt {s!r}")

    def source(self) -> str:
        head = [
            "def walk(A, K, P, C, L, trace_hook, aux_hook, loop_hook,"
            " local_hook):",
            "    int_, float_, range_, min_, max_ = int, float, range, min, max",
            "    nan_, fdiv_ = _nan, _fdiv",
        ]
        head += [f"    A{j} = A[{j}]  # {n}" for n, j in self.arrays.items()]
        head += [f"    p{j} = P[{j}]  # {n}" for n, j in self.params.items()]
        head += [f"    c{j} = C[{j}]" for j in range(len(self.consts))]
        head += [f"    {fn} = _UN_FNS[{op!r}]" for op, fn in self.uns.items()]
        if self.hooked:
            head += [f"    L{j} = L[{j}]" for j in range(self.n_loop)]
        return "\n".join(head + self.lines + ["    return None", ""])


def _same(v):
    return v


# scalar types that compute alike beside a Python float or int and
# beside the numpy float64 or int64 it stands for (numpy takes Python
# scalars as weak: np.float32(x) * 0.5 stays float32)
_WIDE_SCALARS = (bool, int, float, np.bool_, np.int64, np.float64)


def compile_walk(
    program: Program,
    aux_exprs: Optional[dict[str, tuple]] = None,
    with_loop_hook: bool = False,
    local_exprs: Optional[dict[int, tuple]] = None,
) -> Optional[Callable]:
    """``interpret`` with hooks, compiled to one Python function.

    Returns ``walk(program, arrays, params, trace_hook=None,
    aux_hook=None, loop_hook=None, local_hook=None)``, which makes the
    same hook calls in the same order with the same values and returns
    the same final arrays as ``interpret(program, arrays, params,
    trace_hook, aux_exprs, aux_hook, loop_hook, local_exprs,
    local_hook)``; ``local_hook`` is called where ``local_exprs`` name a
    statement, and ``loop_hook`` only when
    compiled ``with_loop_hook``, and its ``reader`` answers at the time
    of the call. ``program`` may be any program of the same
    ``fingerprint()``: its Loop objects are the ones ``loop_hook``
    receives. ``walk`` returns None, and calls no hook, where an array
    or parameter the program names is missing.

    Loops become ``for`` loops and every binding a Python local. Where
    every array the program names is one-dimensional float64, or int64
    and never stored to, and every constant and parameter it reads is a
    Python scalar or a 64-bit numpy one, the arrays are walked as Python
    lists: their floats and ints compute as numpy's float64 and int64
    scalars do, and a store keeps ``float(value)`` as a float64 array
    would. Otherwise they stay numpy arrays and every value has the type
    ``interpret`` gives it.

    Returns None where Python cannot compile the source (loops nested
    about 20 deep) and where the program needs ``interpret``'s run-time
    behaviour: a name or load value read where it may be undefined
    (``interpret`` raises there, or puts NaN in a guard-false aux row),
    arithmetic on a comparison's result that reads an array (logical
    on numpy booleans, integral on Python's), or ``//`` and ``%`` by a
    value read from an array (a zero divisor raises on Python numbers).
    Not reproduced on lists: int64 arithmetic that overflows (numpy
    wraps) and int64 values beyond 2**53 compared with floats.
    """
    aux_exprs = aux_exprs or {}
    local_exprs = local_exprs or {}
    disagree: set[int] = set()
    boolish: set[int] = set()
    try:
        while True:
            gen = _WalkSource(
                program, aux_exprs, with_loop_hook, disagree, boolish,
                local_exprs,
            )
            if not gen.grown:
                break
        # Python refuses more than 20 nested blocks
        code = compile(gen.source(), f"<walk of {program.name}>", "exec")
    except (_Uncompilable, SyntaxError):
        return None
    ns = {"_nan": np.nan, "_fdiv": _fdiv, "_UN_FNS": _UN_FNS}
    exec(code, ns)
    fn = ns["walk"]
    consts = tuple(gen.consts)
    array_names = tuple(gen.arrays)
    param_names = tuple(gen.params)
    stored = [j for j, n in enumerate(array_names) if n in gen.stored]
    wide_consts = all(type(c) in _WIDE_SCALARS for c in consts)

    def listable(j, a):
        return a.ndim == 1 and (a.dtype == np.float64 or (
            a.dtype == np.int64 and j not in stored
        ))

    def walk(program, arrays, params, trace_hook=None, aux_hook=None,
             loop_hook=None, local_hook=None):
        if loop_hook is not None and not with_loop_hook:
            raise ValueError("walk compiled without with_loop_hook")
        params = params or {}
        if any(n not in arrays for n in array_names) or any(
            p not in params for p in param_names
        ):
            return None
        out = {k: np.array(v, copy=True) for k, v in arrays.items()}
        data = [out[n] for n in array_names]
        pvals = [params[p] for p in param_names]
        lists = wide_consts and all(
            listable(j, a) for j, a in enumerate(data)
        ) and all(type(v) in _WIDE_SCALARS for v in pvals)
        if lists:
            data = [a.tolist() for a in data]
        fn(
            data, float if lists else _same, pvals, consts,
            _loops_in_order(program) if with_loop_hook else (),
            trace_hook or (lambda *_: None), aux_hook, loop_hook, local_hook,
        )
        if lists:
            for j in stored:
                out[array_names[j]][:] = data[j]
        return out

    return walk
