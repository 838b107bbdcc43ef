"""Reusable per-op compute tables: store bodies factored out of the oracle.

The sequential oracle (``loopir.interpret``) evaluates every store's
value/guard expression scalar-by-scalar while walking the program. A
hardware backend (``kernels/wave_exec``) cannot call back into the
oracle — it must *compute* store values itself from the load values its
own gathers produced. This module compiles each store into exactly that
shape, mirroring the paper's decoupled access/execute split:

  * everything the CU/AGU side can produce without touching protected
    memory — loop variables, ivars, locals, reads of index arrays —
    is **partially evaluated away**: every maximal ``LoadVal``-free
    subtree of the value/guard expression becomes an *environment
    slot*, a per-request operand stream captured once during the trace
    walk (``loopir.interpret``'s ``aux_exprs`` hook),
  * everything downstream of a protected ``LoadVal`` stays symbolic: a
    small closed closure over (dep load streams, env slots, frozen
    read-only arrays) that the backend evaluates *vectorized per wave*,
    with numpy (bit-exact vs the oracle — same elementwise ops in the
    same order) or jax.numpy (``lib="jnp"``; accelerator dtype rules,
    checked to tolerance).

The closure node set is tiny (Const / DepRef / EnvRef / LocalRef /
Gather / Bin / Un) because the IR's expression language is;
``compile_store_tables`` rejects the one genuinely unsupported case — a
``Read`` whose index depends on a ``LoadVal`` *and* whose array is also
a store target (the closure would need a coherent snapshot
mid-execution; Table-1 and the speculative kernels only gather frozen
index/weight arrays this way).

A local that a ``SetLocal`` computes from load values, directly or
through another such local (``fed_locals``: the ``sum`` register of a
CSR row loop), is load-dependent as well: a store reading it compiles it
to a ``CLocal`` node, and every SetLocal place of such a local gets a
``LocalTable`` (``compile_local_tables``) — the same partial
evaluation, so a backend can fold the local's value from its own
gathers, statement by statement in program order.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Union

import numpy as np

from repro.core import loopir as ir


class OpTableError(Exception):
    """A store body the op-table compiler cannot factor (module doc)."""


# ---------------------------------------------------------------------------
# Closure IR (the residue after partial evaluation)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class CConst:
    v: float


@dataclasses.dataclass(frozen=True)
class CDep:
    """Value stream of a protected load (aligned via WavePlan dep maps)."""

    load_id: str


@dataclasses.dataclass(frozen=True)
class CEnv:
    """Captured environment slot (LoadVal-free subtree), by slot index."""

    slot: int


@dataclasses.dataclass(frozen=True)
class CGather:
    """Gather from a *frozen* read-only array at a load-dependent index."""

    array: str
    index: "CNode"


@dataclasses.dataclass(frozen=True)
class CBin:
    op: str
    a: "CNode"
    b: "CNode"


@dataclasses.dataclass(frozen=True)
class CUn:
    op: str
    a: "CNode"


@dataclasses.dataclass(frozen=True)
class CLocal:
    """Current value of a local fed by loads (``fed_locals``), folded from
    its SetLocal executions (aligned via ``WavePlan.local_maps``)."""

    name: str


CNode = Union[CConst, CDep, CEnv, CLocal, CGather, CBin, CUn]

# The numpy path reuses the oracle's own op tables (ir.NP_BINOPS /
# ir.NP_UN_FNS) — one source, bit-exactness by construction. The jnp
# counterparts below are the only duplicates; built lazily so core/
# stays importable without jax, and key-checked against the oracle
# tables so a new IR op that was only added in loopir fails loudly
# here instead of surfacing as a KeyError mid-kernel.
_JNP_TABLES: Optional[tuple[dict, dict]] = None


def _jnp_tables():
    global _JNP_TABLES
    if _JNP_TABLES is None:
        import jax.numpy as jnp

        binops = {
            "+": jnp.add, "-": jnp.subtract, "*": jnp.multiply,
            "/": jnp.true_divide, "//": jnp.floor_divide, "%": jnp.mod,
            "min": jnp.minimum, "max": jnp.maximum,
            "<": jnp.less, "<=": jnp.less_equal,
            ">": jnp.greater, ">=": jnp.greater_equal,
            "==": jnp.equal, "!=": jnp.not_equal,
        }
        unfns = {
            "tanh": jnp.tanh, "relu": lambda x: jnp.maximum(x, 0),
            "neg": lambda x: -x, "abs": jnp.abs, "sign": jnp.sign,
            "exp": jnp.exp,
        }
        assert set(binops) == set(ir.NP_BINOPS), (
            "jnp binop table out of sync with loopir.NP_BINOPS: "
            f"{set(binops) ^ set(ir.NP_BINOPS)}"
        )
        assert set(unfns) == set(ir.NP_UN_FNS), (
            "jnp unary table out of sync with loopir.NP_UN_FNS: "
            f"{set(unfns) ^ set(ir.NP_UN_FNS)}"
        )
        _JNP_TABLES = (binops, unfns)
    return _JNP_TABLES


@dataclasses.dataclass
class StoreTable:
    """Compute body of one store op, in backend-executable form.

    ``deps`` are the load ops whose values feed the body; the backend
    supplies one aligned stream per dep (see ``WavePlan.dep_maps``).
    ``env_exprs`` are the captured slots in slot order — the plan
    builder evaluates them through the ``aux_exprs`` interpreter hook
    into ``WavePlan.env`` streams. ``locals`` are the fed locals the
    body reads (one aligned stream each, ``WavePlan.local_maps``).
    ``value``/``guard`` are closure trees over those input kinds plus
    ``frozen_reads`` gathers.
    """

    op_id: str
    array: str
    deps: tuple[str, ...]
    env_exprs: tuple[ir.Expr, ...]
    value: CNode
    guard: Optional[CNode]
    frozen_reads: tuple[str, ...]
    locals: tuple[str, ...] = ()

    def eval_value(self, deps, env, arrays, n, lib="np", locs=None):
        """Vectorized store values for ``n`` requests; ``deps``/``env``/
        ``locs`` map to per-request operand arrays already sliced and
        aligned to the same request subset. ``lib="np"`` is the
        bit-exact path."""
        v = _eval_closure(self.value, deps, env, arrays, lib, locs)
        return _bcast(v, n, lib)

    def eval_guard(self, deps, env, arrays, n, lib="np", locs=None):
        """Vectorized §6 valid mask (all-True when unguarded)."""
        if self.guard is None:
            return np.ones(n, dtype=bool)
        m = _eval_closure(self.guard, deps, env, arrays, lib, locs)
        return np.asarray(_bcast(m, n, lib)).astype(bool)


@dataclasses.dataclass
class LocalTable:
    """Compute body of one SetLocal place (number ``index`` in
    ``loopir.set_locals``) of a fed local: ``value`` is a closure tree
    over the loads it reads (``deps``), its captured slots
    (``env_exprs``, through ``interpret``'s ``local_exprs`` hook), the
    fed locals it reads (``locals``, their current values) and
    ``frozen_reads`` gathers. ``scalar`` evaluates it for one execution
    as the oracle does, on Python and numpy scalars."""

    index: int
    name: str
    deps: tuple[str, ...]
    env_exprs: tuple[ir.Expr, ...]
    value: CNode
    frozen_reads: tuple[str, ...]
    locals: tuple[str, ...]

    @functools.cached_property
    def scalar(self):
        """``make(LV, S, V, A, O, R, J) -> f``: ``f(x)`` is the value of
        execution ``x`` (an event of the plan), from the load value
        lists ``LV`` (in ``deps`` order; dep ``i`` read at ordinal
        ``O[i][x]``), the slot value lists ``S`` (read at ``J[x]``, the
        execution's ordinal at this place), the event values ``V``
        (local ``i`` read at ``R[i][x]``, in ``locals`` order) and the
        frozen arrays ``A`` (in ``frozen_reads`` order), with
        ``loopir._binop``'s operations."""
        consts: list = []

        def src(n):
            if isinstance(n, CConst):
                consts.append(n.v)
                return f"C[{len(consts) - 1}]"
            if isinstance(n, CDep):
                j = self.deps.index(n.load_id)
                return f"lv{j}[O[{j}][x]]"
            if isinstance(n, CEnv):
                return f"S[{n.slot}][J[x]]"
            if isinstance(n, CLocal):
                return f"V[R[{self.locals.index(n.name)}][x]]"
            if isinstance(n, CGather):
                j = self.frozen_reads.index(n.array)
                return f"A[{j}][int_({src(n.index)})]"
            if isinstance(n, CBin):
                a, b = src(n.a), src(n.b)
                if n.op == "/":
                    return f"fdiv_({a}, {b})"
                if n.op in ("min", "max"):
                    return f"{n.op}_({a}, {b})"
                if n.op in ("//", "%"):
                    # numpy's: a zero divisor gives inf or nan, as on
                    # the numpy scalars the oracle reads from arrays
                    return f"B[{n.op!r}]({a}, {b})"
                if n.op not in ir.NP_BINOPS:
                    raise ValueError(f"unknown binop {n.op}")
                return f"({a} {n.op} {b})"
            if isinstance(n, CUn):
                return f"U[{n.op!r}]({src(n.a)})"
            raise TypeError(f"cannot compile closure node {n!r}")

        body = src(self.value)
        lvs = "".join(f"lv{j}, " for j in range(len(self.deps)))
        code = (
            "def make(LV, S, V, A, O, R, J):\n"
            f"    ({lvs}) = LV\n"
            "    def f(x):\n"
            f"        return {body}\n"
            "    return f\n"
        )
        ns = {"C": consts, "U": ir.NP_UN_FNS, "B": ir.NP_BINOPS,
              "fdiv_": ir._fdiv,
              "min_": min, "max_": max, "int_": int}
        exec(code, ns)
        return ns["make"]


def _bcast(v, n: int, lib: str):
    """Constant-valued bodies evaluate to scalars; stretch to n rows."""
    if np.ndim(v) == 0:
        if lib == "np":
            return np.full(n, v, dtype=np.float64)
        import jax.numpy as jnp

        return jnp.full(n, v)
    return v


def _eval_closure(node: CNode, deps, env, arrays, lib, locs=None):
    if lib == "np":
        binops, unfns, asarr = ir.NP_BINOPS, ir.NP_UN_FNS, np.asarray
    else:
        binops, unfns = _jnp_tables()
        import jax.numpy as jnp

        asarr = jnp.asarray

    def ev(n):
        if isinstance(n, CConst):
            return n.v
        if isinstance(n, CDep):
            return deps[n.load_id]
        if isinstance(n, CEnv):
            return env[n.slot]
        if isinstance(n, CLocal):
            return locs[n.name]
        if isinstance(n, CGather):
            idx = ev(n.index)
            arr = asarr(arrays[n.array])
            # clip: mis-speculated (§6 guard-false) rows may hold garbage
            # indices; their results are masked out by the valid bit
            if lib == "np":
                i = np.clip(np.asarray(idx).astype(np.int64), 0, len(arr) - 1)
                return arr[i]
            import jax.numpy as jnp

            return jnp.take(arr, asarr(idx).astype(int), mode="clip")
        if isinstance(n, CBin):
            return binops[n.op](ev(n.a), ev(n.b))
        if isinstance(n, CUn):
            return unfns[n.op](ev(n.a))
        raise TypeError(f"cannot eval closure node {n!r}")

    with np.errstate(all="ignore"):
        return ev(node)


# ---------------------------------------------------------------------------
# Compilation: partial evaluation of store bodies
# ---------------------------------------------------------------------------


def _reads(e: ir.Expr, names) -> bool:
    """Does ``e`` read a load value, or a local in ``names``?"""
    if isinstance(e, ir.LoadVal):
        return True
    if isinstance(e, ir.Local):
        return e.name in names
    if isinstance(e, ir.Bin):
        return _reads(e.a, names) or _reads(e.b, names)
    if isinstance(e, ir.Un):
        return _reads(e.a, names)
    if isinstance(e, ir.Read):
        return _reads(e.index, names)
    return False


def fed_locals(program: ir.Program) -> frozenset[str]:
    """Locals that some SetLocal computes from a load value, directly or
    through another such local: the registers a datapath accumulates
    loaded values in (a CSR row's ``sum``, a queue tail)."""
    sets = ir.set_locals(program)
    fed: set[str] = set()
    grown = True
    while grown:
        grown = False
        for s in sets:
            if s.name not in fed and _reads(s.value, fed):
                fed.add(s.name)
                grown = True
    return frozenset(fed)


class _Body:
    """Partial evaluation of one compute body (module doc): ``comp``
    turns an expression into a closure tree and collects its inputs."""

    def __init__(self, owner: str, fed, streamed: dict, stored: set):
        self.owner, self.streamed, self.stored = owner, streamed, stored
        # a streamed local reaches the body through its FIFO pop; every
        # other fed local through its fold
        self.dynamic = set(fed) | set(streamed)
        self.env_exprs: list[ir.Expr] = []
        self.env_index: dict[ir.Expr, int] = {}
        self.deps: list[str] = []
        self.frozen: list[str] = []
        self.locals: list[str] = []

    def slot(self, e: ir.Expr) -> CNode:
        if isinstance(e, ir.Const):
            return CConst(e.v)
        k = self.env_index.get(e)
        if k is None:
            k = len(self.env_exprs)
            self.env_index[e] = k
            self.env_exprs.append(e)
        return CEnv(k)

    def comp(self, e: ir.Expr) -> CNode:
        if not _reads(e, self.dynamic):
            return self.slot(e)
        if isinstance(e, ir.LoadVal):
            if e.load_id not in self.deps:
                self.deps.append(e.load_id)
            return CDep(e.load_id)
        if isinstance(e, ir.Local):
            if e.name in self.streamed:
                pop_op = self.streamed[e.name]
                if pop_op not in self.deps:
                    self.deps.append(pop_op)
                return CDep(pop_op)
            if e.name not in self.locals:
                self.locals.append(e.name)
            return CLocal(e.name)
        if isinstance(e, ir.Bin):
            return CBin(e.op, self.comp(e.a), self.comp(e.b))
        if isinstance(e, ir.Un):
            return CUn(e.op, self.comp(e.a))
        if isinstance(e, ir.Read):
            # index depends on a load value: the gather must run in the
            # backend, against a frozen array
            if e.array in self.stored:
                raise OpTableError(
                    f"{self.owner}: Read('{e.array}') has a "
                    f"load-dependent index but '{e.array}' is also a "
                    f"store target — no frozen snapshot to gather from"
                )
            if e.array not in self.frozen:
                self.frozen.append(e.array)
            return CGather(e.array, self.comp(e.index))
        raise TypeError(f"cannot compile {e!r}")  # pragma: no cover


def _stored_arrays(program: ir.Program) -> set[str]:
    return {op.array for op, _ in program.mem_ops() if op.is_store}


def compile_store_tables(
    program: ir.Program,
    stream_deps: Optional[dict[str, dict[str, str]]] = None,
) -> dict[str, StoreTable]:
    """One ``StoreTable`` per store op of ``program`` (keyed by op id).

    Partial evaluation rule: a maximal ``LoadVal``-free subtree becomes
    an env slot (deduplicated structurally); ``Const`` leaves inline;
    everything containing a ``LoadVal`` compiles to closure nodes, and
    so does a fed local (``fed_locals``), to a ``CLocal``.
    Raises ``OpTableError`` for a load-dependent ``Read`` of an array
    the program also stores to (no frozen snapshot exists).

    ``stream_deps`` maps a store op id to ``{local name: pop op id}``
    for cross-PE streamed locals (DESIGN.md §11): a ``Local`` in that
    map is *dynamic* — it compiles to a ``CDep`` on the pseudo pop op
    instead of an env slot or a ``CLocal``, so the store's value flows
    through the FIFO slot in memory and the wave plan orders the store
    after the pop (the producer-before-consumer dep edge
    ``validate_plan`` asserts per edge).
    """
    stream_deps = stream_deps or {}
    fed = fed_locals(program)
    stored = _stored_arrays(program)
    tables: dict[str, StoreTable] = {}
    for op, _path in program.mem_ops():
        if not op.is_store:
            continue
        body = _Body(f"store '{op.id}'", fed, stream_deps.get(op.id, {}),
                     stored)
        value = body.comp(op.value)
        guard = body.comp(op.guard) if op.guard is not None else None
        tables[op.id] = StoreTable(
            op_id=op.id,
            array=op.array,
            deps=tuple(body.deps),
            env_exprs=tuple(body.env_exprs),
            value=value,
            guard=guard,
            frozen_reads=tuple(body.frozen),
            locals=tuple(body.locals),
        )
    return tables


def compile_local_tables(
    program: ir.Program, store_tables: dict[str, StoreTable]
) -> dict[int, LocalTable]:
    """One ``LocalTable`` per SetLocal place (by its number in
    ``loopir.set_locals``) of every fed local that a store table reads,
    or that such a local's SetLocal reads: the statements whose fold
    gives those stores their ``CLocal`` values."""
    fed = fed_locals(program)
    stored = _stored_arrays(program)
    sets = ir.set_locals(program)
    bodies: dict[int, tuple[_Body, CNode]] = {}
    need = {n for t in store_tables.values() for n in t.locals}
    todo = list(need)
    while todo:
        name = todo.pop()
        for k, s in enumerate(sets):
            if s.name != name:
                continue
            body = _Body(f"SetLocal('{name}')", fed, {}, stored)
            bodies[k] = (body, body.comp(s.value))
            for m in body.locals:
                if m not in need:
                    need.add(m)
                    todo.append(m)
    return {
        k: LocalTable(
            index=k, name=sets[k].name, deps=tuple(body.deps),
            env_exprs=tuple(body.env_exprs), value=value,
            frozen_reads=tuple(body.frozen), locals=tuple(body.locals),
        )
        for k, (body, value) in sorted(bodies.items())
    }
