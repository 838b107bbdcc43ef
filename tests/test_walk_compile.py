"""The generated oracle walk (``loopir.compile_walk``) against
``loopir.interpret``: the same hook calls in the same order with the
same values, the same reader answers at every loop boundary, and the
same final arrays, bit for bit — on the registered kernels, the
benchmark's programs, random programs and hand-built edge cases. A
program the generator declines keeps the interpreter, in
``compile_walk`` and in ``build_wave_plan``."""

import math

import numpy as np
import pytest

import loopir_strategies as strat
from bench_programs import bench_program
from repro import trace
from repro.core import executor, loopir as ir, optable, programs

if strat.HAVE_HYPOTHESIS:
    from hypothesis import given


def _names(program):
    """Every name a reader may be asked for: loop vars, ivars, locals,
    and one no scope defines."""
    out = {"~undefined"}

    def walk(stmts):
        for s in stmts:
            if isinstance(s, ir.Loop):
                out.add(s.var)
                out.update(iv.name for iv in s.ivars)
                walk(s.body)
            elif isinstance(s, ir.SetLocal):
                out.add(s.name)

    walk(program.loops)
    return sorted(out)


def _record(run, names):
    """Every hook call of one walk (loops by identity, the reader asked
    for every name at each call), the final arrays, and the type of the
    exception that ended the walk, if one did."""
    calls = []

    def trace_hook(*args):
        calls.append(("trace",) + args)

    def aux_hook(op_id, values):
        calls.append(("aux", op_id, values))

    def loop_hook(loop, phase, reader):
        seen = []
        for n in names:
            try:
                seen.append((n, reader(n)))
            except KeyError:
                seen.append((n, KeyError))
        calls.append(("loop", id(loop), phase, tuple(seen)))

    try:
        final, raised = run(trace_hook, aux_hook, loop_hook), None
    except Exception as exc:  # the interpreter's errors are part of it
        final, raised = None, type(exc)
    return calls, final, raised


def _same(x, y):
    """Equal values, NaN equal to NaN, and a float's sign bit kept."""
    if isinstance(x, tuple) or isinstance(y, tuple):
        return (isinstance(x, tuple) and isinstance(y, tuple)
                and len(x) == len(y)
                and all(_same(a, b) for a, b in zip(x, y)))
    if x is None or y is None or isinstance(x, (str, type)):
        return x is y or x == y
    if isinstance(x, (float, np.floating)) or isinstance(y, (float, np.floating)):
        return (x == y and math.copysign(1, x) == math.copysign(1, y)) or (
            x != x and y != y
        )
    return x == y


def _aux(program):
    """The op-table operand expressions ``build_wave_plan`` captures."""
    try:
        tables = optable.compile_store_tables(program)
    except optable.OpTableError:
        return {}
    return {o: t.env_exprs for o, t in tables.items() if t.env_exprs}


def check_walks_agree(program, arrays, params, aux=None, compiles=True):
    """Both walkers, with and without a loop hook: identical calls,
    arrays (dtype and bits) and errors. Returns whether the generated
    walk ran."""
    aux = _aux(program) if aux is None else aux
    names = _names(program)
    ran = False
    for hooked in (True, False):
        walker = ir.compile_walk(program, aux, with_loop_hook=hooked)
        assert (walker is not None) == compiles
        if walker is None:
            return False

        def interp(th, ah, lh, hooked=hooked):
            return ir.interpret(program, arrays, params, th, aux, ah,
                                lh if hooked else None)

        def compiled(th, ah, lh, walker=walker, hooked=hooked):
            out = walker(program, arrays, params, th, ah,
                         lh if hooked else None)
            assert out is not None
            return out

        want, got = _record(interp, names), _record(compiled, names)
        assert got[2] is want[2], f"raised {got[2]}, interpreter {want[2]}"
        assert len(got[0]) == len(want[0])
        for k, (a, b) in enumerate(zip(want[0], got[0])):
            assert _same(a, b), f"call {k}: interpreter {a}, generated {b}"
        if want[1] is not None:
            assert want[1].keys() == got[1].keys()
            for name, a in want[1].items():
                b = got[1][name]
                assert a.dtype == b.dtype and a.shape == b.shape, name
                assert a.tobytes() == b.tobytes(), name
        ran = True
    return ran


# ---------------------------------------------------------------------------
# registered kernels and the benchmark's programs
# ---------------------------------------------------------------------------

PLAIN = [n for n in programs.all_names() if not programs.get(n).speculative]


@pytest.mark.parametrize("name", PLAIN)
def test_registered_kernels(name):
    prog, arrays, params = programs.get(name).make(16)
    assert check_walks_agree(prog, arrays, params)


@pytest.mark.parametrize("config,override", [
    ("tanh_spmv", {"nx": 4, "ny": 4, "nz": 4, "level": 0}),
    ("tanh_spmv", {"nx": 8, "ny": 8, "nz": 8, "level": 1}),
    ("pagerank_rmat", {"scale": 5}),
], ids=["spmv_4cubed", "spmv_level1", "pagerank_scale5"])
def test_bench_programs(config, override):
    assert check_walks_agree(*bench_program(config, **override))


# ---------------------------------------------------------------------------
# random programs
# ---------------------------------------------------------------------------

GENERATORS = {
    "affine": strat.random_affine_program,
    "wave": strat.random_wave_program,
    "loadfree_cu": strat.random_loadfree_cu_program,
    "stream": strat.random_stream_program,
}


@pytest.mark.parametrize("seed", range(12))
@pytest.mark.parametrize("kind", sorted(GENERATORS))
def test_random_programs_seeded(kind, seed):
    pa = GENERATORS[kind](np.random.default_rng(1000 + seed))
    assert check_walks_agree(*pa)


if strat.HAVE_HYPOTHESIS:

    class TestRandomProgramsHypothesis:
        @given(strat.affine_programs())
        def test_affine(self, pa):
            assert check_walks_agree(*pa)

        @given(strat.wave_programs())
        def test_wave(self, pa):
            assert check_walks_agree(*pa)

        @given(strat.loadfree_cu_programs())
        def test_loadfree_cu(self, pa):
            assert check_walks_agree(*pa)

        @given(strat.stream_programs())
        def test_stream(self, pa):
            assert check_walks_agree(*pa)


# ---------------------------------------------------------------------------
# hand-built edge cases
# ---------------------------------------------------------------------------


def _guard_false_aux_raises():
    """Where the guard fails, the value's gather runs off the end of
    ``data``: the aux row holds NaN there."""
    gather = ir.Read("data", ir.Read("idx", ir.Var("i")))
    prog = ir.Program("guarded_gather", loops=(
        ir.Loop("i", ir.Const(6), (
            ir.Store("st", "out", ir.Var("i"), gather * 2.0,
                     guard=ir.Bin(">", ir.Read("ok", ir.Var("i")),
                                  ir.Const(0))),
        )),
    ))
    arrays = {
        "data": np.arange(4, dtype=np.float64) + 0.5,
        "idx": np.array([0, 9, 1, 9, 3, 2], dtype=np.int64),
        "ok": np.array([1, 0, 1, 0, 1, 1], dtype=np.int64),
        "out": np.zeros(6),
    }
    return prog, arrays, {}


def _shadowing():
    """An inner loop var shadowing an outer one, a local set in an
    enclosing scope from inside a loop, a local defined per iteration
    and read after it, and a local that overwrites an ivar."""
    prog = ir.Program("shadowing", loops=(
        ir.Loop("i", ir.Const(3), (
            ir.SetLocal("acc", ir.Const(0.0)),
            ir.Loop("i", ir.Var("i") + 2, (
                ir.Load("ld", "a", ir.Var("i")),
                ir.SetLocal("acc", ir.Local("acc") + ir.LoadVal("ld")),
                ir.SetLocal("tmp", ir.LoadVal("ld") * 3.0),
                ir.Store("st_t", "b", ir.Var("i"), ir.Local("tmp")),
            )),
            ir.Store("st_acc", "b", ir.Var("i") + 5, ir.Local("acc")),
            ir.Loop("k", ir.Const(3), (
                ir.SetLocal("w", ir.Var("w") + 10),
                ir.Store("st_w", "b", ir.Var("k") + 8, ir.Var("w") * 1.0),
            ), ivars=(ir.IVar("w", ir.Var("i"), "+", ir.Const(1)),)),
        )),
    ))
    arrays = {"a": np.arange(8, dtype=np.float64) - 2.5,
              "b": np.zeros(11)}
    return prog, arrays, {}


def _ivars():
    """A '*' ivar (FFT's stride) and a '+' ivar with a step that reads
    the loop var, both in addresses; a step that reads a local of the
    body."""
    prog = ir.Program("ivars", loops=(
        ir.Loop("i", ir.Const(4), (
            ir.SetLocal("d", ir.Var("i") * 2),
            ir.Load("ld", "a", ir.Var("s") + ir.Var("o")),
            ir.Store("st", "b", ir.Var("o"), ir.LoadVal("ld") + ir.Var("s")),
        ), ivars=(
            ir.IVar("s", ir.Const(1), "*", ir.Const(2)),
            ir.IVar("o", ir.Const(0), "+", ir.Var("i") + ir.Local("d")),
        )),
    ))
    arrays = {"a": np.arange(40, dtype=np.float64) * 0.25,
              "b": np.zeros(40)}
    return prog, arrays, {}


def _zero_trip_fifo():
    """Producer leaf trips 1, 0, 0, 0: zero-trip instances still enter
    and exit, and push the local's init value."""
    prog = ir.Program("zero_trip_stream", loops=(
        ir.Loop("t", ir.Const(4), (
            ir.SetLocal("x", ir.Const(-1.0)),
            ir.Loop("p", ir.Bin("-", ir.Const(1), ir.Var("t")), (
                ir.Load("ld_d", "d", ir.Var("t")),
                ir.SetLocal("x", ir.LoadVal("ld_d") + 1.0),
            )),
            ir.Loop("c", ir.Const(1), (
                ir.Load("ld_o", "o", ir.Var("t")),
                ir.Store("st_o", "o", ir.Var("t"),
                         ir.LoadVal("ld_o") + ir.Local("x")),
            )),
        )),
    ))
    arrays = {"d": np.arange(4, dtype=np.float64),
              "o": np.zeros(4, dtype=np.float64)}
    return prog, arrays, {}


def _narrow_reads(dtype):
    """A float64 load times a Read of a narrower array: numpy's
    promotion decides the product's precision."""
    prog = ir.Program("narrow", loops=(
        ir.Loop("i", ir.Const(5), (
            ir.Load("ld", "x", ir.Var("i")),
            ir.Store("st", "x", ir.Var("i"),
                     ir.LoadVal("ld") * ir.Read("w", ir.Read("j", ir.Var("i")))
                     + 0.1),
        )),
    ))
    arrays = {
        "x": np.linspace(-1.1, 2.3, 5),
        "w": (np.arange(6) * 0.37 + 0.01).astype(dtype),
        "j": np.array([5, 0, 3, 1, 2], dtype=np.int32),
    }
    return prog, arrays, {}


def _int64_store():
    """Stores into an int64 array truncate as numpy's cast does, and a
    later load reads the truncated value."""
    prog = ir.Program("int_store", loops=(
        ir.Loop("i", ir.Const(5), (
            ir.Load("ld", "n", ir.Var("i")),
            ir.Store("st", "n", ir.Var("i") + 1,
                     ir.LoadVal("ld") * 1.5 + ir.Read("x", ir.Var("i"))),
        )),
    ))
    arrays = {"n": np.array([3, -7, 2, 9, 4, 1], dtype=np.int64),
              "x": np.linspace(-2.7, 3.1, 5)}
    return prog, arrays, {}


EDGE_CASES = {
    "guard_false_aux_raises": _guard_false_aux_raises,
    "setlocal_shadowing": _shadowing,
    "ivars_add_and_mul": _ivars,
    "zero_trip_fifo": _zero_trip_fifo,
    "float32_read": lambda: _narrow_reads(np.float32),
    "int32_read": lambda: _narrow_reads(np.int32),
    "int64_store": _int64_store,
}


@pytest.mark.parametrize("case", sorted(EDGE_CASES))
def test_edge_cases(case):
    assert check_walks_agree(*EDGE_CASES[case]())


def test_guard_false_aux_row_is_nan():
    prog, arrays, params = _guard_false_aux_raises()
    aux = _aux(prog)
    rows = []
    ir.compile_walk(prog, aux)(
        prog, arrays, params, aux_hook=lambda o, v: rows.append(v)
    )
    assert [math.isnan(r[0]) for r in rows] == [False, True, False, True,
                                                False, False]


def _reads_before_defined():
    """``acc`` is read in the iteration before the one that sets it, so
    ``interpret`` raises on the first read: the generator declines."""
    return ir.Program("undefined_read", loops=(
        ir.Loop("i", ir.Const(3), (
            ir.Store("st", "o", ir.Var("i"), ir.Local("acc")),
            ir.SetLocal("acc", ir.Var("i") * 1.0),
        )),
    )), {"o": np.zeros(3)}, {}


def _load_of_last_iteration():
    """A LoadVal read before its Load in the same body: each iteration's
    value map starts afresh."""
    return ir.Program("stale_load", loops=(
        ir.Loop("i", ir.Const(3), (
            ir.Store("st", "o", ir.Var("i"), ir.LoadVal("ld")),
            ir.Load("ld", "o", ir.Var("i")),
        )),
    )), {"o": np.zeros(3)}, {}


def _dead_undefined_read():
    """An undefined read in a zero-trip loop: ``interpret`` never makes
    it, and the program still takes the interpreter."""
    return ir.Program("dead_read", loops=(
        ir.Loop("i", ir.Const(0), (
            ir.Store("st", "o", ir.Var("i"), ir.Local("nope")),
        )),
        ir.Loop("j", ir.Const(2), (
            ir.Store("st2", "o", ir.Var("j"), ir.Const(4.0)),
        )),
    )), {"o": np.zeros(2)}, {}


def _bool_arithmetic():
    """Two comparisons of array values added: logical on numpy's
    booleans, integral on Python's."""
    gt = ir.Bin(">", ir.Read("a", ir.Var("i")), ir.Const(0.0))
    return ir.Program("bool_sum", loops=(
        ir.Loop("i", ir.Const(4), (
            ir.Store("st", "o", ir.Var("i"), gt + gt),
        )),
    )), {"a": np.array([1.0, -1.0, 2.0, 0.0]), "o": np.zeros(4)}, {}


def _divide_by_array_value():
    """``//`` by a value read from an array: zero gives inf on numpy
    scalars and raises on Python floats."""
    return ir.Program("floordiv", loops=(
        ir.Loop("i", ir.Const(3), (
            ir.Store("st", "o", ir.Var("i"),
                     ir.Const(7.0) // ir.Read("a", ir.Var("i"))),
        )),
    )), {"a": np.array([2.0, 0.0, -3.0]), "o": np.zeros(3)}, {}


def _nested(depth=24):
    """Loops nested deeper than Python compiles blocks."""
    body = (ir.Store("st", "o", ir.Const(0), ir.Var(f"v{depth - 1}") + 0.5),)
    for d in reversed(range(depth)):
        body = (ir.Loop(f"v{d}", ir.Const(1), body),)
    return ir.Program("deep", loops=body), {"o": np.zeros(1)}, {}


DECLINED = {
    "read_before_set": _reads_before_defined,
    "loadval_before_load": _load_of_last_iteration,
    "dead_undefined_read": _dead_undefined_read,
    "arithmetic_on_comparisons": _bool_arithmetic,
    "floordiv_by_array_value": _divide_by_array_value,
    "loops_24_deep": _nested,
}


@pytest.mark.parametrize("case", sorted(DECLINED))
def test_declined_programs_keep_the_interpreter(case):
    prog, arrays, params = DECLINED[case]()
    assert not check_walks_agree(prog, arrays, params, compiles=False)


def test_declined_program_plans_through_the_interpreter():
    """``build_wave_plan`` on a program the generator declines: the walk
    span says the interpreter ran, and the plan is the interpreter's."""
    prog = ir.Program("dead_read_plan", loops=(
        ir.Loop("i", ir.Const(0), (
            ir.Load("ld", "o", ir.Var("i")),
            ir.Store("st", "o", ir.Var("i"),
                     ir.LoadVal("ld") + ir.Local("nope")),
        )),
        ir.Loop("j", ir.Const(3), (
            ir.Load("ld2", "o", ir.Var("j")),
            ir.Store("st2", "o", ir.Var("j"), ir.LoadVal("ld2") + 1.5),
        )),
    ))
    plan = executor.build_wave_plan(prog, {"o": np.arange(3.0)}, {})
    stats = trace.RECENT[-1]["repro.plan.walk"][2]
    assert stats["compiled"] == 0 and stats["walk_compiles"] == 0
    np.testing.assert_array_equal(plan.req_value, [0.0, 1.5, 1.0, 2.5,
                                                   2.0, 3.5])


def test_missing_param_returns_none_before_any_hook():
    prog, arrays, params = programs.get("tanh+spmv").make(16)
    walker = ir.compile_walk(prog)
    calls = []
    assert walker(prog, arrays, {}, trace_hook=calls.append) is None
    assert calls == []
    assert walker(prog, arrays, params) is not None


def test_structurally_equal_program_gets_its_own_loops():
    """A walk compiled for one program object runs another of the same
    fingerprint and hands the loop hook that program's Loop objects."""
    prog, arrays, params = _zero_trip_fifo()
    twin = _zero_trip_fifo()[0]
    assert twin is not prog and twin.fingerprint() == prog.fingerprint()
    walker = ir.compile_walk(prog, with_loop_hook=True)
    seen = set()
    walker(twin, arrays, params,
           loop_hook=lambda loop, phase, reader: seen.add(id(loop)))
    twin_loops = {id(lp) for lp in ir._loops_in_order(twin)}
    assert seen == twin_loops


def test_loop_hook_needs_a_hooked_walk():
    prog, arrays, params = _zero_trip_fifo()
    with pytest.raises(ValueError, match="with_loop_hook"):
        ir.compile_walk(prog)(prog, arrays, params,
                              loop_hook=lambda *a: None)
