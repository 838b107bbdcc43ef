"""Differential conformance for the affine trace compiler (DESIGN.md §7).

The compiled AGU/CU front-end (core/affine.py + schedule.compile_pe_trace
+ dae.VecCU) must be **bit-for-bit** equal to the reference interpreter
(schedule._trace_pe + dae.CU) on every program inside the compiled
subset — sched counters, addresses, lastIter hints, seq numbers, and
declared metadata (depth, is_store). This file pins that contract with:

  * a random-program differential fuzz suite (hypothesis strategies in
    tests/loopir_strategies.py; the nightly CI job raises the example
    budget via HYPOTHESIS_PROFILE=nightly and randomizes the seed),
  * the Table-1 acceptance bar: all nine kernels fully on the compiled
    path under trace_mode="auto",
  * fallback coverage: loop-carried-local addresses (CSR-style row
    pointers, histogram-style bin accumulators), load-dependent
    trips/addresses, sequential ivar recurrences — detected, routed to
    the interpreter under "auto", and rejected with a diagnostic naming
    the offender under "compiled",
  * the zero-trip metadata regression: ops of never-executing loops
    declare the same depth/is_store on both paths.
"""

import numpy as np
import pytest

import loopir_strategies as strat
from repro.core import affine
from repro.core import dae as daelib
from repro.core import loopir as ir
from repro.core import programs
from repro.core import schedule as schedlib
from repro.core import simulator


def _assert_traces_equal(ti, tc, label=""):
    assert set(ti) == set(tc), label
    for op_id in ti:
        a, b = ti[op_id], tc[op_id]
        assert a.pe_id == b.pe_id, (label, op_id)
        assert a.depth == b.depth, (label, op_id, a.depth, b.depth)
        assert a.is_store == b.is_store, (label, op_id)
        np.testing.assert_array_equal(
            a.sched, b.sched, err_msg=f"{label}/{op_id}: sched"
        )
        np.testing.assert_array_equal(
            a.addr, b.addr, err_msg=f"{label}/{op_id}: addr"
        )
        np.testing.assert_array_equal(
            a.lastiter, b.lastiter, err_msg=f"{label}/{op_id}: lastiter"
        )
        np.testing.assert_array_equal(
            a.seq, b.seq, err_msg=f"{label}/{op_id}: seq"
        )
        assert b.sched.shape == (b.n_req, b.depth), (label, op_id)
        assert b.sched.dtype == np.int64 and b.addr.dtype == np.int64
        assert b.lastiter.dtype == np.bool_ and b.seq.dtype == np.int64


# ---------------------------------------------------------------------------
# the differential fuzz suite
# ---------------------------------------------------------------------------


def _check_agu_differential(pap):
    """One generated program: every PE classifies compiled and the
    compiled streams equal the interpreter's exactly."""
    prog, arrays, params = pap
    d = daelib.decouple(prog)
    ti = schedlib.trace_program(prog, d, arrays, params, mode="interp")
    report = {}
    tc = schedlib.trace_program(
        prog, d, arrays, params, mode="compiled", report=report
    )
    assert all(r["path"] == "compiled" for r in report.values())
    _assert_traces_equal(ti, tc)


def _check_cu_differential(pap):
    """Load-free value chains: VecCU's outbox (values, §6 valid bits,
    generation order) must equal the generator CU's, which for load-free
    PEs runs to completion when primed."""
    prog, arrays, params = pap
    d = daelib.decouple(prog)
    for pe in d.pes:
        cls = affine.classify_cu(pe)
        assert cls.compilable, cls.reasons
        gen = daelib.CU(pe, arrays, params)
        assert gen.done and gen.waiting_on is None
        vec = daelib.make_cu(pe, arrays, params)
        assert type(vec).__name__ == "VecCU"
        assert vec.done and vec.waiting_on is None
        assert len(vec.outbox) == len(gen.outbox)
        for i, ((ga, gv, gok), (va, vv, vok)) in enumerate(
            zip(gen.outbox, vec.outbox)
        ):
            assert ga == va, (pe.id, i, ga, va)
            assert gok == vok, (pe.id, i, ga)
            assert gv == vv, (pe.id, i, ga, gv, vv)


# deterministic seeded sweep: always runs (no hypothesis dependency),
# keeping the differential pinned in tier-1 even without the test extra
@pytest.mark.parametrize("seed", range(50))
def test_compiled_trace_equals_interpreter_seeded(seed):
    _check_agu_differential(
        strat.random_affine_program(np.random.default_rng(seed))
    )


@pytest.mark.parametrize("seed", range(30))
def test_vectorized_cu_equals_generator_seeded(seed):
    _check_cu_differential(
        strat.random_loadfree_cu_program(np.random.default_rng(1000 + seed))
    )


if strat.HAVE_HYPOTHESIS:
    from hypothesis import given

    # example budget comes from the active profile (tier1: 60 examples;
    # HYPOTHESIS_PROFILE=nightly: 250) — do not pin @settings here, it
    # would override the nightly budget

    @given(strat.affine_programs())
    def test_compiled_trace_equals_interpreter(pap):
        _check_agu_differential(pap)

    @given(strat.loadfree_cu_programs())
    def test_vectorized_cu_equals_generator(pap):
        _check_cu_differential(pap)


# ---------------------------------------------------------------------------
# Table-1 acceptance: all nine kernels fully compiled under "auto"
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("name", programs.TABLE1)
def test_table1_kernels_take_compiled_path(name):
    prog, arrays, params = programs.get(name).make(
        32 if name != "fft" else 64
    )
    d = daelib.decouple(prog)
    report = {}
    tc = schedlib.trace_program(
        prog, d, arrays, params, mode="auto", report=report
    )
    assert all(r["path"] == "compiled" for r in report.values()), report
    ti = schedlib.trace_program(prog, d, arrays, params, mode="interp")
    _assert_traces_equal(ti, tc, name)
    # key ORDER must match too: the trace dict's iteration order is the
    # engines' deterministic port-scan order, so a path-dependent order
    # resolves same-cycle ties differently (2-cycle drift on matpower at
    # 8x scale before compile_pe_trace emitted pe.mem_ops order)
    assert list(ti) == list(tc), (name, list(ti), list(tc))


def test_fft_compiles_despite_non_affine_address():
    """The classifier report separates compilability from §3 CR
    affinity: FFT's multiplicative stride is monotonic but *non-affine*
    in the CR sense, yet the trace compiles (vectorizability is the
    broader criterion)."""
    prog, arrays, params = programs.get("fft").make(64)
    d = daelib.decouple(prog)
    report = {}
    schedlib.trace_program(prog, d, arrays, params, mode="auto", report=report)
    affine_flags = [
        v for r in report.values() for v in r["op_affine"].values()
    ]
    assert all(r["path"] == "compiled" for r in report.values())
    assert not any(affine_flags), "fft addresses should be CR-non-affine"
    # while RAWloop's are plainly affine
    prog, arrays, params = programs.get("RAWloop").make(16)
    d = daelib.decouple(prog)
    report = {}
    schedlib.trace_program(prog, d, arrays, params, mode="auto", report=report)
    assert all(
        v for r in report.values() for v in r["op_affine"].values()
    )


# ---------------------------------------------------------------------------
# fallback coverage: detection, auto-routing, and forced-"compiled" errors
# ---------------------------------------------------------------------------


def _csr_local_rowptr():
    """CSR-style SpMV walking the row pointer in a loop-carried local —
    the address is sequential (non-affine) and must route to the
    interpreter."""
    prog = ir.Program(
        "csr_local",
        loops=(
            ir.Loop("i", ir.Param("rows", 0, 8), (
                ir.SetLocal("ptr", ir.Var("i") * 2),
                ir.Loop("k", ir.Const(2), (
                    ir.Load(
                        "ld_rowptr", "vals",
                        ir.Bin("+", ir.Local("ptr"), ir.Var("k")),
                    ),
                    ir.Store(
                        "st_y", "y", ir.Var("i"),
                        ir.LoadVal("ld_rowptr") * 2.0,
                    ),
                )),
            )),
        ),
        params=("rows",),
    )
    rng = np.random.default_rng(0)
    arrays = {"vals": rng.standard_normal(16), "y": np.zeros(8)}
    return prog, arrays, {"rows": 8}


def _hist_local_bin():
    """Histogram whose bin address round-trips through a loop-carried
    local — data-dependent via the local, not a direct gather."""
    prog = ir.Program(
        "hist_local",
        loops=(
            ir.Loop("i", ir.Param("n", 0, 32), (
                ir.SetLocal("bin", ir.Read("d", ir.Var("i"), 0, 7)),
                ir.Load("ld_h", "h", ir.Local("bin")),
                ir.Store(
                    "st_h", "h", ir.Local("bin"), ir.LoadVal("ld_h") + 1.0
                ),
            )),
        ),
        params=("n",),
    )
    rng = np.random.default_rng(1)
    arrays = {"h": np.zeros(8), "d": rng.integers(0, 8, size=32)}
    return prog, arrays, {"n": 32}


@pytest.mark.parametrize(
    "make,offender",
    [(_csr_local_rowptr, "ld_rowptr"), (_hist_local_bin, "ld_h")],
)
def test_local_carried_addresses_fall_back(make, offender):
    prog, arrays, params = make()
    d = daelib.decouple(prog)

    # detection: the classifier names the op and the local
    report = {}
    tc = schedlib.trace_program(
        prog, d, arrays, params, mode="auto", report=report
    )
    bad = [r for r in report.values() if r["path"] == "interp"]
    assert bad, "expected at least one PE on the interpreter path"
    assert any(offender in (r["reason"] or "") for r in bad)
    assert any("local" in (r["reason"] or "") for r in bad)

    # auto == interp exactly (it IS the interpreter for these PEs)
    ti = schedlib.trace_program(prog, d, arrays, params, mode="interp")
    _assert_traces_equal(ti, tc)

    # forcing "compiled" raises a diagnostic naming the offending op
    with pytest.raises(schedlib.TraceCompileError, match=offender):
        schedlib.trace_program(prog, d, arrays, params, mode="compiled")

    # and the full simulation still runs oracle-exact under auto
    oracle = ir.interpret(prog, arrays, params)
    res = simulator.simulate(
        prog, arrays, params, mode="FUS2", validate=True, trace_mode="auto"
    )
    for k in oracle:
        np.testing.assert_allclose(res.arrays[k], oracle[k], atol=1e-12)


def test_load_dependent_trip_is_detected():
    """A trip fed by a protected load value is loss of decoupling: the
    decoupling pass rejects the program outright under the default
    ``speculation="off"`` (any trace mode), names the consuming loop,
    and the affine classifier independently names the load when handed
    such a PE directly. Under ``speculation="auto"`` the same program
    *runs*, oracle-exact (DESIGN.md §10; the deeper coverage lives in
    tests/test_speculation.py)."""
    loops = (
        ir.Loop("i", ir.Param("n", 0, 4), (
            ir.Load("ld_n", "bounds", ir.Var("i")),
            ir.Loop("k", ir.LoadVal("ld_n"), (
                ir.Load("ld_x", "x", ir.Var("k")),
            )),
        )),
    )
    prog = ir.Program("lod", loops=loops, params=("n",))
    arrays = {"bounds": np.ones(4), "x": np.zeros(8)}
    for tm in ("auto", "compiled", "interp"):
        with pytest.raises(
            daelib.LossOfDecoupling, match=r"trip of loop 'k'"
        ):
            simulator.simulate(prog, arrays, {"n": 4}, trace_mode=tm)

    # regression: the previously-rejected program now runs speculatively
    oracle = ir.interpret(prog, arrays, {"n": 4})
    res = simulator.simulate(
        prog, arrays, {"n": 4}, speculation="auto", validate=True
    )
    for k in oracle:
        np.testing.assert_array_equal(res.arrays[k], oracle[k])

    # classifier view, bypassing the decoupling pass
    pe = daelib.PE(id=0, path=(loops[0], loops[0].body[1]))
    pe.stmts = [(loops[0].body[0], 1), (loops[0].body[1].body[0], 2)]
    cls = affine.classify_pe(pe)
    assert not cls.compilable
    assert any("ld_n" in r and "load" in r for r in cls.reasons)


def test_load_dependent_address_is_detected():
    loop = ir.Loop("i", ir.Const(4), (
        ir.Load("ld_a", "x", ir.Var("i")),
        ir.Load("ld_b", "x", ir.LoadVal("ld_a")),
    ))
    pe = daelib.PE(id=0, path=(loop,))
    pe.stmts = [(loop.body[0], 1), (loop.body[1], 1)]
    cls = affine.classify_pe(pe)
    assert not cls.compilable
    assert any("ld_b" in r and "ld_a" in r for r in cls.reasons)


def test_sequential_multiplicative_ivar_falls_back():
    """A '*' ivar whose step varies inside the loop has no closed form;
    auto must route the PE to the interpreter and agree exactly."""
    prog = ir.Program(
        "seqmul",
        loops=(
            ir.Loop(
                "i", ir.Const(5),
                (ir.Load("ld", "x", ir.Var("s")),),
                ivars=(
                    ir.IVar(
                        "s", ir.Const(1), "*",
                        ir.Bin("+", ir.Var("i"), ir.Const(1)),
                    ),
                ),
            ),
        ),
    )
    arrays = {"x": np.zeros(200)}
    d = daelib.decouple(prog)
    report = {}
    tc = schedlib.trace_program(prog, d, arrays, {}, mode="auto", report=report)
    assert report[0]["path"] == "interp"
    assert "s" in report[0]["reason"]
    ti = schedlib.trace_program(prog, d, arrays, {}, mode="interp")
    _assert_traces_equal(ti, tc)
    with pytest.raises(schedlib.TraceCompileError):
        schedlib.trace_program(prog, d, arrays, {}, mode="compiled")


def test_multiplicative_ivar_overflow_falls_back():
    """3**44 wraps int64; the interpreter computes it with Python's
    arbitrary-precision ints. The build-time magnitude bound must route
    such PEs to the interpreter instead of silently diverging."""
    prog = ir.Program(
        "ovf",
        loops=(
            ir.Loop(
                "i", ir.Const(45),
                (ir.Load("ld", "x", ir.Bin("%", ir.Var("s"), ir.Const(10))),),
                ivars=(ir.IVar("s", ir.Const(1), "*", ir.Const(3)),),
            ),
        ),
    )
    arrays = {"x": np.zeros(16)}
    d = daelib.decouple(prog)
    report = {}
    tc = schedlib.trace_program(prog, d, arrays, {}, mode="auto", report=report)
    assert report[0]["path"] == "interp"
    assert "int64" in report[0]["reason"]
    ti = schedlib.trace_program(prog, d, arrays, {}, mode="interp")
    _assert_traces_equal(ti, tc)
    with pytest.raises(schedlib.TraceCompileError, match="int64"):
        schedlib.trace_program(prog, d, arrays, {}, mode="compiled")


def test_additive_ivar_overflow_falls_back():
    prog = ir.Program(
        "ovfadd",
        loops=(
            ir.Loop(
                "i", ir.Const(8),
                (ir.Load("ld", "x", ir.Bin("%", ir.Var("a"), ir.Const(7))),),
                ivars=(
                    ir.IVar("a", ir.Const(0), "+", ir.Read("big", ir.Var("i"))),
                ),
            ),
        ),
    )
    arrays = {
        "x": np.zeros(8),
        # sum = 2^61 exceeds the 2^60 safety bound while each value (and
        # the true running sum) still fits int64 — guard must be
        # conservative, not just catch actual wraps
        "big": np.full(8, 2**58, dtype=np.int64),
    }
    d = daelib.decouple(prog)
    report = {}
    tc = schedlib.trace_program(prog, d, arrays, {}, mode="auto", report=report)
    assert report[0]["path"] == "interp"
    assert "int64" in report[0]["reason"]
    ti = schedlib.trace_program(prog, d, arrays, {}, mode="interp")
    _assert_traces_equal(ti, tc)
    with pytest.raises(schedlib.TraceCompileError, match="int64"):
        schedlib.trace_program(prog, d, arrays, {}, mode="compiled")


def test_float_ivar_accumulation_falls_back_at_build():
    """Classification is structural; non-integer accumulation is only
    visible at build time (array dtypes). auto falls back, compiled
    raises."""
    prog = ir.Program(
        "facc",
        loops=(
            ir.Loop(
                "i", ir.Const(4),
                (ir.Load("ld", "x", ir.Var("a")),),
                ivars=(
                    ir.IVar(
                        "a", ir.Const(0), "+",
                        ir.Read("w", ir.Var("i")),  # float-valued steps
                    ),
                ),
            ),
        ),
    )
    arrays = {"x": np.zeros(64), "w": np.array([1.5, 2.0, 0.5, 3.0])}
    d = daelib.decouple(prog)
    report = {}
    tc = schedlib.trace_program(prog, d, arrays, {}, mode="auto", report=report)
    assert report[0]["path"] == "interp"
    assert "bit-exact" in report[0]["reason"]
    ti = schedlib.trace_program(prog, d, arrays, {}, mode="interp")
    _assert_traces_equal(ti, tc)
    with pytest.raises(schedlib.TraceCompileError, match="bit-exact"):
        schedlib.trace_program(prog, d, arrays, {}, mode="compiled")


# ---------------------------------------------------------------------------
# zero-trip metadata regression (the negative-space fix)
# ---------------------------------------------------------------------------


def _parent_body_prog():
    return ir.Program(
        "zt",
        loops=(
            ir.Loop("i", ir.Param("n", 0, 8), (
                ir.Store("st_pre", "B", ir.Var("i"), ir.Const(1.0)),
                ir.Loop("k", ir.Param("m", 0, 4), (
                    ir.Load("ld_in", "A", ir.Var("k")),
                )),
            )),
        ),
        params=("n", "m"),
    )


@pytest.mark.parametrize("mode", ("interp", "compiled"))
@pytest.mark.parametrize("n,m", [(0, 2), (3, 0), (0, 0)])
def test_zero_trip_ops_declare_static_metadata(mode, n, m):
    """A mem op whose loop never executes must still declare its static
    depth and kind. Previously the interpreter path silently defaulted
    to pe.depth / is_store=False for such ops."""
    prog = _parent_body_prog()
    arrays = {"A": np.zeros(8), "B": np.zeros(8)}
    params = {"n": n, "m": m}
    d = daelib.decouple(prog)
    tr = schedlib.trace_program(prog, d, arrays, params, mode=mode)
    # st_pre is a parent-body op at depth 1 in a depth-2 PE
    assert tr["st_pre"].depth == 1
    assert tr["st_pre"].is_store is True
    assert tr["st_pre"].sched.shape == (n, 1)
    assert tr["ld_in"].depth == 2
    assert tr["ld_in"].is_store is False
    if n == 0:
        assert tr["st_pre"].n_req == 0
    if n == 0 or m == 0:
        assert tr["ld_in"].n_req == 0


@pytest.mark.parametrize("n,m", [(0, 2), (3, 0)])
def test_zero_trip_simulation_still_oracle_exact(n, m):
    prog = _parent_body_prog()
    arrays = {"A": np.zeros(8), "B": np.zeros(8)}
    params = {"n": n, "m": m}
    oracle = ir.interpret(prog, arrays, params)
    for tm in ("interp", "compiled"):
        res = simulator.simulate(
            prog, arrays, params, mode="FUS2", validate=True, trace_mode=tm
        )
        for k in oracle:
            np.testing.assert_allclose(res.arrays[k], oracle[k], atol=1e-12)
