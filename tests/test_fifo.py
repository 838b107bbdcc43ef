"""Cross-PE FIFO streaming suite (core/fifo.py, DESIGN.md §11).

Pins the whole streaming stack end to end:

  * the three registered streaming kernels (``stream_dot``,
    ``filter_pipe``, ``stream_join``) are bit-identical to their
    hand-written numpy oracles under **both** simulator engines
    (cycle + event, all fused modes) and **both** wave backends
    (numpy executor + Pallas ``run_plan``/``run_sequential``),
  * the wave plan's FIFO slot encoding holds its invariants
    (producer-before-consumer per token, bounded backpressure at the
    configured depth) via ``executor.validate_plan`` plus direct
    metadata checks,
  * the token protocol's edge cases: zero-trip producer instances
    still owe a token (the shared-depth init value), depth-1 queues
    ping-pong correctly under real backpressure (stall counters > 0),
    undersized depths and cyclic/backward/rate-mismatched/derived-use
    edge sets are rejected statically with named-edge diagnostics,
  * the diagnostics bugfix sweep: ``LossOfDecoupling`` joins *every*
    reason (not just the first), the simulator's fallback
    ``NotImplementedError`` names the **full** edge list, and
    ``VecCU.feed`` / ``record_cu_script`` raise a typed
    ``CUContractError`` instead of a bare assert,
  * a deterministic seed sweep over ``random_stream_program`` plus the
    hypothesis wrapper (tier1 / nightly profiles, the nightly CI
    stream-fuzz job raises the budget via ``HYPOTHESIS_PROFILE``).
"""

import numpy as np
import pytest

import loopir_strategies as strat
from repro.core import dae as daelib
from repro.core import executor, loopir as ir, programs, simulator
from repro.core import fifo as fifolib
from repro.kernels import wave_exec
from repro.kernels.dynloop import ref

if strat.HAVE_HYPOTHESIS:
    from hypothesis import given

# small scales: the cycle engine and interpret-mode Pallas both run in
# tier-1, so keep the request streams short
SMALL_SCALE = {"stream_dot": 12, "filter_pipe": 48, "stream_join": 32}


def _copies(arrays):
    return {k: v.copy() for k, v in arrays.items()}


def _oracle(name, arrays, params):
    """The hand-written second semantics (kernels/dynloop/ref.py)."""
    if name == "stream_dot":
        return {
            "out": ref.stream_dot_ref(
                arrays["a"], arrays["bv"], arrays["out"],
                params["nb"], params["k"],
            )
        }
    if name == "filter_pipe":
        return {"y": ref.filter_pipe_ref(arrays["x"], arrays["y"])}
    assert name == "stream_join"
    return {"z": ref.stream_join_ref(arrays["u"], arrays["w"], arrays["z"])}


def test_registry_streaming_set():
    assert programs.STREAM_KERNELS == (
        "stream_dot", "filter_pipe", "stream_join"
    )
    for name in programs.STREAM_KERNELS:
        assert programs.get(name).streaming
        assert not programs.get(name).speculative


@pytest.mark.parametrize("name", programs.STREAM_KERNELS)
def test_interpret_matches_handwritten_oracle(name):
    prog, arrays, params = programs.get(name).make(SMALL_SCALE[name])
    got = ir.interpret(prog, _copies(arrays), params)
    for k, v in _oracle(name, arrays, params).items():
        np.testing.assert_array_equal(got[k], v)


# ---------------------------------------------------------------------------
# engine differential: cycle vs event, all fused modes, exact arrays +
# matching cycle counts + balanced queue accounting
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("name", programs.STREAM_KERNELS)
def test_engines_differential(name):
    prog, arrays, params = programs.get(name).make(SMALL_SCALE[name])
    oracle = _oracle(name, arrays, params)
    for mode in ("LSQ", "FUS1", "FUS2"):
        results = {
            engine: simulator.simulate(
                prog, _copies(arrays), params, mode=mode, engine=engine
            )
            for engine in ("cycle", "event")
        }
        for engine, res in results.items():
            for k, v in oracle.items():
                np.testing.assert_array_equal(
                    res.arrays[k], v,
                    err_msg=f"{name}/{mode}/{engine} diverged ({k})",
                )
            assert res.fifo_stats, f"{name}: no FIFO accounting"
            for qs in res.fifo_stats:
                assert qs["pushed"] == qs["popped"] > 0
                assert qs["max_occupancy"] <= simulator.SimParams().fifo_depth
        assert results["cycle"].cycles == results["event"].cycles, (
            f"{name}/{mode}: engine cycle counts diverged"
        )
        assert (
            results["cycle"].fifo_stats[0]["pushed"]
            == results["event"].fifo_stats[0]["pushed"]
        )


# ---------------------------------------------------------------------------
# wave executor + Pallas backend: slot-encoded FIFO edges
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("name", programs.STREAM_KERNELS)
def test_wave_backends_differential(name):
    prog, arrays, params = programs.get(name).make(SMALL_SCALE[name])
    oracle = _oracle(name, arrays, params)
    waves_by_depth = {}
    for depth in (1, 2, 4):
        plan = executor.build_wave_plan(
            prog, _copies(arrays), params, fifo_depth=depth
        )
        executor.validate_plan(plan)
        waves_by_depth[depth] = plan.stats.n_waves
        assert plan.fifo_edges, f"{name}: plan lost its FIFO edges"
        for fe in plan.fifo_edges:
            assert fe["depth"] == depth
            assert fe["n_tokens"] > 0
            assert fe["push_op"] in plan.op_ids
            assert fe["pop_op"] in plan.op_ids
        res_np = executor.execute(
            prog, _copies(arrays), params, fifo_depth=depth
        )
        res_pl = wave_exec.run_plan(plan, arrays)
        res_sq = wave_exec.run_sequential(plan, arrays, check=True)
        assert res_pl.complete and res_sq.complete
        for k, v in oracle.items():
            for label, got in (
                ("numpy", res_np.arrays[k]),
                ("pallas", res_pl.arrays[k]),
                ("sequential", res_sq.arrays[k]),
            ):
                np.testing.assert_array_equal(
                    got, v,
                    err_msg=f"{name}@depth={depth}: {label} backend "
                    f"diverged from oracle ({k})",
                )
    # deeper queues can only relax slot WAW/WAR edges -> fewer waves;
    # depth 1 serializes hardest (the ping-pong schedule)
    assert waves_by_depth[1] >= waves_by_depth[2] >= waves_by_depth[4]
    assert waves_by_depth[1] > waves_by_depth[4]


# ---------------------------------------------------------------------------
# token-protocol edge cases
# ---------------------------------------------------------------------------


def _zero_trip_program():
    """Producer leaf trips 1, 0, 0, 0 across the outer instances: the
    zero-trip instances still owe a token (the shared-depth init)."""
    prog = ir.Program(
        "zero_trip_stream",
        loops=(
            ir.Loop("t", ir.Const(4), (
                ir.SetLocal("x", ir.Const(-1.0)),
                ir.Loop("p", ir.Bin("-", ir.Const(1), ir.Var("t")), (
                    ir.Load("ld_d", "d", ir.Var("t")),
                    ir.SetLocal("x", ir.LoadVal("ld_d") + 1.0),
                )),
                ir.Loop("c", ir.Const(1), (
                    ir.Load("ld_o", "o", ir.Var("t")),
                    ir.Store(
                        "st_o", "o", ir.Var("t"),
                        ir.LoadVal("ld_o") + ir.Local("x"),
                    ),
                )),
            )),
        ),
    )
    arrays = {
        "d": np.arange(4, dtype=np.float64),
        "o": np.zeros(4, dtype=np.float64),
    }
    return prog, arrays, {}


def test_zero_trip_producer_still_pushes():
    prog, arrays, params = _zero_trip_program()
    # instance 0 computes d[0]+1; instances 1..3 push the init value
    expect = np.array([1.0, -1.0, -1.0, -1.0])
    got = ir.interpret(prog, _copies(arrays), params)
    np.testing.assert_array_equal(got["o"], expect)
    for engine in ("cycle", "event"):
        res = simulator.simulate(
            prog, _copies(arrays), params, engine=engine
        )
        np.testing.assert_array_equal(res.arrays["o"], expect)
        (qs,) = res.fifo_stats
        assert qs["pushed"] == qs["popped"] == 4
    r = executor.execute(prog, _copies(arrays), params)
    executor.validate_plan(r.plan)
    np.testing.assert_array_equal(r.arrays["o"], expect)
    assert r.plan.fifo_edges[0]["n_tokens"] == 4


def _pingpong_program(n=8):
    """Load-free fast producer feeding a slow RMW consumer: at depth 1
    the producer must hit a full queue (real backpressure)."""
    prog = ir.Program(
        "pingpong_stream",
        loops=(
            ir.Loop("t", ir.Const(n), (
                ir.SetLocal("s", ir.Const(0.0)),
                ir.Loop("p", ir.Const(1), (
                    ir.SetLocal("s", ir.Var("t") * 2.0 + 1.0),
                )),
                ir.Loop("c", ir.Const(1), (
                    ir.Load("ld_o", "o", ir.Var("t")),
                    ir.Store(
                        "st_o", "o", ir.Var("t"),
                        ir.LoadVal("ld_o") + ir.Local("s"),
                    ),
                )),
            )),
        ),
    )
    return prog, {"o": np.zeros(n, dtype=np.float64)}, {}


def test_depth1_ping_pong_backpressure():
    prog, arrays, params = _pingpong_program()
    expect = np.arange(8, dtype=np.float64) * 2.0 + 1.0
    sim = simulator.SimParams(fifo_depth=1)
    for engine in ("cycle", "event"):
        res = simulator.simulate(
            prog, _copies(arrays), params, sim=sim, engine=engine
        )
        np.testing.assert_array_equal(res.arrays["o"], expect)
        (qs,) = res.fifo_stats
        assert qs["max_occupancy"] == 1
        assert qs["push_stalls"] > 0, (
            f"{engine}: depth-1 queue never backpressured the producer"
        )
    plan1 = executor.build_wave_plan(
        prog, _copies(arrays), params, fifo_depth=1
    )
    plan4 = executor.build_wave_plan(
        prog, _copies(arrays), params, fifo_depth=4
    )
    for plan in (plan1, plan4):
        executor.validate_plan(plan)
    assert plan1.stats.n_waves > plan4.stats.n_waves
    r = executor.execute(prog, _copies(arrays), params, fifo_depth=1)
    np.testing.assert_array_equal(r.arrays["o"], expect)


def test_undersized_depth_rejected_by_name():
    prog, arrays, params = _pingpong_program()
    edge = "(pe0 -> pe1, 's', shared=1)"
    with pytest.raises(
        fifolib.FifoUnsupportedError, match="undersized FIFO depth 0"
    ) as exc:
        simulator.simulate(
            prog, _copies(arrays), params,
            sim=simulator.SimParams(fifo_depth=0),
        )
    assert edge in str(exc.value)
    with pytest.raises(
        fifolib.FifoUnsupportedError, match="undersized FIFO depth 0"
    ) as exc:
        executor.build_wave_plan(
            prog, _copies(arrays), params, fifo_depth=0
        )
    assert edge in str(exc.value)


# ---------------------------------------------------------------------------
# static rejection diagnostics (never interpreted; shapes the token
# protocol cannot express must fail loudly with every edge named)
# ---------------------------------------------------------------------------


def _cyclic_program():
    """x and y stream into each other's PE: a 2-cycle in the edge graph
    deadlocks for any finite depth (no initial tokens)."""
    return ir.Program(
        "fifo_cycle",
        loops=(
            ir.Loop("t", ir.Const(2), (
                ir.SetLocal("x", ir.Const(0.0)),
                ir.Loop("a", ir.Const(1), (
                    ir.SetLocal("x", ir.Local("y") + 1.0),
                )),
                ir.SetLocal("y", ir.Const(0.0)),
                ir.Loop("b", ir.Const(1), (
                    ir.SetLocal("y", ir.Local("x") * 1.0),
                )),
            )),
        ),
    )


def test_deadlock_cycle_names_every_edge():
    prog = _cyclic_program()
    dres = daelib.decouple(prog)
    assert len(dres.fifo_edges) == 2
    with pytest.raises(fifolib.FifoDeadlockError, match="deadlock") as exc:
        fifolib.analyze_program(prog, dres)
    msg = str(exc.value)
    for p, c, name, d in dres.fifo_edges:
        assert f"(pe{p} -> pe{c}, {name!r}, shared={d})" in msg


def test_simulator_fallback_names_full_edge_list():
    """The bugfix pin: the NotImplementedError fallback must name EVERY
    discovered edge ``(prod_pe -> cons_pe, local, depth)``, not a
    prefix — here two edges exist but only one is malformed."""
    prog = ir.Program(
        "fifo_derived_use",
        loops=(
            ir.Loop("t", ir.Const(2), (
                ir.SetLocal("x", ir.Const(0.0)),
                ir.Loop("p1", ir.Const(1), (
                    ir.SetLocal("x", ir.Var("t") * 1.0),
                )),
                ir.SetLocal("y", ir.Const(0.0)),
                ir.Loop("p2", ir.Const(1), (
                    ir.SetLocal("y", ir.Var("t") + 2.0),
                )),
                ir.Loop("c", ir.Const(1), (
                    ir.SetLocal("d", ir.Local("y") * 2.0),
                    ir.Store(
                        "st", "o", ir.Var("t"),
                        ir.Local("x") + ir.Local("d"),
                    ),
                )),
            )),
        ),
    )
    arrays = {"o": np.zeros(2, dtype=np.float64)}
    dres = daelib.decouple(prog)
    assert len(dres.fifo_edges) == 2
    with pytest.raises(NotImplementedError) as exc:
        simulator.simulate(prog, arrays, {})
    msg = str(exc.value)
    for p, c, name, d in dres.fifo_edges:
        assert f"(pe{p} -> pe{c}, {name!r}, shared={d})" in msg, (
            f"fallback diagnostic dropped edge {name!r}: {msg}"
        )
    assert "derived" in msg
    # the cyclic shape takes the same fallback, with its own diagnostic
    with pytest.raises(NotImplementedError, match="deadlock"):
        simulator.simulate(
            _cyclic_program(), {"o": np.zeros(2)}, {}
        )


def test_unsupported_shapes_rejected():
    # backward edge: consumer leaf precedes the producer leaf
    back = ir.Program(
        "fifo_backward",
        loops=(
            ir.Loop("t", ir.Const(2), (
                ir.Loop("c", ir.Const(1), (
                    ir.Store("st", "o", ir.Var("t"), ir.Local("x")),
                )),
                ir.SetLocal("x", ir.Const(0.0)),
                ir.Loop("p", ir.Const(1), (
                    ir.SetLocal("x", ir.Var("t") * 1.0),
                )),
            )),
        ),
    )
    with pytest.raises(fifolib.FifoUnsupportedError, match="backward"):
        fifolib.analyze_program(back, daelib.decouple(back))

    # rate mismatch: producer leaf is one level deeper than the shared
    # scope, so it would push more than once per consumer pop
    rate = ir.Program(
        "fifo_rate",
        loops=(
            ir.Loop("t", ir.Const(2), (
                ir.SetLocal("x", ir.Const(0.0)),
                ir.Loop("mid", ir.Const(2), (
                    ir.Loop("p", ir.Const(1), (
                        ir.SetLocal("x", ir.Var("mid") * 1.0),
                    )),
                )),
                ir.Loop("c", ir.Const(1), (
                    ir.Store("st", "o", ir.Var("t"), ir.Local("x")),
                )),
            )),
        ),
    )
    with pytest.raises(
        fifolib.FifoUnsupportedError, match="rates would diverge"
    ):
        fifolib.analyze_program(rate, daelib.decouple(rate))


# ---------------------------------------------------------------------------
# diagnostics bugfix sweep: multi-reason LossOfDecoupling + typed CU
# contract errors
# ---------------------------------------------------------------------------


def test_loss_of_decoupling_reports_every_reason():
    """The join bugfix pin: a program losing decoupling through TWO
    expressions at once (inner trip AND store address both depend on a
    protected load) must surface both reasons, '; '-joined."""
    prog = ir.Program(
        "lod_two_reasons",
        loops=(
            ir.Loop("i", ir.Const(3), (
                ir.Load("ld_n", "lens", ir.Var("i")),
                ir.Loop("k", ir.LoadVal("ld_n"), (
                    ir.Load("ld_v", "vals", ir.Var("k")),
                    ir.Store(
                        "st", "A",
                        ir.LoadVal("ld_n") + ir.Var("k"),
                        ir.LoadVal("ld_v"),
                    ),
                )),
            )),
        ),
    )
    with pytest.raises(daelib.LossOfDecoupling) as exc:
        daelib.decouple(prog, speculation="off")
    msg = str(exc.value)
    assert "; " in msg, f"reasons were not joined: {msg}"
    assert msg.count("loss of decoupling") == 2
    assert "trip" in msg and "address of op 'st'" in msg
    # and "auto" still accepts it, keeping both reasons on the SpecInfo
    dres = daelib.decouple(prog, speculation="auto")
    (spec,) = dres.spec.values()
    assert len(spec.reasons) == 2


def test_cu_contract_errors_are_typed():
    # feed on a load-free VecCU: the engine delivered a value no load
    # requested — a typed internal-contract error, not a bare assert
    prog, arrays, params = strat.random_loadfree_cu_program(
        np.random.default_rng(7)
    )
    dres = daelib.decouple(prog)
    pe = dres.pes[0]
    cu = daelib.make_cu(pe, arrays, params)
    assert type(cu).__name__ == "VecCU"
    with pytest.raises(daelib.CUContractError, match="load-free"):
        cu.feed(1.0, 0)
    assert issubclass(daelib.CUContractError, RuntimeError)

    # script-recording a FIFO-coupled PE is timing-dependent: rejected
    # with the same typed error (the DSE planner relies on this)
    sprog, sarrays, sparams = programs.get("filter_pipe").make(16)
    sdres = daelib.decouple(sprog)
    fifo_pe = next(p for p in sdres.pes if p.fifo_in)
    with pytest.raises(daelib.CUContractError, match="FIFO-coupled"):
        daelib.record_cu_script(fifo_pe, sarrays, sparams, {})


# ---------------------------------------------------------------------------
# random stream programs: deterministic tier-1 sweep + hypothesis
# wrapper (the nightly stream-fuzz job raises the example budget)
# ---------------------------------------------------------------------------


def check_stream_program(pa):
    prog, arrays, params = pa
    oracle = ir.interpret(prog, _copies(arrays), params)
    dres = daelib.decouple(prog)
    assert dres.fifo_edges, "generator produced a non-streaming program"
    for engine in ("cycle", "event"):
        res = simulator.simulate(
            prog, _copies(arrays), params, engine=engine
        )
        for k, v in oracle.items():
            np.testing.assert_array_equal(
                res.arrays[k], v,
                err_msg=f"{engine} engine diverged ({k})",
            )
        for qs in res.fifo_stats:
            assert qs["pushed"] == qs["popped"]
    for depth in (1, 3):
        r = executor.execute(
            prog, _copies(arrays), params, fifo_depth=depth
        )
        executor.validate_plan(r.plan)
        for k, v in oracle.items():
            np.testing.assert_array_equal(
                r.arrays[k], v,
                err_msg=f"wave executor (depth={depth}) diverged ({k})",
            )


@pytest.mark.parametrize("seed", range(0, 30, 2))
def test_stream_programs_seeded(seed):
    check_stream_program(
        strat.random_stream_program(np.random.default_rng(seed))
    )


if strat.HAVE_HYPOTHESIS:

    class TestStreamHypothesis:
        @given(strat.stream_programs())
        def test_differential(self, pa):
            check_stream_program(pa)
