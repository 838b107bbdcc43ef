"""Speculative AGU with rollback-free squash (DESIGN.md §10).

Pins the loss-of-decoupling speculation subsystem end to end:

  * the four load-dependent kernels (``programs.SPEC_KERNELS``) run
    under ``speculation="auto"`` in every mode x engine, bit-identical
    to ``loopir.interpret`` AND to the independent numpy oracles in
    ``kernels/dynloop/ref.py``,
  * the predictor-conformance matrix: every ``dae.PREDICTORS`` value
    x both engines x every speculative kernel is arrays-exact, with
    engine cycle counts inside the documented drift envelope — the
    predictor knob moves *time*, never *values*,
  * the ``predictor`` knob is inert where speculation never fires:
    decoupled (Table-1) programs are bit-identical in cycles and
    arrays under every predictor value, and ``predictor="auto"``
    never loses to ``speculation="off"`` there,
  * ``SimResult.spec_stats`` has the documented shape (top-level,
    per-port and per-component-predictor counters),
  * ``speculation="off"`` still rejects, with diagnostics that name the
    consuming statement (op id / loop trip / AGU local) — the message
    shapes are part of the contract,
  * the §6 mis-speculation substrate speculation builds on: the
    interpreter's trace hook reports guarded-false stores with
    ``valid=False, value=None``; both engines preserve request
    existence for invalid stores (they occupy the stream and ACK
    without DRAM),
  * ``SpecPlan`` structure: epoch tags non-decreasing per stream,
    trigger/resolve consistency, predictor-zoo accounting (every
    occurrence either predicted or confidence-suppressed into a wait
    gate; phantoms only behind squash gates, capped by the run-ahead
    window),
  * the DSE axis: ``speculation`` expands in ``SweepSpec``; the result
    identity folds ``off``/``auto`` (and ``squash_latency``) for
    kernels that never speculate,
  * the random differential: generated load-dependent-trip programs
    plus stride-patterned and context-repeating pointer walks
    (tests/loopir_strategies.py) simulate oracle-exact in both engines
    under every predictor (deterministic seeds in tier-1; hypothesis
    strategies in the nightly predictor-fuzz job),
  * TABLE1 stays frozen at the paper's nine kernels (the registry may
    grow, the paper's evaluation set may not).
"""

import collections

import numpy as np
import pytest

import loopir_strategies as strat
from repro.core import dae as daelib
from repro.core import engine_event
from repro.core import executor
from repro.core import loopir as ir
from repro.core import programs
from repro.core import schedule as schedlib
from repro.core import simulator
from repro.core import speculate
from repro.kernels.dynloop import ref as dynref

SCALES = {
    "spmv_ldtrip": 24, "bfs_front": 32, "chase_sum": 24,
    "strided_scan": 24,
}


def _simulate_spec(name, mode, engine, scale=None, **kw):
    prog, arrays, params = programs.get(name).make(scale or SCALES[name])
    res = simulator.simulate(
        prog, arrays, params, mode=mode, engine=engine,
        speculation="auto", validate=(mode != "STA"), **kw,
    )
    oracle = ir.interpret(prog, arrays, params)
    return res, oracle, (prog, arrays, params)


# ---------------------------------------------------------------------------
# kernel acceptance: every mode x engine, oracle- and ref-exact
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("engine", ("cycle", "event"))
@pytest.mark.parametrize("mode", ("STA", "LSQ", "FUS1", "FUS2"))
@pytest.mark.parametrize("name", programs.SPEC_KERNELS)
def test_spec_kernels_all_modes_oracle_exact(name, mode, engine):
    res, oracle, _ = _simulate_spec(name, mode, engine)
    for k in oracle:
        np.testing.assert_array_equal(res.arrays[k], oracle[k], err_msg=k)


@pytest.mark.parametrize("name", programs.SPEC_KERNELS)
def test_spec_kernels_match_independent_refs(name):
    prog, arrays, params = programs.get(name).make(SCALES[name])
    final = ir.interpret(prog, arrays, params)
    if name == "spmv_ldtrip":
        rowlen, y = dynref.spmv_ldtrip_ref(
            arrays["deg"], arrays["rp"], arrays["cidx"], arrays["val"],
            arrays["x"],
        )
        np.testing.assert_allclose(final["rowlen"], rowlen, atol=1e-12)
        np.testing.assert_allclose(final["y"], y, atol=1e-12)
    elif name == "bfs_front":
        foff, visit = dynref.bfs_front_ref(
            arrays["off0"], arrays["front"], arrays["nodeval"],
            len(arrays["visit"]),
        )
        np.testing.assert_allclose(final["foff"], foff, atol=1e-12)
        np.testing.assert_allclose(final["visit"], visit, atol=1e-12)
    elif name == "chase_sum":
        out = dynref.chase_sum_ref(
            arrays["nxt"], arrays["w"], params["steps"]
        )
        np.testing.assert_allclose(final["out"], out, atol=1e-12)
    else:  # strided_scan
        out = dynref.strided_scan_ref(
            arrays["ptr"], arrays["w"], params["n"]
        )
        np.testing.assert_allclose(final["out"], out, atol=1e-12)


@pytest.mark.parametrize("name", programs.SPEC_KERNELS)
def test_spec_kernels_rejected_without_speculation(name):
    prog, arrays, params = programs.get(name).make(SCALES[name])
    with pytest.raises(daelib.LossOfDecoupling, match="loss of decoupling"):
        simulator.simulate(prog, arrays, params)


@pytest.mark.parametrize("name", programs.SPEC_KERNELS)
def test_spec_kernel_engines_agree(name):
    rc, oracle, _ = _simulate_spec(name, "FUS2", "cycle")
    re_, _, _ = _simulate_spec(name, "FUS2", "event")
    for k in oracle:
        np.testing.assert_array_equal(rc.arrays[k], re_.arrays[k])
    assert rc.squashed == re_.squashed
    assert rc.dram_requests == re_.dram_requests
    # same drift envelope as test_engine_diff (DESIGN.md §1.2)
    assert abs(rc.cycles - re_.cycles) <= max(2, int(0.02 * rc.cycles))


# ---------------------------------------------------------------------------
# predictor-conformance matrix: every predictor x both engines x every
# speculative kernel — arrays oracle-exact, engines agree on squash
# accounting and stay inside the cycle drift envelope
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("predictor", daelib.PREDICTORS)
@pytest.mark.parametrize("name", programs.SPEC_KERNELS)
def test_predictor_conformance_matrix(name, predictor):
    rc, oracle, _ = _simulate_spec(name, "FUS2", "cycle", predictor=predictor)
    re_, _, _ = _simulate_spec(name, "FUS2", "event", predictor=predictor)
    for k in oracle:
        np.testing.assert_array_equal(
            rc.arrays[k], oracle[k], err_msg=f"cycle/{predictor}/{k}"
        )
        np.testing.assert_array_equal(
            re_.arrays[k], oracle[k], err_msg=f"event/{predictor}/{k}"
        )
    # the predictor changes *when* gates resolve, never *what* commits:
    # both engines see the same gate schedule, hence the same squashes
    assert rc.squashed == re_.squashed
    assert rc.dram_requests == re_.dram_requests
    assert abs(rc.cycles - re_.cycles) <= max(2, int(0.02 * rc.cycles))
    assert rc.spec_stats["predictor"] == predictor
    assert re_.spec_stats["predictor"] == predictor


@pytest.mark.parametrize("name", programs.TABLE1)
def test_auto_predictor_never_loses_to_off_on_table1(name):
    """Decoupled kernels never open a gate, so the full zoo under
    ``auto`` costs exactly zero cycles over ``speculation="off"``."""
    scale = max(8, programs.get(name).default_scale // 8)
    prog, arrays, params = programs.get(name).make(scale)
    off = simulator.simulate(prog, arrays, params, speculation="off")
    auto = simulator.simulate(
        prog, arrays, params, speculation="auto", predictor="auto"
    )
    assert auto.cycles <= off.cycles
    assert auto.cycles == off.cycles  # stronger: a strict no-op
    assert auto.squashed == 0 and auto.spec_stats == {}
    for k in off.arrays:
        np.testing.assert_array_equal(off.arrays[k], auto.arrays[k])


@pytest.mark.parametrize("predictor", daelib.PREDICTORS)
def test_predictor_knob_inert_without_speculation(predictor):
    """Regression: on non-speculative programs every ``predictor=``
    value is bit-identical — the knob must not leak into decoupled
    scheduling."""
    prog, arrays, params = programs.get("RAWloop").make(48)
    base = simulator.simulate(prog, arrays, params)
    for spec in ("off", "auto"):
        res = simulator.simulate(
            prog, arrays, params, speculation=spec, predictor=predictor
        )
        assert res.cycles == base.cycles
        assert res.spec_stats == {}
        for k in base.arrays:
            np.testing.assert_array_equal(res.arrays[k], base.arrays[k])


def test_spec_stats_shape():
    """``SimResult.spec_stats`` is evidence surfaced to benchmarks and
    DSE rows — its key set (top-level, per-port, per-component) is a
    contract, pinned here for both engines."""
    top = {
        "predictor", "runahead", "predictions", "mispredictions",
        "wait_gates", "squash_gates", "gates", "phantom_requests",
        "phantom_capped", "cap_hits", "per_port", "by_predictor",
    }
    per_port = {"predictor", "predictions", "mispredictions", "waits"}
    by_pred = {"mispredictions", "wait_gates", "squashed", "cap_hits"}
    for engine in ("cycle", "event"):
        res, _, _ = _simulate_spec(
            "chase_sum", "FUS2", engine, predictor="auto"
        )
        s = res.spec_stats
        assert set(s) == top, engine
        assert s["predictor"] == "auto"
        assert s["runahead"] == simulator.SimParams().spec_runahead
        assert s["gates"] == s["wait_gates"] + s["squash_gates"]
        assert s["per_port"] and all(
            set(p) == per_port for p in s["per_port"].values()
        )
        # auto runs a tournament: component names appear in the stats
        assert s["by_predictor"] and all(
            set(v) == by_pred for v in s["by_predictor"].values()
        )
        assert set(s["by_predictor"]) <= {"last", "stride", "context"}
        for p in s["per_port"].values():
            assert p["predictor"] in ("last", "stride", "context")
        # a fixed-predictor run reports that component only
        res1, _, _ = _simulate_spec(
            "chase_sum", "FUS2", engine, predictor="stride"
        )
        assert res1.spec_stats["predictor"] == "stride"
        assert set(res1.spec_stats["by_predictor"]) <= {"stride"}


def test_trace_modes_on_spec_programs():
    """interp and auto share the speculative path; compiled refuses."""
    prog, arrays, params = programs.get("spmv_ldtrip").make(16)
    a = simulator.simulate(
        prog, arrays, params, speculation="auto", trace_mode="auto"
    )
    b = simulator.simulate(
        prog, arrays, params, speculation="auto", trace_mode="interp"
    )
    assert a.cycles == b.cycles and a.squashed == b.squashed
    with pytest.raises(schedlib.TraceCompileError, match="speculative AGU"):
        simulator.simulate(
            prog, arrays, params, speculation="auto", trace_mode="compiled"
        )


def test_speculation_auto_is_noop_on_decoupled_programs():
    prog, arrays, params = programs.get("RAWloop").make(64)
    assert daelib.decouple(prog, speculation="auto").spec == {}
    off = simulator.simulate(prog, arrays, params)
    auto = simulator.simulate(prog, arrays, params, speculation="auto")
    assert off.cycles == auto.cycles
    assert auto.squashed == 0
    for k in off.arrays:
        np.testing.assert_array_equal(off.arrays[k], auto.arrays[k])


@pytest.mark.parametrize("name", programs.SPEC_KERNELS)
def test_executor_runs_spec_kernels(name):
    from repro.core import executor

    prog, arrays, params = programs.get(name).make(SCALES[name])
    ra = executor.execute(prog, arrays, params, speculation="auto")
    rb = executor.execute(prog, arrays, params, predictor="stride")
    # the executor admits loss-of-decoupling programs with no option
    # set: ``speculation`` is a simulate()-only field
    rd = executor.execute(prog, arrays, params)
    oracle = ir.interpret(prog, arrays, params)
    for k in oracle:
        np.testing.assert_array_equal(ra.arrays[k], oracle[k])
        np.testing.assert_array_equal(rd.arrays[k], ra.arrays[k])
    np.testing.assert_array_equal(ra.waves, rb.waves)
    np.testing.assert_array_equal(rd.waves, ra.waves)
    np.testing.assert_array_equal(rd.plan.req_step, ra.plan.req_step)


def _bfs_rmat(scale, seed):
    """An instance of the ``bfs_rmat`` benchmark configuration (Graph500
    kernel 2, bench/configs) at ``scale``."""
    import importlib.util
    import json
    import pathlib

    configs = pathlib.Path(__file__).resolve().parents[1] / "bench" / "configs"
    mods = {}
    for name in ("bfs_rmat", "bfs_rmat_ref"):
        spec = importlib.util.spec_from_file_location(
            f"_cfg_{name}", configs / f"{name}.py"
        )
        mods[name] = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mods[name])
    params = {
        **json.loads((configs / "bfs_rmat.json").read_text())["params"],
        "scale": scale,
    }
    arrays, pp = mods["bfs_rmat_ref"].generate(
        params, np.random.default_rng(seed), np.random.default_rng(seed + 1)
    )
    return mods["bfs_rmat"].build(params), arrays, pp


_GENERATORS = {
    "spec": (strat.random_spec_program, 2000),
    "stride": (strat.random_stride_spec_program, 3000),
    "context": (strat.random_context_spec_program, 4000),
}
# small instances of the decoupled registry kernels
_DECOUPLED_SCALES = {
    "RAWloop": 48, "WARloop": 48, "WAWloop": 48, "bnn": 8, "pagerank": 16,
    "fft": 32, "matpower": 8, "hist+add": 48, "tanh+spmv": 32,
    "stream_dot": 16, "filter_pipe": 16, "stream_join": 16,
}
# (id, make, AGU trace mode of the reference, speculative)
_STREAM_CASES = (
    [(f"kernel-{n}", lambda n=n: programs.get(n).make(SCALES[n]), "auto",
      True) for n in programs.SPEC_KERNELS]
    + [(f"bfs_rmat-{sc}-{sd}", lambda sc=sc, sd=sd: _bfs_rmat(sc, sd),
        "auto", True) for sc, sd in ((5, 0), (6, 1), (6, 2))]
    + [(f"{g}-{seed}",
        lambda g=g, seed=seed: _GENERATORS[g][0](
            np.random.default_rng(_GENERATORS[g][1] + seed)), "auto", True)
       for g in _GENERATORS for seed in range(4)]
    # the compiled AGU refuses FIFO consumers, so stream kernels are
    # held to the interpreted trace only
    + [(f"kernel-{n}-{mode}",
        lambda n=n: programs.get(n).make(_DECOUPLED_SCALES[n]), mode, False)
       for n in programs.TABLE1 for mode in ("interp", "compiled")]
    + [(f"kernel-{n}-interp",
        lambda n=n: programs.get(n).make(_DECOUPLED_SCALES[n]), "interp",
        False) for n in programs.STREAM_KERNELS]
)


@pytest.mark.parametrize(
    "make, mode, speculative", [c[1:] for c in _STREAM_CASES],
    ids=[c[0] for c in _STREAM_CASES],
)
def test_plan_stream_is_the_agu_trace(make, mode, speculative):
    """A plan takes its op/addr/kind stream from the oracle walk: that
    stream is the schedule-order merge of the AGU traces
    (``schedule.program_order_stream``), request for request, for
    decoupled programs under either AGU mode and for speculative ones
    (the post-squash stream). FIFO pseudo-requests (``~push``/``~pop``)
    are the plan's own and are left out. Every other plan array is a
    function of this stream and the walk's captures, and the executed
    arrays are the oracle's."""
    prog, arrays, params = make()
    dae = daelib.decouple(prog, speculation="auto")
    assert bool(dae.spec) == speculative
    res = executor.execute(prog, arrays, params)
    plan = res.plan
    op_l, addr_l, store_l = schedlib.program_order_stream(
        prog, dae, arrays, params, mode
    )
    real = np.array([not plan.op_ids[i].startswith("~")
                     for i in plan.req_op], dtype=bool)
    index = {o: i for i, o in enumerate(plan.op_ids)}
    assert int(real.sum()) == len(op_l) > 0
    np.testing.assert_array_equal(plan.req_op[real],
                                  [index[o] for o in op_l])
    np.testing.assert_array_equal(plan.req_addr[real], addr_l)
    np.testing.assert_array_equal(plan.req_store[real], store_l)
    np.testing.assert_array_equal(
        plan.req_flat[real], [plan.base[plan.op_array[o]] + a
                              for o, a in zip(op_l, addr_l)]
    )
    assert {o: n for o, n in plan.op_nreq.items()
            if n and not o.startswith("~")} == collections.Counter(op_l)
    oracle = ir.interpret(prog, arrays, params)
    for k in oracle:
        np.testing.assert_array_equal(res.arrays[k], oracle[k], err_msg=k)


# ---------------------------------------------------------------------------
# LossOfDecoupling diagnostics name the consuming statement
# ---------------------------------------------------------------------------


def test_lod_message_names_trip_consumer():
    prog, arrays, params = programs.get("spmv_ldtrip").make(8)
    with pytest.raises(
        daelib.LossOfDecoupling,
        match=r"trip of loop 'k' depends on protected load\(s\) \['ld_len'\]",
    ):
        daelib.decouple(prog)


def test_lod_message_names_local_and_its_consumer():
    prog, arrays, params = programs.get("chase_sum").make(8)
    with pytest.raises(
        daelib.LossOfDecoupling,
        match=(
            r"AGU local 'cur' \(SetLocal feeding address of op 'ld_nxt'\) "
            r"depends on protected load\(s\) \['ld_nxt'\]"
        ),
    ):
        daelib.decouple(prog)


def test_lod_message_names_address_consumer():
    loop = ir.Loop("i", ir.Const(4), (
        ir.Load("ld_a", "x", ir.Var("i")),
        ir.Load("ld_b", "x", ir.LoadVal("ld_a")),
    ))
    prog = ir.Program("addr", loops=(loop,))
    with pytest.raises(
        daelib.LossOfDecoupling,
        match=r"address of op 'ld_b' depends on protected load\(s\) \['ld_a'\]",
    ):
        daelib.decouple(prog)


def test_cross_pe_load_dependence_always_rejects():
    prog = ir.Program("xpe", loops=(
        ir.Loop("i", ir.Const(2), (ir.Load("ld_a", "x", ir.Var("i")),)),
        ir.Loop("j", ir.Const(2), (
            ir.Load("ld_b", "x", ir.LoadVal("ld_a")),
        )),
    ))
    # both modes name the real blocker — "off" must not promise an
    # auto that would just re-reject (the predicted port has to live
    # in the PE whose AGU consumes it)
    for mode in ("off", "auto"):
        with pytest.raises(daelib.LossOfDecoupling, match="cross-PE"):
            daelib.decouple(prog, speculation=mode)


@pytest.mark.parametrize("engine", ("cycle", "event"))
def test_mispredicted_bound_outside_its_array_does_not_fault(engine):
    """A row walk whose stride-predicted row id runs past the end of
    ``rp``: the phantom-trip estimate would read ``rp`` outside it. The
    bound is counted as gated, with no phantom tail; simulate() and the
    wave executor run the program oracle-exact."""
    from repro.core import executor

    n = 8
    u = ir.LoadVal("ld_u")
    x_at = ir.Read("rp", u) + ir.Var("e")
    prog = ir.Program("rows", loops=(
        ir.Loop("i", ir.Param("m", 0, 16), (
            ir.Load("ld_u", "idx", ir.Var("i")),
            ir.Loop("e", ir.Read("rp", u + 1) - ir.Read("rp", u), (
                ir.Load("ld_x", "x", x_at),
                ir.Store("st_x", "x", x_at, ir.LoadVal("ld_x") + 1.0),
            )),
        )),
    ), params=("m",))
    # rows 0..7 in order, then row 0: the stride predictor says row 8,
    # and rp[9] does not exist
    seq = [*range(n), 0]
    arrays = {
        "idx": np.array(seq, dtype=np.float64),
        "rp": np.arange(0, 2 * n + 1, 2, dtype=np.int64),
        "x": np.zeros(2 * n),
    }
    params = {"m": len(seq)}
    oracle = ir.interpret(prog, arrays, params)
    res = simulator.simulate(
        prog, arrays, params, mode="FUS2", engine=engine,
        speculation="auto", predictor="stride", validate=True,
    )
    assert res.spec_stats["mispredictions"] >= 1
    ex = executor.execute(prog, arrays, params, predictor="stride")
    for k in oracle:
        np.testing.assert_array_equal(res.arrays[k], oracle[k])
        np.testing.assert_array_equal(ex.arrays[k], oracle[k])


def test_self_bounding_trip_rejects_even_under_auto():
    from repro.core import executor

    prog = ir.Program("selftrip", loops=(
        ir.Loop("i", ir.Const(3), (
            ir.Loop("k", ir.LoadVal("ld_in"), (
                ir.Load("ld_in", "x", ir.Var("k")),
            )),
        )),
    ))
    with pytest.raises(daelib.LossOfDecoupling, match="cannot run ahead"):
        simulator.simulate(prog, {"x": np.zeros(4)}, {}, speculation="auto")
    # the wave executor raises the same documented rejection
    with pytest.raises(daelib.LossOfDecoupling, match="cannot run ahead"):
        executor.execute(prog, {"x": np.zeros(4)}, {}, speculation="auto")


def test_unrelated_keyerrors_are_not_masked_as_lod():
    """A typo'd Read array must surface as a plain KeyError, not be
    misattributed to the speculation subsystem's auto-reject."""
    prog = ir.Program("typo", loops=(
        ir.Loop("i", ir.Const(3), (
            ir.Load("ld_len", "lens", ir.Var("i")),
            ir.Loop("k", ir.LoadVal("ld_len"), (
                ir.Load("ld_x", "x", ir.Read("MISSING", ir.Var("k"))),
            )),
        )),
    ))
    arrays = {"lens": np.ones(3), "x": np.zeros(4)}
    with pytest.raises(KeyError, match="MISSING") as exc:
        simulator.simulate(prog, arrays, {}, speculation="auto")
    assert not isinstance(exc.value, daelib.LossOfDecoupling)


# ---------------------------------------------------------------------------
# §6 mis-speculation substrate (the contract speculation builds on)
# ---------------------------------------------------------------------------


def _guarded_program(n=8):
    prog = ir.Program("g", loops=(
        ir.Loop("i", ir.Param("n", 0, n), (
            ir.Load("ld_v", "v", ir.Var("i")),
            ir.Store(
                "st_v", "v", ir.Var("i"),
                ir.LoadVal("ld_v") * 2.0,
                guard=ir.Bin(">", ir.LoadVal("ld_v"), ir.Const(0.0)),
            ),
        )),
    ), params=("n",))
    v = np.array([1.0, -1.0, 2.0, -2.0, 3.0, -3.0, 4.0, -4.0][:n])
    return prog, {"v": v}, {"n": n}


def test_trace_hook_reports_invalid_stores():
    """§6: a guarded-false store is reported valid=False, value=None —
    the request exists even when the effect doesn't."""
    prog, arrays, params = _guarded_program()
    rows = []
    ir.interpret(
        prog, arrays, params,
        trace_hook=lambda *a: rows.append(a),
    )
    st = [r for r in rows if r[0] == "st_v"]
    assert len(st) == params["n"]  # every iteration produced a request
    for i, (_op, addr, is_store, valid, value) in enumerate(st):
        assert is_store and addr == i
        if i % 2 == 0:  # positive values: guard holds
            assert valid and value == arrays["v"][i] * 2.0
        else:
            assert valid is False and value is None


@pytest.mark.parametrize("engine", ("cycle", "event"))
def test_engines_preserve_invalid_request_existence(engine):
    """Both engines keep mis-speculated stores in the request stream:
    they issue, occupy the pending buffer, ACK without DRAM (Fig. 7)."""
    prog, arrays, params = _guarded_program()
    comp = simulator.Compiled(prog, forwarding=False)
    traces = schedlib.trace_program(prog, comp.dae, arrays, params)
    n = params["n"]
    assert traces["st_v"].n_req == n  # AGU emits all requests (§6)
    p = simulator.SimParams()
    if engine == "event":
        eng = engine_event.EventEngine(
            comp, traces, arrays, params, "FUS1", p
        )
        res = eng.run()
        port = eng.ports["st_v"]
        assert port.head == port.n == n  # all requests drained
        assert list(port.valid) == [i % 2 == 0 for i in range(n)]
    else:
        eng = simulator.Engine(comp, traces, arrays, params, "FUS1", p)
        res = eng.run()
        port = eng.ports["st_v"]
        assert port.exhausted and not port.pending
        assert port.acked_count == n
    # invalid stores never touched DRAM: store DRAM traffic = valid half
    assert res.dram_requests == n + n // 2
    oracle = ir.interpret(prog, arrays, params)
    np.testing.assert_array_equal(res.arrays["v"], oracle["v"])


# ---------------------------------------------------------------------------
# SpecPlan structure
# ---------------------------------------------------------------------------


def test_spec_plan_structure():
    prog, arrays, params = programs.get("spmv_ldtrip").make(32)
    dae = daelib.decouple(prog, speculation="auto")
    assert list(dae.spec) != []
    spec_out = []
    traces = schedlib.trace_program(
        prog, dae, arrays, params, spec_out=spec_out
    )
    plan = spec_out[0]
    assert isinstance(plan, speculate.SpecPlan)
    # every trip-load occurrence is either predicted or confidence-
    # suppressed into a wait gate — nothing falls through
    assert plan.predictions + plan.wait_gates == traces["ld_len"].n_req
    assert 0 < plan.mispredictions <= plan.predictions
    assert plan.n_gates == plan.mispredictions + plan.wait_gates
    assert plan.n_gates == len(plan.phantoms)
    # gate kinds partition the gates; phantoms only behind squashes
    kinds = [plan.gate_kind[g] for g in range(plan.n_gates)]
    assert kinds.count("squash") == plan.mispredictions
    assert kinds.count("wait") == plan.wait_gates
    for gid, lst in enumerate(plan.phantoms):
        if plan.gate_kind[gid] == "wait":
            assert lst == []
    # epoch tags are non-decreasing along every stream and only ever
    # point at allocated gates
    for op_id, g in plan.gates.items():
        assert len(g) == traces[op_id].n_req
        assert (np.diff(g) >= 0).all(), op_id
        assert g.max(initial=-1) < plan.n_gates
    # trigger/resolve consistency
    for gid, (op_id, k) in enumerate(plan.triggers):
        assert plan.resolve_of[op_id][k] == gid
    # phantom accounting matches the counters and respects the cap
    total = sum(c for lst in plan.phantoms for (_o, c, _s) in lst)
    assert total == plan.phantom_requests
    per_gate_op: dict = {}
    for gid, lst in enumerate(plan.phantoms):
        for op_id, c, _s in lst:
            per_gate_op[(gid, op_id)] = per_gate_op.get((gid, op_id), 0) + c
    assert all(c <= plan.runahead for c in per_gate_op.values())


def test_perfect_prediction_single_gate():
    """Uniform row lengths: only the cold-start prediction misses.

    Confidence gating shapes the trace: the cold miss (conf 4 -> 2)
    suppresses the next two occurrences into wait gates while the
    counter climbs back (3, then 4); the last three speculate and hit.
    """
    prog = ir.Program("uni", loops=(
        ir.Loop("i", ir.Const(6), (
            ir.Load("ld_len", "lens", ir.Var("i")),
            ir.Loop("k", ir.LoadVal("ld_len"), (
                ir.Load("ld_x", "x", ir.Var("k")),
            )),
        )),
    ))
    arrays = {"lens": np.full(6, 3.0), "x": np.zeros(8)}
    dae = daelib.decouple(prog, speculation="auto")
    spec_out = []
    schedlib.trace_program(prog, dae, arrays, {}, spec_out=spec_out)
    plan = spec_out[0]
    assert plan.predictions == 4  # occurrences 1, 4, 5, 6 speculate
    assert plan.mispredictions == 1  # 0.0 -> 3.0 cold start only
    assert plan.wait_gates == 2  # occurrences 2-3 suppressed
    assert plan.phantom_requests == 0  # under-prediction squashes nothing


# ---------------------------------------------------------------------------
# DSE axis
# ---------------------------------------------------------------------------


def test_result_key_folds_speculation_for_decoupled_kernels():
    from repro import dse

    a = dse.SweepPoint(kernel="RAWloop", scale=32, speculation="off")
    b = dse.SweepPoint(kernel="RAWloop", scale=32, speculation="auto")
    assert a.spec_class == b.spec_class == "-"
    assert a.result_key == b.result_key
    assert a.point_id != b.point_id  # still distinct requested points
    # squash_latency is projected out unless the point speculates
    c = dse.SweepPoint(
        kernel="RAWloop", scale=32, sim=(("squash_latency", 9),)
    )
    assert c.result_key == a.result_key
    d = dse.SweepPoint(kernel="spmv_ldtrip", scale=32, speculation="auto")
    e = dse.SweepPoint(
        kernel="spmv_ldtrip", scale=32, speculation="auto",
        sim=(("squash_latency", 9),),
    )
    assert d.spec_class == "auto"
    assert d.result_key != e.result_key


def test_result_key_folds_predictor_and_runahead():
    """The predictor/run-ahead axes share result identity with
    ``speculation``: folded to ``"-"`` wherever the knob cannot reach
    a gate, distinct where it can."""
    from repro import dse

    # non-speculating points: predictor and spec_runahead fold away
    a = dse.SweepPoint(kernel="RAWloop", scale=32, predictor="last")
    b = dse.SweepPoint(kernel="RAWloop", scale=32, predictor="context")
    assert a.predictor_class == b.predictor_class == "-"
    assert a.runahead_class == b.runahead_class == "-"
    assert a.result_key == b.result_key
    c = dse.SweepPoint(
        kernel="RAWloop", scale=32, sim=(("spec_runahead", 4),)
    )
    assert c.result_key == a.result_key
    # STA never consults the SpecPlan either, even on spec kernels
    s1 = dse.SweepPoint(
        kernel="spmv_ldtrip", scale=32, mode="STA",
        speculation="auto", predictor="last",
    )
    s2 = dse.SweepPoint(
        kernel="spmv_ldtrip", scale=32, mode="STA",
        speculation="auto", predictor="stride",
    )
    assert s1.predictor_class == s2.predictor_class == "-"
    assert s1.result_key == s2.result_key
    # speculating points: distinct predictors are distinct results...
    d = dse.SweepPoint(
        kernel="spmv_ldtrip", scale=32, speculation="auto",
        predictor="last",
    )
    e = dse.SweepPoint(
        kernel="spmv_ldtrip", scale=32, speculation="auto",
        predictor="stride",
    )
    assert d.predictor_class == "last" and e.predictor_class == "stride"
    assert d.result_key != e.result_key
    # ...and so are distinct run-ahead windows (default surfaces too)
    f = dse.SweepPoint(
        kernel="spmv_ldtrip", scale=32, speculation="auto",
        predictor="last", sim=(("spec_runahead", 4),),
    )
    assert d.runahead_class == simulator.SimParams().spec_runahead
    assert f.runahead_class == 4
    assert d.result_key != f.result_key


def test_planner_folds_predictor_axis_into_shared_runs():
    """A predictor sweep over {STA, FUS2} on a speculative kernel runs
    STA once: the planner groups by predictor *class*, so the four STA
    points share one group while FUS2 gets one per predictor."""
    from repro.dse import planner
    from repro.dse.spec import SweepSpec

    pts = SweepSpec(
        kernels=["spmv_ldtrip"], scales={"spmv_ldtrip": 16},
        modes=("STA", "FUS2"), speculations=("auto",),
        predictors=daelib.PREDICTORS,
    ).points()
    assert len(pts) == 2 * len(daelib.PREDICTORS)
    groups = planner.plan(pts)
    sta = [g for g in groups if all(r.rep.mode == "STA" for r in g.runs)]
    fus = [g for g in groups if all(r.rep.mode == "FUS2" for r in g.runs)]
    assert len(sta) == 1 and len(sta[0].runs) == 1  # one run serves all
    assert len(sta[0].runs[0].point_indices) == len(daelib.PREDICTORS)
    assert len(fus) == len(daelib.PREDICTORS)
    assert sorted(g.predictor for g in fus) == sorted(daelib.PREDICTORS)


def test_sweep_matches_standalone_on_spec_kernels():
    from repro import dse

    spec = dse.SweepSpec(
        kernels=["spmv_ldtrip", "bfs_front"],
        scales={"spmv_ldtrip": 16, "bfs_front": 24},
        modes=("STA", "FUS2"),
        speculations=("auto",),
        predictors=("last", "context"),
    )
    res = dse.sweep(spec, validate=True)
    for pr in res.points:
        p = pr.point
        prog, arrays, params = programs.get(p.kernel).make(p.scale)
        base = simulator.simulate(
            prog, arrays, params, mode=p.mode, sim=p.sim_params(),
            engine=p.engine, trace_mode=p.trace_mode,
            speculation=p.speculation, predictor=p.predictor,
        )
        assert base.cycles == pr.result.cycles, p
        assert base.squashed == pr.result.squashed
        for k in base.arrays:
            np.testing.assert_array_equal(base.arrays[k], pr.result.arrays[k])


# ---------------------------------------------------------------------------
# TABLE1 freeze (the paper's evaluation set may not silently grow)
# ---------------------------------------------------------------------------


def test_table1_is_frozen_and_registry_superset():
    assert programs.TABLE1 == (
        "RAWloop", "WARloop", "WAWloop", "bnn", "pagerank", "fft",
        "matpower", "hist+add", "tanh+spmv",
    )
    assert set(programs.TABLE1) <= set(programs.REGISTRY)
    # speculative kernels are registered but never in Table 1
    assert programs.SPEC_KERNELS != ()
    assert not set(programs.SPEC_KERNELS) & set(programs.TABLE1)
    for name in programs.TABLE1:
        assert not programs.REGISTRY[name].speculative


# ---------------------------------------------------------------------------
# random differential (nightly fuzz reuses the hypothesis wrapper)
# ---------------------------------------------------------------------------


def _check_spec_differential(pap):
    prog, arrays, params = pap
    dae = daelib.decouple(prog, speculation="auto")
    assert dae.spec, "generator must produce a speculative PE"
    with pytest.raises(daelib.LossOfDecoupling):
        daelib.decouple(prog)
    oracle = ir.interpret(prog, arrays, params)
    for engine in ("cycle", "event"):
        res = simulator.simulate(
            prog, arrays, params, mode="FUS2", engine=engine,
            speculation="auto", validate=True,
        )
        for k in oracle:
            np.testing.assert_array_equal(
                res.arrays[k], oracle[k], err_msg=f"{engine}/{k}"
            )


def _check_predictor_differential(pap):
    """Oracle-exactness under *every* predictor knob, both engines —
    the predictor changes the gate schedule, never the committed
    values (speculate.py's oracle-stream soundness argument)."""
    prog, arrays, params = pap
    dae = daelib.decouple(prog, speculation="auto")
    assert dae.spec, "generator must produce a speculative PE"
    oracle = ir.interpret(prog, arrays, params)
    for pred in daelib.PREDICTORS:
        for engine in ("cycle", "event"):
            res = simulator.simulate(
                prog, arrays, params, mode="FUS2", engine=engine,
                speculation="auto", predictor=pred, validate=True,
            )
            for k in oracle:
                np.testing.assert_array_equal(
                    res.arrays[k], oracle[k], err_msg=f"{pred}/{engine}/{k}"
                )


@pytest.mark.parametrize("seed", range(25))
def test_spec_differential_seeded(seed):
    _check_spec_differential(
        strat.random_spec_program(np.random.default_rng(2000 + seed))
    )


@pytest.mark.parametrize("seed", range(25))
def test_stride_predictor_differential_seeded(seed):
    _check_predictor_differential(
        strat.random_stride_spec_program(np.random.default_rng(3000 + seed))
    )


@pytest.mark.parametrize("seed", range(25))
def test_context_predictor_differential_seeded(seed):
    _check_predictor_differential(
        strat.random_context_spec_program(np.random.default_rng(4000 + seed))
    )


if strat.HAVE_HYPOTHESIS:
    from hypothesis import given

    @given(strat.spec_programs())
    def test_spec_differential(pap):
        _check_spec_differential(pap)

    @given(strat.stride_spec_programs())
    def test_stride_predictor_differential(pap):
        _check_predictor_differential(pap)

    @given(strat.context_spec_programs())
    def test_context_predictor_differential(pap):
        _check_predictor_differential(pap)
