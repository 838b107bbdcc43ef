"""Pallas wave-backend parity: the hardware execution path against the
simulator and the sequential oracle, across kernel × speculation, plus
the WavePlan contract and the op-table factoring.

This is the conformance suite the backend's claim rests on (DESIGN.md
§2): "Pallas hardware path agrees with simulate()" — final arrays
bit-identical (assert_array_equal, not allclose), wave counts pinned
against ``executor.WaveStats``, §6 valid bits recomputed from op-table
guards and matched request-exact.

Scales are small (interpret-mode Pallas runs one kernel call per wave);
the full paper_table1 scales run nightly via
``benchmarks/bench_pallas.py`` (BENCH_PALLAS.json).
"""

import numpy as np
import pytest

from repro.core import executor, loopir as ir, optable, programs, simulator
from repro.kernels import wave_exec

SCALES = {
    "RAWloop": 96, "WARloop": 96, "WAWloop": 96,
    "bnn": 12, "pagerank": 16, "fft": 32, "matpower": 12,
    "hist+add": 96, "tanh+spmv": 64,
    "spmv_ldtrip": 24, "bfs_front": 48, "chase_sum": 32,
    "strided_scan": 24,
}

ALL_KERNELS = tuple(programs.TABLE1) + tuple(programs.SPEC_KERNELS)


def _make(name):
    bench = programs.get(name)
    prog, arrays, params = bench.make(SCALES[name])
    spec = "auto" if bench.speculative else "off"
    return prog, arrays, params, spec


# ---------------------------------------------------------------------------
# every kernel through the device path, arrays exact + waves pinned
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("name", ALL_KERNELS)
def test_pallas_backend_matrix(name):
    prog, arrays, params, spec = _make(name)
    oracle = ir.interpret(prog, arrays, params)
    sim = simulator.simulate(prog, arrays, params, mode="FUS2",
                             engine="event", speculation=spec)
    res = executor.execute(
        prog, arrays, params, speculation=spec, backend="pallas",
    )
    for k in oracle:
        np.testing.assert_array_equal(
            res.arrays[k], oracle[k],
            err_msg=f"{name}: backend != oracle on {k}",
        )
    for k in sim.arrays:
        np.testing.assert_array_equal(
            res.arrays[k], sim.arrays[k],
            err_msg=f"{name}: backend != simulate() on {k}",
        )
    # wave counts pinned against WaveStats
    assert res.stats.n_waves == res.plan.stats.n_waves
    assert res.stats.n_requests == len(res.waves)


@pytest.mark.parametrize("name", ALL_KERNELS)
def test_wave_plan_contract(name):
    """The WavePlan invariants every backend relies on (executor doc)."""
    prog, arrays, params, spec = _make(name)
    plan = executor.build_wave_plan(prog, arrays, params, speculation=spec)
    executor.validate_plan(plan)
    # §6 reference valid bits: loads always valid, invalid stores NaN
    assert np.all(plan.req_valid[~plan.req_store])
    bad = plan.req_store & ~plan.req_valid
    assert np.all(np.isnan(plan.req_value[bad]))
    # flat layout covers exactly the protected arrays, disjointly
    total = sum(len(arrays[a]) for a in plan.array_order)
    assert plan.mem_size == total
    for a in plan.array_order:
        assert 0 <= plan.base[a] <= plan.mem_size - len(arrays[a])


@pytest.mark.parametrize("name", ["tanh+spmv", "pagerank", "chase_sum"])
def test_numpy_and_pallas_backends_agree(name):
    prog, arrays, params, spec = _make(name)
    a = executor.execute(prog, arrays, params, speculation=spec,
                         backend="numpy")
    b = executor.execute(prog, arrays, params, speculation=spec,
                         backend="pallas")
    for k in a.arrays:
        np.testing.assert_array_equal(a.arrays[k], b.arrays[k])
    assert a.stats.n_waves == b.stats.n_waves


def test_unknown_backend_rejected():
    prog, arrays, params, _ = _make("RAWloop")
    with pytest.raises(ValueError, match="unknown backend"):
        executor.execute(prog, arrays, params, backend="fpga")


def test_non_f64_protected_arrays_rejected_up_front():
    """The flat image computes in f64; a narrower protected array would
    diverge from the oracle in the last ulp — clear error, not a
    divergence assert deep in the wave loop. Unprotected (Read) arrays
    keep their dtype."""
    prog, arrays, params, _ = _make("RAWloop")
    arrays = dict(arrays, A=arrays["A"].astype(np.float32))
    with pytest.raises(ValueError, match="float64 protected arrays"):
        executor.build_wave_plan(prog, arrays, params)
    # d0 is Read-only: any dtype is fine
    arrays2, _ = dict(_make("RAWloop")[1]), None
    arrays2["d0"] = arrays2["d0"].astype(np.float32)
    res = executor.execute(prog, arrays2, params, backend="pallas")
    oracle = ir.interpret(prog, arrays2, params)
    np.testing.assert_array_equal(res.arrays["B"], oracle["B"])


# ---------------------------------------------------------------------------
# op tables: the compute bodies factored out of the oracle
# ---------------------------------------------------------------------------


def test_op_tables_partial_evaluation_shape():
    """tanh+spmv: guarded store keeps only LoadVal-reachable residue in
    the closure; the §6 guard compiles; LoadVal-free operands become
    env slots."""
    prog, _, _, _ = _make("tanh+spmv")
    tables = optable.compile_store_tables(prog)
    t = tables["st_v"]
    assert t.deps == ("ld_v",)
    assert t.guard is not None
    assert t.env_exprs == ()  # tanh(LoadVal) has no CU-side operands
    t2 = tables["st_y"]
    assert set(t2.deps) == {"ld_y", "ld_vv"}
    assert len(t2.env_exprs) == 1  # R(val, e) — captured, not recomputed


def test_op_tables_gather_residue():
    """bfs_front: a Read indexed by a LoadVal stays a closure gather
    against a frozen array."""
    prog, _, _, _ = _make("bfs_front")
    tables = optable.compile_store_tables(prog)
    t = tables["st_v"]
    assert "nodeval" in t.frozen_reads
    assert t.deps == ("ld_n",)


def test_op_tables_reject_mutable_gather():
    """A load-dependent Read of a store-target array has no frozen
    snapshot — documented OpTableError."""
    from repro.core.loopir import (
        Const, Load, LoadVal, Loop, Param, Program, Read, Store, Var,
    )

    prog = Program(
        name="bad",
        loops=(
            Loop("i", Param("n", 0, 4), (
                Load("ld", "a", Var("i")),
                # value gathers a["ld"] — but "a" is also stored below
                Store("st", "a", Var("i"),
                      Read("a", LoadVal("ld")) + Const(1.0)),
            )),
        ),
        params=("n",),
    )
    with pytest.raises(optable.OpTableError, match="frozen snapshot"):
        optable.compile_store_tables(prog)


def test_guard_protected_env_capture():
    """§6: the guard may be the bounds check that makes the value
    operands evaluable — env-slot capture must not crash on (and must
    mask) guard-false rows whose operands are out of range."""
    from repro.core.loopir import (
        Bin, Const, Load, LoadVal, Loop, Param, Program, Read, Store, Var,
    )

    prog = Program(name="guarded_oob", loops=(
        Loop("i", Param("n", 0, 5), (
            Load("ld", "src", Var("i")),
            Store("st", "out", Var("i"),
                  Read("tab", Var("i")) + LoadVal("ld"),
                  guard=Bin("<", Var("i"), Const(3.0))),
        )),
    ), params=("n",))
    arrays = {"out": np.zeros(5), "src": np.arange(5, dtype=np.float64),
              "tab": np.arange(3, dtype=np.float64)}  # len 3 < trip 5
    oracle = ir.interpret(prog, arrays, {"n": 5})
    for backend in ("numpy", "pallas"):
        res = executor.execute(prog, arrays, {"n": 5}, backend=backend)
        np.testing.assert_array_equal(res.arrays["out"], oracle["out"])


def test_backend_recomputes_guards_not_oracle():
    """The §6 valid bits the backend scatters with come from op-table
    guard evaluation; corrupting the plan's reference valid stream must
    trip the divergence check, proving the backend computed its own."""
    prog, arrays, params, _ = _make("tanh+spmv")
    plan = executor.build_wave_plan(prog, arrays, params)
    stores = np.nonzero(plan.req_store & ~plan.req_valid)[0]
    assert len(stores), "tanh+spmv must have guard-failed stores"
    plan.req_valid[stores[0]] = True  # corrupt the reference
    with pytest.raises(AssertionError, match="guard diverged"):
        wave_exec.run_plan(plan, arrays)


def test_jnp_compute_mode_close():
    """The same closures run under jax.numpy (accelerator dtype rules):
    tolerance parity, not bit parity — documented tradeoff."""
    prog, arrays, params, _ = _make("pagerank")
    plan = executor.build_wave_plan(prog, arrays, params)
    oracle = ir.interpret(prog, arrays, params)
    res = wave_exec.run_plan(plan, arrays, compute="jnp", check=False)
    for k in oracle:
        # f32 closure arithmetic under default jax config — tolerance
        # parity is the most this mode claims
        np.testing.assert_allclose(res.arrays[k], oracle[k],
                                   rtol=1e-5, atol=1e-6)


# ---------------------------------------------------------------------------
# sequential baseline path
# ---------------------------------------------------------------------------


def test_sequential_path_exact_and_truncatable():
    prog, arrays, params, _ = _make("hist+add")
    plan = executor.build_wave_plan(prog, arrays, params)
    oracle = ir.interpret(prog, arrays, params)
    full = wave_exec.run_sequential(plan, arrays, check=True)
    assert full.complete and full.n_steps == plan.stats.n_requests
    for k in oracle:
        np.testing.assert_array_equal(full.arrays[k], oracle[k])
    part = wave_exec.run_sequential(plan, arrays, max_steps=7)
    assert not part.complete and part.n_steps == 7


def test_wave_backend_empty_program():
    prog = ir.Program(name="empty", loops=(), params=())
    res = executor.execute(prog, {"a": np.zeros(4)}, {}, backend="pallas")
    assert res.stats.n_requests == 0 and res.stats.n_waves == 0
    np.testing.assert_array_equal(res.arrays["a"], np.zeros(4))
