"""Float division in LoopIR and dataflow through intra-PE locals
(WavePlan contract 6, DESIGN.md §2.1).

``/`` is IEEE float64 true division on every evaluator: the
interpreter, the generated walk, and the op tables' numpy and jnp
closures. A store that reads a local the program accumulates loaded
values in (a register accumulator, ``optable.fed_locals``) depends on
exactly the loads the local's value was folded from: it sits in a later
step than each, ``validate_plan`` refuses a plan where it does not, and
the backends fold the value from their own gathers. HPCG's symmetric
Gauss-Seidel smoother (``bench/configs/hpcg_symgs.py``) runs through
``execute()`` on both backends bit-identical to its plain reference, in
at least as many steps as its dependence chains need. The plans of
programs without such a local are the ones the executor built before
contract 6 (``fixtures/plan_digests.json``), and ``bfs_rmat``'s differ
only by the edges of the one store that reads its queue tail."""

import copy
import hashlib
import json
import math
import pathlib
import sys

import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

from bench import harness, spec  # noqa: E402
from repro import trace  # noqa: E402
from repro.analysis import deps as depslib  # noqa: E402
from repro.core import executor, monotonic, optable, programs  # noqa: E402
from repro.core import loopir as ir  # noqa: E402
from repro.core.config import RunConfig  # noqa: E402

CONFIGS = ROOT / "bench" / "configs"
SYMGS = spec.load_module(CONFIGS / "hpcg_symgs.py")
SYMGS_REF = spec.load_module(CONFIGS / "hpcg_symgs_ref.py")
SYMGS_PARAMS = json.loads((CONFIGS / "hpcg_symgs.json").read_text())["params"]
DIGESTS = json.loads(
    (ROOT / "tests" / "fixtures" / "plan_digests.json").read_text())

SPECIAL = [2.0, -0.5, 0.0, -0.0, np.inf, -np.inf, np.nan]


def _bits_equal(got, want):
    got = np.asarray(got, dtype=np.float64)
    want = np.asarray(want, dtype=np.float64)
    nan = np.isnan(want)
    assert np.array_equal(np.isnan(got), nan)
    assert np.array_equal(got[~nan].view(np.uint64), want[~nan].view(np.uint64))


# ---------------------------------------------------------------------------
# "/" on every evaluator
# ---------------------------------------------------------------------------


def _division_program(n):
    """Loaded operands divided in a store, read-only operands divided
    into a local the store reads, and a division by a constant."""
    i = ir.Var("i")
    return ir.Program("div", loops=(ir.Loop("i", ir.Const(n), (
        ir.Load("ld_a", "a", i),
        ir.Load("ld_b", "b", i),
        ir.Store("st_q", "q", i, ir.LoadVal("ld_a") / ir.LoadVal("ld_b")),
        ir.SetLocal("t", ir.Read("ra", i) / ir.Read("rb", i)),
        ir.Store("st_t", "t", i, ir.Local("t") / 4.0),
    )),))


def _division_instance():
    a, b = (np.asarray(v, dtype=np.float64).reshape(-1)
            for v in np.meshgrid(SPECIAL, SPECIAL, indexing="ij"))
    n = len(a)
    return {"a": a, "b": b, "ra": a.copy(), "rb": b.copy(),
            "q": np.zeros(n), "t": np.zeros(n)}, a, b


def test_division_is_ieee_on_the_interpreter_and_the_generated_walk():
    arrays, a, b = _division_instance()
    prog = _division_program(len(a))
    with np.errstate(all="ignore"):
        want = a / b
    calls = {}
    for name, run in (
        ("interpret", lambda th: ir.interpret(prog, arrays, {}, th)),
        ("compiled", lambda th: ir.compile_walk(prog)(prog, arrays, {}, th)),
    ):
        seen = []
        out = run(lambda *args: seen.append(args))
        _bits_equal(out["q"], want)
        _bits_equal(out["t"], want / 4.0)
        calls[name] = seen
    for x, y in zip(calls["interpret"], calls["compiled"]):
        assert x[:4] == y[:4]
        assert (math.isnan(x[4]) and math.isnan(y[4])) or (
            x[4] == y[4] and math.copysign(1, x[4]) == math.copysign(1, y[4]))
    # Python's own / raises on these operands; the walk does not
    assert ir._binop("/", 1.0, 0.0) == np.inf
    assert ir._binop("/", -1.0, 0.0) == -np.inf
    assert math.isnan(ir._binop("/", 0.0, -0.0))
    assert ir._binop("/", 7, 2) == 3.5


@pytest.mark.parametrize("lib", ["np", "jnp"])
def test_division_agrees_in_the_op_tables(lib):
    arrays, a, b = _division_instance()
    table = optable.compile_store_tables(_division_program(len(a)))["st_q"]
    assert table.value == optable.CBin("/", optable.CDep("ld_a"),
                                       optable.CDep("ld_b"))
    got = table.eval_value({"ld_a": a, "ld_b": b}, [], {}, len(a), lib=lib)
    with np.errstate(all="ignore"):
        # every operand and quotient here is exact in float32 as well
        _bits_equal(np.asarray(got, dtype=np.float64), a / b)
    assert "/" in ir.NP_BINOPS and "/" in optable._jnp_tables()[0]


def test_division_certifies_nothing():
    """``/`` is not affine: an address that divides has no chain of
    recurrences, and its op is not proven conflict-free, where the same
    address by multiplication is."""
    i = ir.Var("i")

    def prog(addr):
        return ir.Program("p", loops=(ir.Loop("i", ir.Const(8), (
            ir.Store("st", "out", addr, ir.Const(1.0)),
        )),))

    div, mul = prog((i * 4) / 2), prog(i * 2)
    path = (div.loops[0],)
    assert monotonic.to_cr_or_none(div.loops[0].body[0].addr, path) is None
    assert monotonic.to_cr_or_none(mul.loops[0].body[0].addr, path) is not None
    assert depslib.symbolically_free_ops(mul) == {"st": True}
    assert depslib.symbolically_free_ops(div) == {"st": False}
    out = np.zeros(16)
    for p in (div, mul):
        np.testing.assert_array_equal(
            executor.execute(p, {"out": out}).arrays["out"],
            ir.interpret(p, {"out": out})["out"])


# ---------------------------------------------------------------------------
# dataflow through intra-PE locals
# ---------------------------------------------------------------------------


def _row_sums(rows, width):
    """``b[i] = 2 * sum(a[width*i : width*(i+1)])``, the sum held in a
    local, with no memory hazard between ``a`` and ``b``."""
    i, j = ir.Var("i"), ir.Var("j")
    prog = ir.Program("row_sums", loops=(ir.Loop("i", ir.Const(rows), (
        ir.SetLocal("acc", ir.Const(0.0)),
        ir.Loop("j", ir.Const(width), (
            ir.Load("ld", "a", i * width + j),
            ir.SetLocal("acc", ir.Local("acc") + ir.LoadVal("ld")),
        )),
        ir.Store("st", "b", i, ir.Local("acc") * 2.0),
    )),))
    rng = np.random.default_rng(3)
    return prog, {"a": rng.standard_normal(rows * width),
                  "b": np.zeros(rows)}


def test_store_reading_a_fed_local_waits_for_its_loads():
    rows, width = 6, 3
    prog, arrays = _row_sums(rows, width)
    plan = executor.build_wave_plan(prog, arrays, {})
    executor.validate_plan(plan)
    assert set(plan.local_maps) == {"st"}
    assert plan.tables["st"].locals == ("acc",) and not plan.tables["st"].deps
    ld = np.flatnonzero(plan.req_op == plan.op_ids.index("ld"))
    st = np.flatnonzero(plan.req_op == plan.op_ids.index("st"))
    for k, s in enumerate(st):
        feeding = ld[k * width:(k + 1) * width]
        assert plan.req_step[s] > plan.req_step[feeding].max()
        assert plan.req_wave[s] > plan.req_wave[feeding].max()
    assert trace.RECENT[-1]["repro.plan.locals"][2]["edges"] == rows * width
    np.testing.assert_array_equal(
        executor.execute(prog, arrays).arrays["b"],
        ir.interpret(prog, arrays)["b"])


def test_validate_plan_rejects_a_store_beside_its_local_loads():
    prog, arrays = _row_sums(6, 3)
    plan = executor.build_wave_plan(prog, arrays, {})
    broken = copy.deepcopy(plan)
    broken.req_wave = np.zeros_like(plan.req_wave)
    broken.req_step = np.zeros_like(plan.req_step)
    broken.stats.n_waves = broken.stats.n_steps = 1
    with pytest.raises(AssertionError, match="folded from"):
        executor.validate_plan(broken)
    # the same plan without its contract-6 edges is what a sweep blind to
    # them would emit: every request in wave 0
    assert plan.stats.n_waves == 2


def test_the_fold_computes_from_the_backends_gathers():
    """Drive a plan over an image whose ``a`` differs from the one the
    walk saw: the stores hold the fold of the new values, so the walk's
    numbers are not replayed."""
    prog, arrays = _row_sums(5, 4)
    plan = executor.build_wave_plan(prog, arrays, {})
    other = {**arrays, "a": arrays["a"] * 3.0 - 1.0}
    mem = executor.flat_image(plan, other)

    def mem_step(addr, write, sval):
        got = mem[addr]
        mem[addr[write]] = sval[write]
        return got

    executor.drive_plan(plan, mem_step, frozen=other, check=False)
    out = executor.unpack_image(plan, mem, other)
    _bits_equal(out["b"], ir.interpret(prog, other)["b"])
    assert not np.array_equal(out["b"], ir.interpret(prog, arrays)["b"])


def test_walkers_report_the_same_local_executions():
    params = {**SYMGS_PARAMS, "nx": 4, "ny": 3, "nz": 2}
    arrays, pp = SYMGS_REF.generate(params, harness.instance_rng(1, 0, 0),
                                    harness.base_rng(0))
    prog = SYMGS.build(params)
    tables = optable.compile_store_tables(prog)
    locs = optable.compile_local_tables(prog, tables)
    assert [ir.set_locals(prog)[k].name for k in locs] == \
        ["sumf"] * 3 + ["sumb"] * 3
    exprs = {k: t.env_exprs for k, t in locs.items()}
    seen = {"interpret": [], "compiled": []}
    ir.interpret(prog, arrays, pp, local_exprs=exprs,
                 local_hook=lambda *a: seen["interpret"].append(a))
    ir.compile_walk(prog, local_exprs=exprs)(
        prog, arrays, pp, local_hook=lambda *a: seen["compiled"].append(a))
    assert seen["interpret"] == seen["compiled"]
    rows, nnz = SYMGS_REF.sizes(params)
    assert len(seen["compiled"]) == 2 * (nnz + 2 * rows)


def test_streamed_locals_keep_their_fifo_path():
    """The streaming kernels' stores read their locals through FIFO pops
    only: no contract-6 edges, no fold."""
    for name in ("stream_dot", "filter_pipe", "stream_join"):
        prog, arrays, params = programs.get(name).make(16)
        plan = executor.build_wave_plan(prog, arrays, params)
        assert not plan.local_maps and not plan.local_tables, name
        assert plan.fifo_edges, name


# ---------------------------------------------------------------------------
# HPCG's symmetric Gauss-Seidel smoother
# ---------------------------------------------------------------------------


def _symgs(d, seed=2**31 + 9):
    params = {**SYMGS_PARAMS, "nx": d, "ny": d, "nz": d}
    arrays, pp = SYMGS_REF.generate(params, harness.instance_rng(seed, 0, 0),
                                    harness.base_rng(0))
    return params, arrays, pp


@pytest.mark.parametrize("backend", ["pallas", "numpy"])
@pytest.mark.parametrize("d", [4, 8])
def test_symgs_through_execute_matches_the_reference(d, backend):
    params, arrays, pp = _symgs(d)
    res = executor.execute(SYMGS.build(params), arrays, pp,
                           config=RunConfig(backend=backend))
    _bits_equal(res.arrays["x"], SYMGS_REF.reference(arrays, params)["x"])
    assert res.plan.n_requests == SYMGS_REF.words(arrays, params)
    assert set(res.plan.local_maps) == {"st_xf", "st_xb"}


def _symgs_depths(arrays):
    """(waves, step lower bound) of the smoother's request stream, rebuilt
    from the CSR: every store after each load its row's ``sum`` folds
    and after the same-address RAW, WAR and WAW order — the waves; and
    the longest chain of RAW and load-to-store edges alone, which a
    gather-before-scatter step cannot hold two links of — the steps."""
    rp, cols = arrays["rp"], arrays["cols"]
    n = len(rp) - 1
    last_store, loads_since = [-1] * n, [-1] * n
    store_depth = [-1] * n
    waves = steps = 0
    for i in [*range(n), *range(n - 1, -1, -1)]:
        row = [*cols[rp[i]:rp[i + 1]].tolist(), i]
        lw = [last_store[c] + 1 for c in row]
        ld = [store_depth[c] + 1 for c in row]
        for c, w in zip(row, lw):
            loads_since[c] = max(loads_since[c], w)
        w = max(max(lw) + 1, last_store[i] + 1, loads_since[i] + 1)
        last_store[i], loads_since[i] = w, -1
        store_depth[i] = max(ld) + 1
        waves, steps = max(waves, w + 1), max(steps, store_depth[i] + 1)
    return waves, steps


@pytest.mark.parametrize("d", [4, 8])
def test_symgs_takes_at_least_its_wavefront_in_steps(d):
    params, arrays, pp = _symgs(d)
    plan = executor.build_wave_plan(SYMGS.build(params), arrays, pp)
    executor.validate_plan(plan)
    waves, steps = _symgs_depths(arrays)
    assert plan.stats.n_waves == waves == {4: 88, 8: 200}[d]
    assert plan.stats.n_steps >= steps
    rows, nnz = SYMGS_REF.sizes(params)
    assert trace.RECENT[-1]["repro.plan.locals"][2]["edges"] == \
        2 * (nnz + rows)


# ---------------------------------------------------------------------------
# plans of the other configurations
# ---------------------------------------------------------------------------

# (configuration, parameter overrides, seed, base): test-size instances
PLAN_CASES = [
    ("pagerank_rmat", {"scale": 6}, 0, 0),
    ("pagerank_rmat", {"scale": 6}, 2**31 + 5, 2),
    ("tanh_spmv", {"nx": 5, "ny": 4, "nz": 3}, 1, 0),
    ("tanh_spmv", {"nx": 8, "ny": 8, "nz": 8, "level": 1}, 2**33 + 3, 0),
    ("bfs_rmat", {"scale": 6}, 0, 0),
    ("bfs_rmat", {"scale": 6}, 3, 3),
]


def _digest(a) -> str:
    a = np.ascontiguousarray(a)
    h = hashlib.sha256(f"{a.dtype.str}{a.shape}".encode())
    h.update(a.tobytes())
    return h.hexdigest()[:16]


def plan_digests(plan) -> dict:
    """A digest of every per-request array, op-table stream and dep map
    of a plan, and its counts."""
    out = {f: _digest(getattr(plan, f)) for f in (
        "req_op", "req_addr", "req_flat", "req_store", "req_valid",
        "req_value", "req_wave", "req_step", "req_ordinal")}
    for op_id, slots in sorted(plan.env.items()):
        for j, a in enumerate(slots):
            out[f"env.{op_id}.{j}"] = _digest(a)
    for op_id, per in sorted(plan.dep_maps.items()):
        for ld, a in sorted(per.items()):
            out[f"dep.{op_id}.{ld}"] = _digest(a)
    out["op_ids"] = ",".join(plan.op_ids)
    out["waves"], out["steps"] = plan.stats.n_waves, plan.stats.n_steps
    return out


def case_plan(name, over, seed, base, root=ROOT):
    """(plan, final arrays, reference) of one case, from the benchmark's
    own generator and program."""
    params = {**json.loads(
        (root / "bench" / "configs" / f"{name}.json").read_text()
    )["params"], **over}
    prog = spec.load_module(root / "bench" / "configs" / f"{name}.py")
    ref = spec.load_module(root / "bench" / "configs" / f"{name}_ref.py")
    arrays, pp = ref.generate(params, harness.instance_rng(seed, 0, 0),
                              harness.base_rng(base))
    res = executor.execute(prog.build(params), arrays, pp)
    return res.plan, res.arrays, ref.reference(arrays, params)


@pytest.mark.parametrize("case", range(len(PLAN_CASES)))
def test_plans_without_a_fed_store_are_unchanged(case, monkeypatch):
    """With no local taken for fed, every plan array equals the one the
    executor built before contract 6; so does the plan itself where no
    store reads a fed local. ``bfs_rmat``'s plan has contract-6 edges on
    ``st_f`` alone, the store of the queue tail: its request streams and
    dep maps are the same, and its waves only later."""
    name, over, seed, base = PLAN_CASES[case]
    plan, out, want = case_plan(name, over, seed, base)
    executor.validate_plan(plan)
    for k, v in want.items():
        _bits_equal(out[k], v)
    with monkeypatch.context() as m:
        m.setattr(optable, "fed_locals", lambda program: frozenset())
        plain = case_plan(name, over, seed, base)[0]
    assert plan_digests(plain) == DIGESTS[str(case)]
    got = plan_digests(plan)
    if name != "bfs_rmat":
        assert got == DIGESTS[str(case)]
        assert not plan.local_maps and not len(plan.local_stmt)
        return
    assert set(plan.local_maps) == {"st_f"}
    assert {t.name for t in plan.local_tables.values()} == {"tail"}
    moved = {"req_wave", "req_step", "env.st_f.0", "waves", "steps"}
    assert {k: v for k, v in got.items() if k not in moved} == {
        k: v for k, v in DIGESTS[str(case)].items() if k not in moved}
    assert np.all(plan.req_wave >= plain.req_wave)
    assert plan.stats.n_steps <= 1.02 * plain.stats.n_steps
