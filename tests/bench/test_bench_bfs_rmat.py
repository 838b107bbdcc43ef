"""The ``bfs_rmat`` configuration (Graph500 kernel 2): its plain reference
agrees with the sequential oracle, ``execute()`` runs the program on its
default path through the speculative AGU, the instances are the
specification's, and the cell's comparison rejects a wrong tie-break."""

import ast
import collections
import json
import pathlib
import sys
import types

import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import adapter, compare, harness, spec  # noqa: E402
from repro.core import executor, loopir, simulator  # noqa: E402
from repro.core.config import RunConfig  # noqa: E402

CONFIGS = ROOT / "bench" / "configs"
PROG = spec.load_module(CONFIGS / "bfs_rmat.py")
REF = spec.load_module(CONFIGS / "bfs_rmat_ref.py")
PAGERANK = spec.load_module(CONFIGS / "pagerank_rmat_ref.py")
PARAMS = json.loads((CONFIGS / "bfs_rmat.json").read_text())["params"]
# (seed, base) pairs over all four bases, seeds past 32 bits included
CASES = [(0, 0), (1, 1), (2**31 + 5, 2), (3, 3), (2**33 + 6, 0), (7, 3)]


def _instance(seed, base, scale):
    params = {**PARAMS, "scale": scale}
    arrays, pp = REF.generate(params, harness.instance_rng(seed, 0, 0),
                              harness.base_rng(base))
    return params, arrays, pp


def _same_bits(got, want):
    for k, v in want.items():
        np.testing.assert_array_equal(
            np.asarray(got[k]).view(np.uint64), v.view(np.uint64), err_msg=k)


@pytest.mark.parametrize("scale", [5, 6])
@pytest.mark.parametrize("seed,base", CASES)
def test_reference_matches_oracle(seed, base, scale):
    params, arrays, pp = _instance(seed, base, scale)
    want = REF.reference(arrays, params)
    assert set(want) == set(REF.PROTECTED)
    _same_bits(loopir.interpret(PROG.build(params), arrays, pp), want)


@pytest.mark.parametrize("backend", ["pallas", "numpy"])
@pytest.mark.parametrize("seed,base", CASES[:4])
def test_execute_default_path_matches_reference(seed, base, backend):
    """The benchmark's own call (``RunConfig(backend="pallas")``, on
    XLA's CPU backend here) and the numpy backend, with no speculation
    option set."""
    params, arrays, pp = _instance(seed, base, 6)
    config = adapter.CONFIG if backend == "pallas" else RunConfig()
    res = executor.execute(PROG.build(params), arrays, pp, config=config)
    _same_bits(res.arrays, REF.reference(arrays, params))
    assert res.plan.n_requests > 0


@pytest.mark.parametrize("engine", ["cycle", "event"])
def test_mispredicted_row_outside_rp_does_not_fault(engine):
    """The SCALE-6 search whose predicted vertex id once indexed ``rp``
    past its end in the phantom-trip estimate: simulate() in both
    engines and execute() run it oracle-exact."""
    params, arrays, pp = _instance(3, 3, 6)
    prog = PROG.build(params)
    oracle = loopir.interpret(prog, arrays, pp)
    res = simulator.simulate(prog, arrays, pp, mode="FUS2", engine=engine,
                             speculation="auto")
    _same_bits(res.arrays, oracle)
    assert res.spec_stats["gates"] > 0
    _same_bits(adapter.call(prog, arrays, pp).arrays, oracle)


@pytest.mark.parametrize("seed,base", CASES)
def test_root_has_a_neighbour_and_levels_end_on_an_empty_frontier(seed,
                                                                  base):
    params, arrays, pp = _instance(seed, base, 6)
    rp, root = arrays["rp"], int(arrays["queue"][0])
    assert rp[root + 1] > rp[root]
    assert arrays["parent"][root] == root
    foff = REF.reference(arrays, params)["foff"]
    assert len(foff) == len(rp) and not np.any(arrays["foff"])
    sizes = np.diff(foff[:pp["levels"] + 1])
    # every level but the last has a frontier; the last finds it empty,
    # and the offsets past it stay 0
    assert np.all(sizes[:-1] > 0) and sizes[-1] == 0
    assert not np.any(foff[pp["levels"] + 1:])
    assert pp["levels"] == REF.search(rp, arrays["cidx"], root)[0] + 1


def test_roots_are_drawn_among_vertices_of_degree_one_or_more():
    """SCALE-5 graphs have isolated vertices; no root is one, and the
    roots spread over the vertices."""
    params = {**PARAMS, "scale": 5}
    roots, isolated = set(), 0
    for k in range(64):
        arrays, _ = REF.generate(params, harness.instance_rng(11, 0, k),
                                 harness.base_rng(k % 4))
        deg = np.diff(arrays["rp"])
        root = int(arrays["queue"][0])
        assert deg[root] > 0
        roots.add(root)
        isolated += int(np.sum(deg == 0))
    assert isolated > 0 and len(roots) > 16


@pytest.mark.parametrize("seed,base", CASES[:4])
def test_bases_are_the_pagerank_graphs(seed, base):
    """The four bases give the edge lists, and with the same labelling
    the same CSR, as ``pagerank_rmat``'s."""
    params = {**PARAMS, "scale": 6}
    arrays, _ = REF.generate(params, harness.instance_rng(seed, 0, 0),
                             harness.base_rng(base))
    pr_params = {**json.loads((CONFIGS / "pagerank_rmat.json").read_text())
                 ["params"], "scale": 6}
    pr, _ = PAGERANK.generate(pr_params, harness.instance_rng(seed, 0, 0),
                              harness.base_rng(base))
    np.testing.assert_array_equal(arrays["rp"], pr["rp"])
    np.testing.assert_array_equal(arrays["cidx"], pr["cidx"])
    a = REF.graph500.kronecker_edges(6, 16, 0.57, 0.19, 0.19,
                                     harness.base_rng(base))
    b = PAGERANK.kronecker_edges(6, 16, 0.57, 0.19, 0.19,
                                 harness.base_rng(base))
    assert all(np.array_equal(x, y) for x, y in zip(a, b))


@pytest.mark.parametrize("seed,base", CASES[:3])
def test_words_count_the_sequential_requests(seed, base):
    """``words`` equals the oracle walk's protected requests that read or
    write (guard-invalid stores write nothing)."""
    params, arrays, pp = _instance(seed, base, 6)
    n = [0]

    def hook(op_id, addr, is_store, valid, value):
        n[0] += bool(valid) or not is_store

    loopir.interpret(PROG.build(params), arrays, pp, trace_hook=hook)
    assert REF.words(arrays, params) == n[0]


def test_reference_imports_no_program_code():
    tree = ast.parse((CONFIGS / "bfs_rmat_ref.py").read_text())
    imported = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            imported |= {a.name.split(".")[0] for a in node.names}
        elif isinstance(node, ast.ImportFrom):
            imported.add((node.module or "").split(".")[0])
    assert imported <= {"__future__", "importlib", "pathlib", "numpy"}


def _last_discoverer(arrays, params):
    """The search with the other tie-break: a vertex's parent is the last
    frontier vertex that finds it, as an unordered parallel BFS may
    leave it."""
    want = REF.reference(arrays, params)
    parent = want["parent"].copy()
    rp, cidx, queue, foff = arrays["rp"], arrays["cidx"], want["queue"], \
        want["foff"]
    for t in range(len(foff) - 1):
        level = set(queue[int(foff[t + 1]):int(foff[t + 2])].astype(int)) \
            if t + 2 < len(foff) else set()
        for u in queue[int(foff[t]):int(foff[t + 1])].astype(int):
            for v in cidx[rp[u]:rp[u + 1]]:
                if v in level:
                    parent[v] = u
    return {**want, "parent": parent}


@pytest.mark.parametrize("seed,base", CASES[:4])
def test_comparison_rejects_the_other_tie_break(seed, base):
    """The exact comparison reads words off for a search that breaks ties
    the other way; the float32 control reads none, since every value is
    an integer below 2**24."""
    params, arrays, _ = _instance(seed, base, 6)
    want = REF.reference(arrays, params)
    assert compare.words_off(_last_discoverer(arrays, params), want) > \
        compare.LIMITS["words_off"]
    assert compare.control_words_off(REF, params, arrays) == 0


def test_cell_is_in_the_benchmark():
    cell = spec.load_cell("bfs.rmat")
    assert cell.config_name == "bfs_rmat" and cell.chips == 1
    assert {k: cell.traffic[k] for k in
            ("bases", "warmup_calls", "warmup_max", "pool")} == {
        "bases": 4, "warmup_calls": 4, "warmup_max": 8, "pool": 24}
    names = {m["name"] for m in cell.per_layer}
    assert {"spec_trace_s", "spec_gates_per_call", "wave_loop_roofline",
            "oracle_walk_s"} <= names
    assert len(names) == 26


def test_sound_run_is_correct():
    """A whole CPU run of the cell at SCALE 4 through the harness."""
    import time

    import jax

    cell = spec.load_cell("bfs.rmat")
    cell.traffic = dict(cell.traffic, instance={"scale": 4}, warmup_calls=4,
                        pool=2)
    line = harness.run(cell, 2**31 + 11, 0.05, False, time.perf_counter(),
                       jax.devices()[0])
    assert line["correct"], line["checks"]
    assert line["attempted"] >= 1 and line["failed"] == 0
    assert set(line["metrics"]) == {"call_s", "setup_s"}


@pytest.fixture
def spec_window(monkeypatch):
    """Two window calls whose speculative traces took 0.5 s and 1.5 s
    and opened 10 and 30 gates, after a warm-up call."""
    from repro import trace

    def tally(seconds, gates):
        return {"repro.execute": [2 * seconds, 1, {}],
                "repro.plan.spec": [seconds, 1, {"gates": gates,
                                                 "requests": 7}]}

    monkeypatch.setattr(trace, "RECENT", collections.deque(
        [tally(9.0, 99), tally(0.5, 10), tally(1.5, 30)]))
    return types.SimpleNamespace(durations=[1.0, 3.0], records=[{}, {}])


def test_spec_readers_mean_the_window_calls(spec_window):
    assert spec.metric_reader("spec_trace_s")(spec_window) == 1.0
    assert spec.metric_reader("spec_gates_per_call")(spec_window) == 20.0


def test_spec_readers_read_nothing_without_the_span(spec_window):
    """A program that opens no ``repro.plan.spec`` span (a decoupled
    program, or a tree without the span) gives the readers nothing."""
    from repro import trace

    for t in trace.RECENT:
        del t["repro.plan.spec"]
    assert spec.metric_reader("spec_trace_s")(spec_window) is None
    assert spec.metric_reader("spec_gates_per_call")(spec_window) is None
    spec_window.durations = []
    assert spec.metric_reader("spec_gates_per_call")(spec_window) is None
