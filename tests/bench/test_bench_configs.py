"""The benchmark's configurations: each generator and plain reference
agree with the sequential oracle (``loopir.interpret``) over several
seeds, and the reference modules import nothing of the program."""

import ast
import json
import pathlib
import sys

import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import harness, spec  # noqa: E402
from repro.core import loopir  # noqa: E402

CONFIGS = ["pagerank_rmat", "tanh_spmv"]
TINY = {"pagerank_rmat": {"scale": 5},
        "tanh_spmv": {"nx": 5, "ny": 4, "nz": 3}}


def _modules(name):
    base = ROOT / "bench" / "configs"
    return (spec.load_module(base / f"{name}.py"),
            spec.load_module(base / f"{name}_ref.py"))


def _params(name):
    data = json.loads((ROOT / "bench" / "configs" / f"{name}.json").read_text())
    return {**data["params"], **TINY[name]}


@pytest.mark.parametrize("name", CONFIGS)
@pytest.mark.parametrize("seed,base", [(0, 0), (1, 1), (2**31 + 5, 2),
                                       (3, 0), (2**31 + 6, 1)])
def test_reference_matches_oracle(name, seed, base):
    prog_mod, ref_mod = _modules(name)
    params = _params(name)
    arrays, pp = ref_mod.generate(
        params, harness.instance_rng(seed, 0, 0), harness.base_rng(base))
    oracle = loopir.interpret(prog_mod.build(params), arrays, pp)
    want = ref_mod.reference(arrays, params)
    assert set(want) == set(ref_mod.PROTECTED)
    for k, v in want.items():
        np.testing.assert_array_equal(
            v.view(np.uint64), oracle[k].view(np.uint64), err_msg=k
        )


@pytest.mark.parametrize("name", CONFIGS)
def test_same_seed_same_instance(name):
    _, ref_mod = _modules(name)
    params = _params(name)
    a, _ = ref_mod.generate(params, harness.instance_rng(7, 0, 3),
                            harness.base_rng(0))
    b, _ = ref_mod.generate(params, harness.instance_rng(7, 0, 3),
                            harness.base_rng(0))
    c, _ = ref_mod.generate(params, harness.instance_rng(7, 0, 4),
                            harness.base_rng(0))
    assert all(np.array_equal(a[k], b[k]) for k in a)
    assert not all(np.array_equal(a[k], c[k]) for k in a)


@pytest.mark.parametrize("name", CONFIGS)
def test_reference_imports_no_program_code(name):
    tree = ast.parse((ROOT / "bench" / "configs" / f"{name}_ref.py").read_text())
    imported = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            imported |= {a.name.split(".")[0] for a in node.names}
        elif isinstance(node, ast.ImportFrom):
            imported.add((node.module or "").split(".")[0])
    assert imported <= {"__future__", "numpy"}, imported


def test_graph500_edge_list_shape():
    _, ref_mod = _modules("pagerank_rmat")
    params = _params("pagerank_rmat")
    arrays, pp = ref_mod.generate(params, harness.instance_rng(1, 0, 0),
                                  harness.base_rng(0))
    n = 1 << params["scale"]
    assert pp == {"iters": params["iterations"], "nodes": n}
    rp, cidx = arrays["rp"], arrays["cidx"]
    assert rp[0] == 0 and rp[-1] == len(cidx)
    assert len(cidx) <= 2 * params["edgefactor"] * n
    assert np.all(np.diff(rp) >= 0)
    row = np.repeat(np.arange(n), np.diff(rp))
    # kernel 1's undirected graph: no self-loop, no duplicate (neighbours
    # strictly increasing within each row, which the hint needs sorted),
    # and every edge in both directions
    assert not np.any(row == cidx)
    for v in range(n):
        assert np.all(np.diff(cidx[rp[v]:rp[v + 1]]) > 0)
    pairs = set(zip(row.tolist(), cidx.tolist()))
    assert pairs == {(b, a) for a, b in pairs}
    deg = np.diff(rp)
    np.testing.assert_array_equal(arrays["invdeg"], 1.0 / np.maximum(deg, 1))
    # R-MAT skew: the largest degree is far above the mean
    assert deg.max() > 2 * deg.mean()


def test_undirected_keeps_each_distinct_edge_in_both_directions():
    _, ref_mod = _modules("pagerank_rmat")
    start = np.array([0, 1, 2, 2, 3, 1])
    end = np.array([1, 0, 2, 3, 0, 2])  # 0-1 twice, a self-loop at 2
    row, nbr = ref_mod.undirected(start, end, 4)
    assert list(zip(row.tolist(), nbr.tolist())) == [
        (0, 1), (0, 3), (1, 0), (1, 2), (2, 1), (2, 3), (3, 0), (3, 2)]


def test_kronecker_quadrant_shares():
    _, ref_mod = _modules("pagerank_rmat")
    rng = np.random.default_rng(0)
    src, dst = ref_mod.kronecker_edges(1, 20000, 0.57, 0.19, 0.19, rng)
    # at SCALE 1 each edge picks one quadrant; labels may be swapped by
    # the permutation, so compare the sorted shares
    share = np.sort(np.bincount(2 * src + dst, minlength=4) / len(src))
    np.testing.assert_allclose(share, [0.05, 0.19, 0.19, 0.57], atol=0.015)


@pytest.mark.parametrize("name", CONFIGS)
def test_words_count_the_sequential_requests(name):
    """``words`` equals the oracle walk's protected requests that read or
    write (guard-invalid stores write nothing)."""
    prog_mod, ref_mod = _modules(name)
    params = _params(name)
    arrays, pp = ref_mod.generate(params, harness.instance_rng(4, 0, 0),
                                  harness.base_rng(0))
    n = [0]

    def hook(op_id, addr, is_store, valid, value):
        n[0] += bool(valid) or not is_store

    loopir.interpret(prog_mod.build(params), arrays, pp, trace_hook=hook)
    assert ref_mod.words(arrays, params) == n[0]


def _structure(name, arrays):
    """What a base fixes: the sorted degrees of a graph; the whole
    matrix of an HPCG operator."""
    if name == "pagerank_rmat":
        return (sorted(np.diff(arrays["rp"])),)
    return tuple(arrays[k].tobytes() for k in ("rows", "cols", "val"))


@pytest.mark.parametrize("name", CONFIGS)
def test_one_base_gives_new_instances_of_one_structure(name):
    _, ref_mod = _modules(name)
    params = _params(name)
    a, _ = ref_mod.generate(params, harness.instance_rng(9, 0, 0),
                            harness.base_rng(2))
    b, _ = ref_mod.generate(params, harness.instance_rng(9, 0, 1),
                            harness.base_rng(2))
    c, _ = ref_mod.generate(params, harness.instance_rng(9, 0, 1),
                            harness.base_rng(3))
    assert _structure(name, a) == _structure(name, b)
    if name == "pagerank_rmat":  # bases are other graphs, a new labelling
        assert _structure(name, a) != _structure(name, c)
        assert not np.array_equal(a["cidx"], b["cidx"])
    else:  # one operator, a new vector
        assert _structure(name, a) == _structure(name, c)
        assert not np.array_equal(a["v"], b["v"])


def _hpcg_loops(nx, ny, nz):
    """HPCG's GenerateProblem_ref loops, transcribed: (rows, cols, vals)."""
    rows, cols, vals = [], [], []
    for iz in range(nz):
        for iy in range(ny):
            for ix in range(nx):
                r = iz * nx * ny + iy * nx + ix
                for sz in (-1, 0, 1):
                    if not 0 <= iz + sz < nz:
                        continue
                    for sy in (-1, 0, 1):
                        if not 0 <= iy + sy < ny:
                            continue
                        for sx in (-1, 0, 1):
                            if not 0 <= ix + sx < nx:
                                continue
                            c = r + sz * nx * ny + sy * nx + sx
                            rows.append(r)
                            cols.append(c)
                            vals.append(26.0 if c == r else -1.0)
    return rows, cols, vals


@pytest.mark.parametrize("dims,level", [((5, 4, 3), 0), ((16, 16, 16), 2),
                                        ((8, 4, 8), 1)])
def test_hpcg_stencil_matches_generate_problem(dims, level):
    _, ref_mod = _modules("tanh_spmv")
    params = {**_params("tanh_spmv"), **dict(zip(("nx", "ny", "nz"), dims)),
              "level": level}
    rows, cols, vals = ref_mod.stencil(params)
    want = _hpcg_loops(*(d >> level for d in dims))
    assert rows.tolist() == want[0] and cols.tolist() == want[1]
    assert vals.tolist() == want[2]
    assert ref_mod.sizes(params) == (rows[-1] + 1, len(rows))
    assert np.all(np.diff(rows) >= 0)  # sorted, as the row hint asserts


def test_hpcg_level_outside_the_hierarchy_is_refused():
    _, ref_mod = _modules("tanh_spmv")
    with pytest.raises(ValueError):
        ref_mod.grid({**_params("tanh_spmv"), "level": 4})


@pytest.mark.parametrize("seed", [0, 2**31 + 7, -3])
def test_window_takes_every_base_once_a_round(seed):
    bases = 5
    for r in range(3):
        got = [harness.base_of(seed, harness.WINDOW, r * bases + j, bases)
               for j in range(bases)]
        assert sorted(got) == list(range(bases))
    assert [harness.base_of(seed, harness.WARMUP, k, bases)
            for k in range(7)] == [0, 1, 2, 3, 4, 0, 1]
    first = [harness.base_of(seed, harness.WINDOW, k, bases) for k in range(10)]
    assert first == [harness.base_of(seed, harness.WINDOW, k, bases)
                     for k in range(10)]
