"""The ``hpcg_symgs`` configuration (HPCG's symmetric Gauss-Seidel
smoother): its operator is HPCG's level-0 27-point stencil, its plain
reference is ``ComputeSYMGS_ref`` transliterated and agrees with the
sequential oracle, the float32 control reads words off, and the cell
``symgs.l0`` resolves and runs through the harness."""

import ast
import json
import pathlib
import sys

import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import compare, harness, spec  # noqa: E402
from repro.core import loopir  # noqa: E402

CONFIGS = ROOT / "bench" / "configs"
PROG = spec.load_module(CONFIGS / "hpcg_symgs.py")
REF = spec.load_module(CONFIGS / "hpcg_symgs_ref.py")
PARAMS = json.loads((CONFIGS / "hpcg_symgs.json").read_text())["params"]
CASES = [(0, 0), (1, 0), (2**31 + 5, 0), (2**33 + 6, 0)]


def _instance(seed, dims=(4, 4, 4), k=0):
    params = {**PARAMS, **dict(zip(("nx", "ny", "nz"), dims))}
    arrays, pp = REF.generate(params, harness.instance_rng(seed, 0, k),
                              harness.base_rng(0))
    return params, arrays, pp


def _same_bits(got, want):
    for k, v in want.items():
        np.testing.assert_array_equal(
            np.asarray(got[k]).view(np.uint64), v.view(np.uint64), err_msg=k)


def _generate_problem(nx, ny, nz):
    """HPCG's ``GenerateProblem_ref`` loops for one process: per row its
    nonzeros' columns and values in the order the loops meet them, and
    its diagonal value."""
    n_in_row, ind, values, diagonal = [], [], [], []
    for iz in range(nz):
        for iy in range(ny):
            for ix in range(nx):
                row = iz * nx * ny + iy * nx + ix
                cols, vals = [], []
                for sz in (-1, 0, 1):
                    for sy in (-1, 0, 1):
                        for sx in (-1, 0, 1):
                            if 0 <= iz + sz < nz and 0 <= iy + sy < ny \
                                    and 0 <= ix + sx < nx:
                                col = row + sz * nx * ny + sy * nx + sx
                                cols.append(col)
                                vals.append(26.0 if col == row else -1.0)
                                if col == row:
                                    diagonal.append(26.0)
                n_in_row.append(len(cols))
                ind.append(cols)
                values.append(vals)
    return n_in_row, ind, values, diagonal


def _compute_symgs_ref(nrow, nonzeros_in_row, mtx_ind_l, matrix_values,
                       matrix_diagonal, rv, xv):
    """``ComputeSYMGS_ref``, statement for statement."""
    xv = list(xv)
    for i in range(nrow):
        current_values = matrix_values[i]
        current_col_indices = mtx_ind_l[i]
        current_number_of_nonzeros = nonzeros_in_row[i]
        current_diagonal = matrix_diagonal[i]
        sum_ = rv[i]
        for j in range(current_number_of_nonzeros):
            cur_col = current_col_indices[j]
            sum_ -= current_values[j] * xv[cur_col]
        sum_ += xv[i] * current_diagonal
        xv[i] = sum_ / current_diagonal
    for i in range(nrow - 1, -1, -1):
        current_values = matrix_values[i]
        current_col_indices = mtx_ind_l[i]
        current_number_of_nonzeros = nonzeros_in_row[i]
        current_diagonal = matrix_diagonal[i]
        sum_ = rv[i]
        for j in range(current_number_of_nonzeros):
            cur_col = current_col_indices[j]
            sum_ -= current_values[j] * xv[cur_col]
        sum_ += xv[i] * current_diagonal
        xv[i] = sum_ / current_diagonal
    return np.asarray(xv)


def test_level0_sizes():
    """The configuration's own operator: HPCG's 16^3 local grid."""
    assert REF.sizes(PARAMS) == (4096, 97336)
    rp, cols, val, diag = REF.operator(PARAMS)
    assert len(rp) == 4097 and rp[-1] == len(cols) == len(val) == 97336
    assert np.all(diag == PARAMS["diagonal"]) and len(diag) == 4096
    arrays = {"x": np.zeros(4096), "val": val}
    assert REF.words(arrays, PARAMS) == 211056


@pytest.mark.parametrize("dims", [(4, 4, 4), (5, 3, 2)])
def test_operator_is_generate_problem(dims):
    params = {**PARAMS, **dict(zip(("nx", "ny", "nz"), dims))}
    rp, cols, val, diag = REF.operator(params)
    n_in_row, ind, values, diagonal = _generate_problem(*dims)
    assert np.diff(rp).tolist() == n_in_row
    assert cols.tolist() == [c for row in ind for c in row]
    assert val.tolist() == [v for row in values for v in row]
    assert diag.tolist() == diagonal


@pytest.mark.parametrize("seed,base", CASES)
def test_reference_is_compute_symgs_ref(seed, base):
    params, arrays, _ = _instance(seed)
    n_in_row, ind, values, diagonal = _generate_problem(4, 4, 4)
    want = _compute_symgs_ref(len(n_in_row), n_in_row, ind, values, diagonal,
                              arrays["r"].tolist(), arrays["x"].tolist())
    got = REF.reference(arrays, params)
    assert set(got) == set(REF.PROTECTED)
    _same_bits(got, {"x": want})


@pytest.mark.parametrize("dims", [(4, 4, 4), (5, 3, 2)])
@pytest.mark.parametrize("seed,base", CASES[:2])
def test_reference_matches_oracle(seed, base, dims):
    params, arrays, pp = _instance(seed, dims)
    _same_bits(loopir.interpret(PROG.build(params), arrays, pp),
               REF.reference(arrays, params))


def test_calls_get_new_vectors_and_the_same_operator():
    _, a, _ = _instance(7, k=0)
    _, b, _ = _instance(7, k=1)
    _, c, _ = _instance(7, k=0)
    for name in ("x", "r"):
        assert not np.array_equal(a[name], b[name])
        np.testing.assert_array_equal(a[name], c[name])
    for name in ("rp", "cols", "val", "diag"):
        np.testing.assert_array_equal(a[name], b[name])


@pytest.mark.parametrize("seed,base", CASES[:2])
def test_words_count_the_sequential_requests(seed, base):
    params, arrays, pp = _instance(seed, (5, 3, 2))
    n = [0]

    def hook(op_id, addr, is_store, valid, value):
        n[0] += bool(valid) or not is_store

    loopir.interpret(PROG.build(params), arrays, pp, trace_hook=hook)
    assert REF.words(arrays, params) == n[0]


@pytest.mark.parametrize("seed,base", CASES)
def test_float32_control_reads_words_off(seed, base):
    params, arrays, _ = _instance(seed)
    assert compare.control_words_off(REF, params, arrays) > \
        compare.LIMITS["words_off"]


def test_reference_imports_no_program_code():
    tree = ast.parse((CONFIGS / "hpcg_symgs_ref.py").read_text())
    imported = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            imported |= {a.name.split(".")[0] for a in node.names}
        elif isinstance(node, ast.ImportFrom):
            imported.add((node.module or "").split(".")[0])
    assert imported <= {"__future__", "importlib", "pathlib", "numpy"}


def test_cell_is_in_the_benchmark():
    cell = spec.load_cell("symgs.l0")
    assert cell.config_name == "hpcg_symgs" and cell.chips == 1
    assert cell.traffic_name == "symgs_l0"
    assert {k: cell.traffic[k] for k in
            ("bases", "warmup_calls", "warmup_max", "pool")} == {
        "bases": 1, "warmup_calls": 3, "warmup_max": 8, "pool": 24}
    assert cell.params == PARAMS
    names = {m["name"] for m in cell.per_layer}
    assert {"local_deps_per_call", "local_deps_s", "wave_loop_roofline",
            "steps_per_call"} <= names
    assert not names & {"trace_stream_s", "spec_trace_s",
                        "spec_gates_per_call"}
    assert {m["name"] for m in cell.end_to_end} == {"call_s", "setup_s"}


def test_sound_run_is_correct():
    """A whole CPU run of the cell on a 4^3 grid through the harness,
    traced, with the new per-layer metrics read."""
    import time

    import jax

    cell = spec.load_cell("symgs.l0")
    cell.traffic = dict(cell.traffic, instance={"nx": 4, "ny": 4, "nz": 4},
                        pool=2)
    line = harness.run(cell, 2**31 + 11, 0.05, False, time.perf_counter(),
                       jax.devices()[0])
    assert line["correct"], line["checks"]
    assert line["attempted"] >= 1 and line["failed"] == 0
    assert set(line["metrics"]) == {"call_s", "setup_s"}
    run = harness.Run(cell=cell, device_kind="cpu", setup_s=0.0,
                      durations=[1.0], records=[], words=[], compiles=0)
    assert spec.metric_reader("local_deps_per_call")(run) == 2 * (1000 + 64)
    assert spec.metric_reader("local_deps_s")(run) > 0
