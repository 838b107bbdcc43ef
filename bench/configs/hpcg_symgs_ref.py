"""Instances and plain reference for the ``hpcg_symgs`` configuration.

Numpy only: nothing here imports the system under test.

An instance is one call of HPCG's symmetric Gauss-Seidel smoother
(reference ``ComputeSYMGS_ref.cpp``) on level 0 of HPCG's multigrid
hierarchy: the 27-point operator of ``GenerateProblem_ref.cpp`` on the
``nx * ny * nz`` grid, the same matrix as the ``tanh_spmv``
configuration's (``tanh_spmv_ref.stencil``), laid out as CSR: ``rp`` the
row offsets, ``cols`` and ``val`` each row's columns and values in
ascending column order (HPCG's ``mtxIndL[i]`` and ``matrixValues[i]``),
``diag`` each row's diagonal value (``matrixDiagonal[i]``). The right
hand side ``r`` and the start vector ``x`` are drawn from ``rng``,
standard normal, as in ``ComputeMG_ref``'s post-smoothing call, which
runs on a nonzero ``x``; the matrix is the operator's, the same in every
call.

The reference is the smoother itself, in plain sequential Python: a
forward sweep over the rows, then a backward one, each row
``sum = r[i]; sum -= a_ij * x[j]`` over the row's nonzeros (the diagonal
among them), ``sum += x[i] * diag``, ``x[i] = sum / diag``, with ``x``
updated in place, in the reference's operation order.
"""

from __future__ import annotations

import importlib.util
import pathlib

import numpy as np

PROTECTED = ("x",)


def _tanh_spmv_ref():
    """``tanh_spmv_ref``, HPCG's 27-point stencil."""
    path = pathlib.Path(__file__).with_name("tanh_spmv_ref.py")
    spec = importlib.util.spec_from_file_location("_hpcg_symgs_stencil", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


hpcg = _tanh_spmv_ref()


def _level0(params):
    """The stencil's parameters for the finest level, the only one."""
    return {**params, "mg_levels": 1, "level": 0}


def sizes(params):
    """(rows, nonzeros) of the operator."""
    return hpcg.sizes(_level0(params))


def operator(params):
    """(rp, cols, val, diag) of the operator in CSR."""
    rows, cols, vals = hpcg.stencil(_level0(params))
    n = int(rows[-1]) + 1
    rp = np.zeros(n + 1, dtype=np.int64)
    rp[1:] = np.cumsum(np.bincount(rows, minlength=n))
    diag = vals[rows == cols]
    return rp, cols, vals, diag


def generate(params, rng, base):
    """One instance: (arrays, program params); ``r`` and ``x`` come from
    ``rng``, the operator from ``params`` (``base`` fixes nothing
    here)."""
    rp, cols, vals, diag = operator(params)
    n = len(rp) - 1
    arrays = {
        "x": rng.standard_normal(n),
        "r": rng.standard_normal(n),
        "rp": rp,
        "cols": cols,
        "val": vals,
        "diag": diag,
    }
    return arrays, {"n": n}


def reference(arrays, params, dtype=np.float64):
    """Final protected arrays of one sweep pair, computed in ``dtype``
    (float64 is the configuration's precision)."""

    def values(name):
        a = np.asarray(arrays[name], dtype=dtype)
        # Python floats are float64; other precisions keep numpy scalars
        return a.tolist() if dtype == np.float64 else list(a)

    x, r, val, diag = (values(k) for k in ("x", "r", "val", "diag"))
    rp, cols = arrays["rp"].tolist(), arrays["cols"].tolist()
    n = len(x)
    for i in [*range(n), *range(n - 1, -1, -1)]:
        s = r[i]
        for j in range(rp[i], rp[i + 1]):
            s -= val[j] * x[cols[j]]
        s += x[i] * diag[i]
        x[i] = s / diag[i]
    return {"x": np.asarray(x, dtype=dtype)}


def words(arrays, params):
    """Float64 words the sequential program reads or writes in protected
    arrays: per sweep and row, a load of ``x`` for each nonzero, a load
    of ``x[i]`` and a store of ``x[i]``."""
    return 2 * (len(arrays["val"]) + 2 * len(arrays["x"]))
