"""LoopIR program of the ``tanh_spmv`` configuration.

The paper's Table-1 ``tanh+spmv`` (arXiv:2501.14631, section 7.2): a
guarded in-place ``tanh`` (the store fires only where the loaded value is
positive, section 6 valid bits) feeding a sorted-COO SpMV whose row
stream is asserted monotonic. Built for one operator of the HPCG
hierarchy (its grid at ``level``); every instance of that operator runs
the same program on new arrays.
"""

from __future__ import annotations

from repro.core.loopir import (
    Bin, Const, Load, LoadVal, Loop, MonotonicHint, Param, Program, Read,
    Store, Un, Var,
)


def build(params):
    level = params.get("level", 0)
    dims = [params[k] >> level for k in ("nx", "ny", "nz")]
    n = dims[0] * dims[1] * dims[2]
    nnz = (3 * dims[0] - 2) * (3 * dims[1] - 2) * (3 * dims[2] - 2)
    sorted_rows = MonotonicHint(True, None)
    row = Read("rows", Var("e"), 0, n - 1)
    return Program(
        name="tanh+spmv",
        loops=(
            Loop("i", Param("n", 0, n), (
                Load("ld_v", "v", Var("i")),
                Store(
                    "st_v", "v", Var("i"),
                    Un("tanh", LoadVal("ld_v")),
                    guard=Bin(">", LoadVal("ld_v"), Const(0.0)),
                ),
            )),
            Loop("e", Param("nnz", 0, nnz), (
                Load("ld_vv", "v", Read("cols", Var("e"), 0, n - 1)),
                Load("ld_y", "y", row, hint=sorted_rows),
                Store(
                    "st_y", "y", row,
                    LoadVal("ld_y") + Read("val", Var("e")) * LoadVal("ld_vv"),
                    hint=sorted_rows,
                ),
            )),
        ),
        params=("n", "nnz"),
    )
