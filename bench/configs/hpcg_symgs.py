"""LoopIR program of the ``hpcg_symgs`` configuration.

HPCG's symmetric Gauss-Seidel smoother (reference
``ComputeSYMGS_ref.cpp``), loop for loop: a forward sweep over the rows,
then a backward one. Each row accumulates ``sum`` in a local, as the
reference keeps it in a register: ``sum = r[i]``, then over the row's
nonzeros (the diagonal among them) ``sum -= a_ij * x[col]``, then
``sum += x[i] * diag``, and the row stores ``x[i] = sum / diag``. ``x``
is updated in place, so a row reads neighbours its own sweep has
already rewritten, or not yet reached: loop-carried RAW and WAR
dependences through memory at data-dependent distances, and between the
two sweeps, the second of which runs backwards (row ``n - 1 - i``).
Each row's columns ascend, as the hints assert. Built for one grid;
every instance runs the same program on new vectors.
"""

from __future__ import annotations

from repro.core.loopir import (
    Bin, Load, LoadVal, Local, Loop, MonotonicHint, Param, Program, Read,
    SetLocal, Store, Var,
)


def sweep(tag, row, n):
    """One sweep, with its own ``sum<tag>`` as each of the reference's
    loops declares its own; ``row`` is the row index in the sweep's
    variable ``i<tag>``."""
    ld_x, ld_d, s = f"ld_x{tag}", f"ld_d{tag}", Local(f"sum{tag}")
    nz = Read("rp", row) + Var(f"j{tag}")
    ascending = MonotonicHint(True, None)
    return Loop(f"i{tag}", Param("n", 0, n), (
        SetLocal(s.name, Read("r", row)),
        Loop(f"j{tag}", Read("rp", row + 1) - Read("rp", row), (
            Load(ld_x, "x", Read("cols", nz, 0, n - 1), hint=ascending),
            SetLocal(s.name, s - Read("val", nz) * LoadVal(ld_x)),
        )),
        Load(ld_d, "x", row),
        SetLocal(s.name, s + LoadVal(ld_d) * Read("diag", row)),
        Store(f"st_x{tag}", "x", row, Bin("/", s, Read("diag", row))),
    ))


def build(params):
    n = params["nx"] * params["ny"] * params["nz"]
    return Program(
        name="hpcg_symgs",
        loops=(
            sweep("f", Var("if"), n),
            sweep("b", Param("n", 0, n) - 1 - Var("ib"), n),
        ),
        params=("n",),
    )
