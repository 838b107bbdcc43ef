"""LoopIR program of the ``pagerank_rmat`` configuration.

The paper's Table-1 PageRank (arXiv:2501.14631, section 7.2), pull-CSR:
a contribution loop, an irregular in-edge gather whose ``acc[i]``
read-modify-write chain is as long as the vertex's in-degree, and a
damping loop, repeated ``iterations`` times. Built for one vertex count;
every instance of that size runs the same program on new arrays.
"""

from __future__ import annotations

from repro.core.loopir import (
    Const, Load, LoadVal, Loop, MonotonicHint, Param, Program, Read, Store,
    Var,
)


def build(params):
    nodes = 1 << params["scale"]
    iters = params["iterations"]
    sorted_in_row = MonotonicHint(True, None)
    return Program(
        name="pagerank",
        loops=(
            Loop("t", Param("iters", 0, iters), (
                Loop("i", Param("nodes", 0, nodes), (
                    Load("ld_rank", "rank", Var("i")),
                    Store(
                        "st_c", "contrib", Var("i"),
                        LoadVal("ld_rank") * Read("invdeg", Var("i")),
                    ),
                    Store("st_z", "acc", Var("i"), Const(0.0)),
                )),
                Loop("i2", Param("nodes", 0, nodes), (
                    Loop("e", Read("rp", Var("i2") + 1)
                         - Read("rp", Var("i2")), (
                        Load(
                            "ld_c", "contrib",
                            Read("cidx", Read("rp", Var("i2")) + Var("e")),
                            hint=sorted_in_row,
                        ),
                        Load("ld_acc", "acc", Var("i2")),
                        Store(
                            "st_acc", "acc", Var("i2"),
                            LoadVal("ld_acc") + LoadVal("ld_c"),
                        ),
                    )),
                )),
                Loop("i3", Param("nodes", 0, nodes), (
                    Load("ld_acc2", "acc", Var("i3")),
                    Store(
                        "st_rank", "rank", Var("i3"),
                        LoadVal("ld_acc2") * params["damping"]
                        + params["teleport"],
                    ),
                )),
            )),
        ),
        params=("iters", "nodes"),
    )
