"""LoopIR program of the ``bfs_rmat`` configuration.

Graph500 kernel 2 (specification section 4): the reference's queue-based,
level-synchronous top-down breadth-first search. Level ``t`` scans the
frontier ``queue[foff[t]:foff[t+1]]``; each frontier vertex ``u`` is
loaded from ``queue``, its CSR row is scanned (the trip count depends on
that load), and each neighbour ``v`` whose ``parent`` is still -1 gets
``parent[v] = u`` and is appended to ``queue``. The queue tail is a
loop-carried local, as a datapath keeps a counter in a register, and is
published to ``foff[t+1]`` when level ``t`` starts. Trip counts and
addresses depend on values the program itself writes, so the program
loses decoupling and runs through the speculative AGU (DESIGN.md §10).
Built for one vertex count; every instance of that size runs the same
program on new arrays, with its own ``levels``.
"""

from __future__ import annotations

from repro.core.loopir import (
    Const, Load, LoadVal, Local, Loop, MonotonicHint, Param, Program, Read,
    SetLocal, Store, Var,
)


def build(params):
    nodes = 1 << params["scale"]
    sorted_in_row = MonotonicHint(True, None)
    u = LoadVal("ld_u")
    v = Read("cidx", Read("rp", u) + Var("e"))
    unvisited = LoadVal("ld_p") < 0
    return Program(
        name="bfs_rmat",
        loops=(
            Loop("o", Const(1), (
                SetLocal("tail", Const(1)),
                Loop("t", Param("levels", 1, nodes + 1), (
                    Store("st_f", "foff", Var("t") + 1, Local("tail")),
                    Load("ld_lo", "foff", Var("t")),
                    Load("ld_hi", "foff", Var("t") + 1),
                    Loop("k", LoadVal("ld_hi") - LoadVal("ld_lo"), (
                        Load("ld_u", "queue", LoadVal("ld_lo") + Var("k")),
                        Loop("e", Read("rp", u + 1) - Read("rp", u), (
                            Load("ld_p", "parent", v, hint=sorted_in_row),
                            Store("st_p", "parent", v, u, guard=unvisited),
                            Store("st_q", "queue", Local("tail"), v,
                                  guard=unvisited),
                            SetLocal("tail", Local("tail") + unvisited),
                        )),
                    )),
                )),
            )),
        ),
        params=("levels",),
    )
