"""End-to-end behaviour test: the fused pipeline reproduces the paper's
headline claim (cross-loop parallelism with exact semantics)."""

import numpy as np


def test_paper_headline_fused_speedup_and_exactness():
    """The paper's core claim, end to end: dynamic fusion runs dependent
    sibling loops concurrently (faster than sequential dynamic HLS)
    while preserving sequential semantics exactly."""
    from repro.core import loopir, programs, simulator

    prog, arrays, params = programs.get("RAWloop").make(512)
    oracle = loopir.interpret(prog, arrays, params)
    lsq = simulator.simulate(prog, arrays, params, mode="LSQ")
    fus = simulator.simulate(prog, arrays, params, mode="FUS2", validate=True)
    assert fus.cycles < 0.5 * lsq.cycles  # >2x over sequential dynamic HLS
    for k in oracle:
        np.testing.assert_allclose(fus.arrays[k], oracle[k], atol=1e-12)

