"""Wave-plan property suite: the invariants every ``WavePlan`` must
hold, pinned over random executable programs (DESIGN.md §2).

The exact per-(PE, dep-edge) partition replaced a per-PE barrier (a
store used to wait on *every* prior load of its PE); these properties
are what make that replacement safe and worthwhile:

  * **topological waves** — every request sits strictly after its
    same-address RAW/WAR/WAW predecessors and (for stores) after every
    load request feeding its value/guard, asserted here *independently*
    of ``executor.validate_plan`` (which is also run — the two
    implementations check each other),
  * **intra-wave conflict-freedom** — a backend may execute a wave in
    any internal order,
  * **never worse than the barrier** — per request, the exact
    partition's wave index is <= the old per-PE-barrier partition's
    (reimplemented here from the pre-change sweep): exactness can only
    remove edges,
  * **step coarsening is semantics-free** — ``batch_waves=False``
    degenerates steps to waves and the executed arrays are bit-equal,
  * **execution is exact** — the numpy wave backend matches the
    sequential oracle bit for bit.

The suite runs a deterministic seed sweep in tier-1 even without
hypothesis; with hypothesis the same cores run under the shared
profiles (tier1 / nightly, tests/loopir_strategies.py — the nightly CI
fuzz job raises the budget via ``HYPOTHESIS_PROFILE=nightly``).

The file also carries the backend differential for the three kernels
the barrier used to serialize (matpower, pagerank, spmv_ldtrip):
numpy backend vs Pallas ``run_plan`` vs ``run_sequential`` at two
scales, arrays exact, plus a regression pin on their wave counts.
"""

import numpy as np
import pytest

import loopir_strategies as strat
from repro.core import dae as daelib
from repro.core import executor, loopir as ir, programs
from repro.kernels import wave_exec

if strat.HAVE_HYPOTHESIS:
    from hypothesis import given


def _build(pa, **kw):
    prog, arrays, params = pa
    return executor.build_wave_plan(
        prog, {k: v.copy() for k, v in arrays.items()}, params, **kw
    )


# ---------------------------------------------------------------------------
# property cores (plain functions: deterministic sweep + hypothesis)
# ---------------------------------------------------------------------------


def check_topological_waves(plan):
    """Every dependence edge crosses strictly increasing waves, redone
    from the request streams without touching the plan's own sweep."""
    waves = plan.req_wave
    last_store: dict[int, int] = {}  # flat addr -> wave of last store
    loads_since: dict[int, int] = {}  # flat addr -> max load wave since
    load_wave: dict[str, list[int]] = {}
    for i in range(plan.n_requests):
        a = int(plan.req_flat[i])
        w = int(waves[i])
        op_id = plan.op_ids[plan.req_op[i]]
        if plan.req_store[i]:
            assert w > last_store.get(a, -1), "store not after last store"
            assert w > loads_since.get(a, -1), "store not after WAR loads"
            k = int(plan.req_ordinal[i])
            for ld, rows in plan.dep_maps[op_id].items():
                m = int(rows[k])
                if m >= 0:
                    assert w > load_wave[ld][m], (
                        f"store {op_id} not after its feeding {ld} load"
                    )
                else:
                    assert not plan.req_valid[i]
            if plan.req_valid[i]:
                last_store[a] = w
                loads_since.pop(a, None)
            else:
                last_store[a] = max(last_store.get(a, -1), w)
        else:
            assert w > last_store.get(a, -1), "load not after last store"
            loads_since[a] = max(loads_since.get(a, -1), w)
            load_wave.setdefault(op_id, []).append(w)


def check_conflict_free_waves(plan):
    """Within one wave no two requests share an address unless both are
    loads."""
    store_addrs: dict[int, set] = {}
    load_addrs: dict[int, set] = {}
    for i in range(plan.n_requests):
        w, a = int(plan.req_wave[i]), int(plan.req_flat[i])
        if plan.req_store[i]:
            assert a not in store_addrs.setdefault(w, set()), (
                "two stores share (wave, address)"
            )
            assert a not in load_addrs.get(w, ()), (
                "store shares (wave, address) with a load"
            )
            store_addrs[w].add(a)
        else:
            assert a not in store_addrs.get(w, ()), (
                "load shares (wave, address) with a store"
            )
            load_addrs.setdefault(w, set()).add(a)


def barrier_partition_waves(plan) -> np.ndarray:
    """The pre-change per-PE-barrier partition, reimplemented: a store
    waits on the max wave of *every* prior load of its PE, not just the
    loads feeding it. The comparison baseline for the exactness win."""
    op_pe = daelib.decouple(plan.program).op_to_pe
    n = plan.n_requests
    waves = np.zeros(n, dtype=np.int64)
    last_store: dict[int, int] = {}
    loads_since: dict[int, int] = {}
    pe_load_wave: dict[int, int] = {}
    for i in range(n):
        a = int(plan.req_flat[i])
        op_id = plan.op_ids[plan.req_op[i]]
        if plan.req_store[i]:
            w = max(
                last_store.get(a, -1) + 1,
                loads_since.get(a, -1) + 1,
                pe_load_wave.get(op_pe[op_id], -1) + 1,
            )
            if plan.req_valid[i]:
                last_store[a] = w
                loads_since.pop(a, None)
            else:
                last_store[a] = max(last_store.get(a, -1), w)
        else:
            w = last_store.get(a, -1) + 1
            loads_since[a] = max(loads_since.get(a, -1), w)
            pe = op_pe[op_id]
            pe_load_wave[pe] = max(pe_load_wave.get(pe, -1), w)
        waves[i] = w
    return waves


def check_plan_properties(pa):
    plan = _build(pa)
    executor.validate_plan(plan)
    check_topological_waves(plan)
    check_conflict_free_waves(plan)
    # exactness can only remove dependence edges, so per request the
    # new wave index never exceeds the old barrier partition's
    old = barrier_partition_waves(plan)
    assert np.all(plan.req_wave <= old), (
        "exact partition worse than the per-PE barrier"
    )
    # batching is pure coarsening: turning it off degenerates steps to
    # waves and changes nothing else
    plan_nb = _build(pa, batch_waves=False)
    np.testing.assert_array_equal(plan_nb.req_wave, plan.req_wave)
    np.testing.assert_array_equal(plan_nb.req_step, plan_nb.req_wave)
    assert plan_nb.stats.n_steps == plan_nb.stats.n_waves
    assert plan.stats.n_steps <= plan.stats.n_waves
    executor.validate_plan(plan_nb)


def check_execution_exact(pa):
    prog, arrays, params = pa
    oracle = ir.interpret(
        prog, {k: v.copy() for k, v in arrays.items()}, params
    )
    for batch in (True, False):
        res = executor.execute(
            prog, {k: v.copy() for k, v in arrays.items()}, params,
            batch_waves=batch,
        )
        for k in oracle:
            np.testing.assert_array_equal(
                res.arrays[k], oracle[k],
                err_msg=f"numpy wave backend (batch_waves={batch}) "
                f"diverged from oracle ({k})",
            )


# ---------------------------------------------------------------------------
# deterministic tier-1 sweep (runs without hypothesis)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("seed", range(0, 40, 2))
def test_wave_plan_properties_seeded(seed):
    pa = strat.random_wave_program(np.random.default_rng(seed))
    check_plan_properties(pa)


@pytest.mark.parametrize("seed", range(1, 41, 2))
def test_wave_execution_exact_seeded(seed):
    pa = strat.random_wave_program(np.random.default_rng(seed))
    check_execution_exact(pa)


# ---------------------------------------------------------------------------
# hypothesis wrappers (budget from the shared tier1/nightly profiles)
# ---------------------------------------------------------------------------


if strat.HAVE_HYPOTHESIS:

    class TestWavePlanHypothesis:
        @given(strat.wave_programs())
        def test_plan_properties(self, pa):
            check_plan_properties(pa)

        @given(strat.wave_programs())
        def test_execution_exact(self, pa):
            check_execution_exact(pa)


# ---------------------------------------------------------------------------
# the three ex-serialized kernels: backend differential + wave-count pin
# ---------------------------------------------------------------------------

# two scales per kernel (small enough for interpret-mode Pallas in
# tier-1); the n_waves caps pin the exact partition's critical path —
# the old barrier produced ~n_requests/2 waves on these (parallelism
# 1.8-3.4x), so any regression toward it trips the cap immediately
FLOOR_KERNELS = {
    # (scale, wave cap): measured 27/29, 56/54, 15/17 — pinned at +~30%
    "matpower": ((16, 36), (32, 40)),
    "pagerank": ((24, 72), (48, 72)),
    "spmv_ldtrip": ((32, 20), (64, 24)),
}


@pytest.mark.parametrize("name", sorted(FLOOR_KERNELS))
def test_floor_kernel_backends_differential(name):
    bench = programs.get(name)
    spec = "auto" if bench.speculative else "off"
    for scale, wave_cap in FLOOR_KERNELS[name]:
        prog, arrays, params = bench.make(scale)
        oracle = ir.interpret(
            prog, {k: v.copy() for k, v in arrays.items()}, params
        )
        plan = executor.build_wave_plan(
            prog, arrays, params, speculation=spec
        )
        executor.validate_plan(plan)
        assert plan.stats.n_waves <= wave_cap, (
            f"{name}@{scale}: {plan.stats.n_waves} waves exceeds the "
            f"{wave_cap} regression cap — partition lost exactness"
        )
        res_np = executor.execute(
            prog, {k: v.copy() for k, v in arrays.items()}, params,
            speculation=spec,
        )
        res_pl = wave_exec.run_plan(plan, arrays)
        res_sq = wave_exec.run_sequential(plan, arrays, check=True)
        assert res_pl.complete and res_sq.complete
        for k in oracle:
            for label, got in (
                ("numpy", res_np.arrays[k]),
                ("pallas", res_pl.arrays[k]),
                ("sequential", res_sq.arrays[k]),
            ):
                np.testing.assert_array_equal(
                    got, oracle[k],
                    err_msg=f"{name}@{scale}: {label} backend diverged "
                    f"from oracle ({k})",
                )
