"""The device phase's segments (``wave_exec.ops.plan_segments``) and its
per-segment check.

The planner is a pure function of the plan's step widths: it covers
every step once and in order, gives each segment the bucket of its
widest step, and costs no more under its model (``_LAUNCH_LANES`` a
segment plus its padded lanes) than one segment per run of equal
buckets. The check compares every real lane's gathered bit pattern of
a segment against the resolve phase's at once, so a flipped bit in any
lane of any step still raises.
"""

import numpy as np
import pytest

from bench_programs import bench_program
from repro.core import executor, programs
from repro.kernels.wave_exec import kernel, ops

L = ops._LAUNCH_LANES


def _bucket(n):
    b = 8
    while b < n:
        b *= 2
    return b


def _pow2(n):
    p = 1
    while p < n:
        p *= 2
    return p


def _runs(widths):
    """One segment per run of equal buckets: the split before the cost
    model."""
    segs = []
    for s, w in enumerate(widths):
        b = _bucket(w)
        if segs and segs[-1][2] == b:
            segs[-1] = (segs[-1][0], s + 1, b)
        else:
            segs.append((s, s + 1, b))
    return segs


def _cost(segs):
    return sum(L + _pow2(e - s) * wd for s, e, wd in segs)


def _check_split(widths, segs):
    """Every step once, in order; each width the widest step's bucket."""
    assert [s for s, _, _ in segs] == [0] + [e for _, e, _ in segs[:-1]]
    assert segs[-1][1] == len(widths)
    for s, e, wd in segs:
        assert s < e
        assert wd == max(_bucket(w) for w in widths[s:e])


def _random_widths(seed):
    rng = np.random.default_rng(seed)
    n = int(rng.integers(1, 600))
    kind = seed % 3
    if kind == 0:  # any width, step to step
        return rng.integers(1, 5000, n).tolist()
    if kind == 1:  # narrow runs broken by wide steps
        w = rng.integers(1, 40, n)
        w[rng.random(n) < 0.05] = rng.integers(1000, 70000)
        return w.tolist()
    # log-uniform widths in runs of random length
    out = []
    while len(out) < n:
        out += [int(2 ** rng.uniform(0, 17))] * int(rng.integers(1, 30))
    return out[:n]


@pytest.fixture(scope="module")
def pagerank_widths():
    """Step widths of one ``pagerank_rmat`` SCALE-6 plan."""
    plan = executor.build_wave_plan(*bench_program("pagerank_rmat", scale=6))
    return np.bincount(plan.req_step).tolist()


@pytest.mark.parametrize("seed", range(24))
def test_split_covers_every_step_and_costs_no_more_than_runs(seed):
    widths = _random_widths(seed)
    segs = ops.plan_segments(widths)
    _check_split(widths, segs)
    assert _cost(segs) <= _cost(_runs(widths))


def test_pagerank_widths_merge_and_cost_less(pagerank_widths):
    segs = ops.plan_segments(pagerank_widths)
    _check_split(pagerank_widths, segs)
    runs = _runs(pagerank_widths)
    assert len(segs) < len(runs)
    assert _cost(segs) < _cost(runs)


@pytest.mark.parametrize("width", [1, 8, 9, 300, 5000])
def test_constant_widths_give_one_segment(width):
    assert ops.plan_segments([width] * 77) == [(0, 77, _bucket(width))]


def test_one_request_steps_give_one_narrowest_segment():
    """``run_sequential``'s plan: one request a step, one bucket-8
    segment."""
    assert ops.plan_segments(np.ones(1000, dtype=np.int64)) == [(0, 1000, 8)]


def test_no_steps_give_no_segment():
    assert ops.plan_segments([]) == []


@pytest.mark.parametrize("around", [1, 9])
def test_a_very_wide_step_between_narrow_ones_stays_alone(around):
    widths = [5] * around + [100_000] + [3] * around
    assert ops.plan_segments(widths) == [
        (0, around, 8), (around, around + 1, 131_072),
        (around + 1, 2 * around + 1, 8)]


def test_narrow_steps_join_a_wide_neighbour_when_padding_costs_less():
    # 3 steps at bucket 16 pad into a run at bucket 64 for 3 x 48 lanes,
    # far less than a launch
    assert ops.plan_segments([60, 60, 60, 60, 60, 10, 10, 10]) == [
        (0, 8, 64)]


def test_buckets_are_powers_of_two_from_eight():
    w = np.array([0, 1, 7, 8, 9, 16, 17, 1023, 1024, 1025, 2**20 + 1])
    assert ops._buckets(w).tolist() == [_bucket(x) for x in w]


@pytest.mark.parametrize("program,case", [
    ("tanh+spmv", "low_bit"), ("pagerank_rmat", "low_bit"),
    ("pagerank_rmat", "sign_of_zero")])
def test_a_flipped_lane_fails_the_segment_check(monkeypatch, program, case):
    """A float compare would let the flipped sign of a gathered 0.0 pass:
    the check compares bit patterns."""
    if program == "tanh+spmv":
        prog, arrays, params = programs.get(program).make(64)
    else:
        prog, arrays, params = bench_program(program, scale=6)
    plan = executor.build_wave_plan(prog, arrays, params)
    widths = np.bincount(plan.req_step)
    segs = ops.plan_segments(widths)
    s0, s1, _ = segs[-1]
    assert s1 - s0 >= 2
    # a load lane of the segment, from its last step back, whose gather
    # is flipped: bit 0 of the low word, or the sign bit of a gathered 0.0
    word, bit = (0, 0) if case == "low_bit" else (1, 31)
    order = np.argsort(plan.req_step, kind="stable")
    bounds = np.searchsorted(plan.req_step[order], np.arange(s1 + 1))
    real = kernel.wave_loop
    calls = []

    def fake(mem, addrs, writes, svals):
        mem, vals = real(mem, addrs, writes, svals)
        calls.append(1)
        if len(calls) < len(segs):
            return mem, vals
        got = np.asarray(vals)
        for step in range(s1 - 1, s0, -1):
            reqs = order[bounds[step]:bounds[step + 1]]
            lanes = [i for i, r in enumerate(reqs) if not plan.req_store[r]
                     and (case == "low_bit" or not got[step - s0, i].any())]
            if lanes:
                flipped[:] = [step, lanes[-1]]
                j, lane = step - s0, lanes[-1]
                return mem, vals.at[j, lane, word].set(
                    vals[j, lane, word] ^ np.uint32(1 << bit))
        raise AssertionError("no load lane to flip")

    flipped = []
    monkeypatch.setattr(kernel, "wave_loop", fake)
    with pytest.raises(AssertionError,
                       match="device gather diverged from resolve phase") as e:
        ops.run_plan(plan, arrays, check=True)
    assert len(calls) == len(segs)
    assert f"at step {flipped[0]}, lane {flipped[1]}" in str(e.value)


def test_a_segment_check_passes_without_a_flip(monkeypatch):
    """The same stand-in with nothing flipped: the call completes."""
    prog, arrays, params = bench_program("pagerank_rmat", scale=6)
    plan = executor.build_wave_plan(prog, arrays, params)
    real = kernel.wave_loop
    calls = []

    def fake(*args):
        calls.append(1)
        return real(*args)

    monkeypatch.setattr(kernel, "wave_loop", fake)
    res = ops.run_plan(plan, arrays, check=True)
    assert res.n_segments == len(calls) >= 2
