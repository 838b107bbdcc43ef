"""Sweep service contract: shard/merge, resume, streaming, retry
(DESIGN.md §13).

Pins, in order:

  * shard planning is deterministic and balanced, and every group is
    owned by exactly one shard;
  * ``merge_results`` over independently-run shards is bit-identical
    to the single-host sweep (points, cycles, arrays, group order),
    and rejects duplicate/missing shards;
  * streaming (``on_point`` / ``iter_points``) delivers every point
    exactly once, in completion order, with the same results as the
    batch return;
  * ``ParetoTracker`` prefix fronts equal the batch ``pareto_front``
    recompute at every prefix;
  * ``SweepStats`` counters cohere, and a warm-cache ``resume=True``
    run executes nothing;
  * a SIGKILLed sweep resumes from the surviving cache computing only
    the missing runs, bit-identical to uninterrupted (subprocess —
    spawn workers need a real ``__main__`` file);
  * a corrupt journal entry is skipped-and-counted, never fatal;
  * transient worker failures retry with backoff; permanent failures
    raise ``SweepGroupError`` naming the (kernel, scale, spec_class)
    group and the surviving cache state.
"""

import json
import os
import signal
import subprocess
import sys
import textwrap
import time

import numpy as np
import pytest

from repro import dse
from repro.dse import runner as runner_mod
from repro.dse import summary

# chase_sum is a speculative kernel (pointer chase): "auto" keeps it
# legal while folding to the "off" spec class on the other three, so
# both speculation classes are exercised without an illegal point
SPEC = dse.SweepSpec(
    kernels=("RAWloop", "hist+add", "tanh+spmv", "chase_sum"),
    scales={"RAWloop": 64, "hist+add": 48, "tanh+spmv": 16,
            "chase_sum": 32},
    modes=("STA", "FUS2"),
    speculations=("auto",),
    sizings={"base": {}, "narrow": {"burst_size": 4}},
)


def _sig(pr):
    if pr is None:
        return None
    return (
        pr.result.cycles, pr.result.dram_bursts,
        tuple(sorted(
            (k, v.tobytes()) for k, v in pr.result.arrays.items()
        )),
    )


def _same(a, b):
    assert len(a.points) == len(b.points)
    for pa, pb in zip(a.points, b.points):
        assert _sig(pa) == _sig(pb)


# -- shard planning ----------------------------------------------------------


def test_shard_plan_deterministic_and_balanced():
    p1 = dse.shard_plan(SPEC, 3)
    p2 = dse.shard_plan(SPEC, 3)
    assert p1 == p2
    assert p1.n_shards == 3
    assert len(p1.loads) == 3
    # LPT greedy: max load at most min load + the largest group
    assert max(p1.loads) - min(p1.loads) <= max(
        len(g.runs) for g in dse.plan(SPEC.points())
    )
    # every group owned exactly once
    owned = [i for s in range(3) for i in p1.groups_for(s)]
    assert sorted(owned) == list(range(len(p1.assignment)))
    with pytest.raises(ValueError):
        p1.groups_for(3)


# -- shard + merge bit-identity ----------------------------------------------


@pytest.mark.parametrize("n_shards", (2, 3))
def test_merge_equals_single_host(tmp_path, n_shards):
    whole = dse.sweep(SPEC, cache_dir=str(tmp_path / "whole"))
    shards = [
        dse.sweep_shard(
            SPEC, i, n_shards, cache_dir=str(tmp_path / f"s{i}")
        )
        for i in range(n_shards)
    ]
    merged = dse.merge_results(shards)
    _same(merged, whole)
    assert merged.stats.shard is None
    assert merged.stats.n_unique_runs == whole.stats.n_unique_runs
    assert [g["class_key"] for g in merged.groups] == [
        g["class_key"] for g in whole.groups
    ]
    volatile = ("cached", "run_wall_s")
    strip = lambda rows: [
        {k: v for k, v in r.items() if k not in volatile} for r in rows
    ]
    assert strip(merged.rows()) == strip(whole.rows())


def test_merge_rejects_duplicate_and_missing_shards():
    shards = [dse.sweep_shard(SPEC, i, 2) for i in range(2)]
    with pytest.raises(ValueError, match="duplicate shard"):
        dse.merge_results([shards[0], shards[0]])
    with pytest.raises(ValueError):
        dse.merge_results([shards[0]])
    with pytest.raises(ValueError):
        dse.merge_results([])


def test_merge_caches(tmp_path):
    a, b, dst = (str(tmp_path / d) for d in ("a", "b", "dst"))
    dse.sweep_shard(SPEC, 0, 2, cache_dir=a)
    dse.sweep_shard(SPEC, 1, 2, cache_dir=b)
    n = dse.merge_caches(dst, a, b)
    assert n > 0
    # the merged cache warm-serves the whole sweep
    res = dse.sweep(SPEC, cache_dir=dst, resume=True)
    assert res.stats.n_executed == 0
    assert res.stats.n_cache_hits == res.stats.n_unique_runs


# -- streaming ---------------------------------------------------------------


def test_on_point_and_iter_points_stream_everything():
    seen = []
    res = dse.sweep(SPEC, on_point=seen.append)
    assert len(seen) == len([p for p in res.points if p is not None])
    assert {id(p) for p in seen} == {id(p) for p in res.points}
    iterated = list(dse.iter_points(SPEC))
    assert len(iterated) == len(seen)
    by_id = {pr.point.point_id: _sig(pr) for pr in iterated}
    for pr in res.points:
        assert by_id[pr.point.point_id] == _sig(pr)


def test_pareto_tracker_prefix_equals_batch():
    rng = np.random.default_rng(7)
    tracker = summary.ParetoTracker()
    rows = []
    for i in range(200):
        row = {"cycles": int(rng.integers(1, 40)),
               "dram_bursts": int(rng.integers(1, 40)), "i": i}
        rows.append(row)
        tracker.update(row)
        batch = [rows[j] for j in summary.pareto_front(rows)]
        assert tracker.front() == batch, f"prefix {i}"
    assert tracker.n_seen == 200


# -- stats + resume ----------------------------------------------------------


def test_stats_cohere_and_warm_resume_executes_nothing(tmp_path):
    cache = str(tmp_path / "cache")
    cold = dse.sweep(SPEC, cache_dir=cache)
    st = cold.stats
    assert st.n_points == len(cold.points)
    assert st.n_cache_hits + st.n_executed == st.n_unique_runs
    assert st.n_executed == st.n_unique_runs  # cold: no hits
    assert st.journal_entries == 0 and st.journal_corrupt == 0
    assert st.wall_s > 0

    warm = dse.sweep(SPEC, cache_dir=cache, resume=True)
    wst = warm.stats
    assert wst.n_executed == 0
    assert wst.n_cache_hits == wst.n_unique_runs
    assert wst.n_resumed_runs == wst.n_unique_runs
    assert wst.journal_entries == st.n_unique_runs
    _same(warm, cold)


def test_resume_requires_cache_dir():
    with pytest.raises(ValueError, match="cache_dir"):
        dse.sweep(SPEC, resume=True)


def test_corrupt_journal_entry_skipped_not_fatal(tmp_path):
    cache = str(tmp_path / "cache")
    cold = dse.sweep(SPEC, cache_dir=cache)
    path = os.path.join(cache, dse.SweepJournal.FILENAME)
    with open(path, "a") as f:
        f.write("{truncated json\n")
        f.write("[1, 2, 3]\n")  # parseable but not a dict: also corrupt
    with pytest.warns(UserWarning, match="journal"):
        res = dse.sweep(SPEC, cache_dir=cache, resume=True)
    assert res.stats.journal_corrupt == 2
    assert res.stats.journal_entries == cold.stats.n_unique_runs
    assert res.stats.n_executed == 0
    _same(res, cold)


KILL_CHILD = textwrap.dedent("""
    import sys
    from repro import dse
    from tests.test_sweep_service import SPEC
    dse.sweep(SPEC, cache_dir=sys.argv[1], workers=1)
""")


def test_kill_resume_bit_identical(tmp_path):
    """SIGKILL a child sweep once its journal shows progress; the
    resumed run computes only the missing runs and matches the
    uninterrupted result bit-for-bit."""
    whole = dse.sweep(SPEC)
    cache = str(tmp_path / "cache")
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in ("src", ".", env.get("PYTHONPATH", "")) if p
    )
    child = subprocess.Popen(
        [sys.executable, "-c", KILL_CHILD, cache],
        env=env, cwd=os.path.dirname(os.path.dirname(__file__)),
        stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL,
    )
    journal = os.path.join(cache, dse.SweepJournal.FILENAME)
    deadline = time.time() + 60.0
    while time.time() < deadline and child.poll() is None:
        if os.path.exists(journal):
            with open(journal) as f:
                if sum(1 for _ in f) >= 2:
                    break
        time.sleep(0.02)
    finished_early = child.poll() is not None
    if not finished_early:
        child.send_signal(signal.SIGKILL)
    child.wait()

    res = dse.sweep(SPEC, cache_dir=cache, resume=True)
    st = res.stats
    assert st.n_cache_hits + st.n_executed == st.n_unique_runs
    if not finished_early:
        assert st.n_resumed_runs >= 1
        assert st.n_executed >= 1
    _same(res, whole)


# -- retry + failure naming --------------------------------------------------


def test_transient_failure_retries_with_backoff(monkeypatch):
    calls = {"n": 0}
    orig = runner_mod._run_group_task

    def flaky(args):
        calls["n"] += 1
        if calls["n"] == 1:
            raise OSError("transient spawn failure")
        return orig(args)

    monkeypatch.setattr(runner_mod, "_run_group_task", flaky)
    res = dse.sweep(SPEC, retries=2, backoff_s=0.01)
    assert res.stats.n_retries == 1
    rec = res.stats.retries[0]
    assert rec["error"].startswith("OSError")
    assert rec["attempt"] == 1
    _same(res, dse.sweep(SPEC))


def test_permanent_failure_names_group_and_cache(tmp_path, monkeypatch):
    """A group that keeps failing raises SweepGroupError naming
    (kernel, scale, spec_class) and the surviving cache state, with the
    original error chained."""
    cache = str(tmp_path / "cache")
    dse.sweep(SPEC, cache_dir=cache)  # populate survivors

    orig = runner_mod._run_group_task

    def doomed(args):
        group = args[0]
        if group.kernel == "tanh+spmv":
            raise ValueError("engine exploded")
        return orig(args)

    monkeypatch.setattr(runner_mod, "_run_group_task", doomed)
    # differential=True changes the run signature vs the cached rows,
    # forcing real execution through the doomed path
    with pytest.raises(dse.SweepGroupError) as ei:
        dse.sweep(SPEC, cache_dir=cache, differential=True,
                  retries=0, backoff_s=0.0)
    msg = str(ei.value)
    assert "kernel='tanh+spmv'" in msg
    assert "scale=16" in msg
    assert "spec_class=" in msg
    assert "cache" in msg
    assert isinstance(ei.value.__cause__, ValueError)
