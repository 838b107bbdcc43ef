"""Sweep summaries (``repro.dse.summary``) on hand-built rows: the
Pareto front against a brute-force dominance filter, the harmonic
mean, and the per-kernel FUS2 speedups."""

import numpy as np
import pytest

from repro.dse import summary


def _brute_front(rows, objectives):
    """Indices of rows no other row strictly dominates, keeping the
    first of each set of equal vectors."""
    vecs = [tuple(r[o] for o in objectives) for r in rows]
    keep = []
    for i, v in enumerate(vecs):
        strictly_dominated = any(
            all(a <= b for a, b in zip(w, v)) and w != v for w in vecs
        )
        if not strictly_dominated and vecs.index(v) == i:
            keep.append(i)
    return keep


@pytest.mark.parametrize("seed", range(4))
def test_pareto_front_is_the_dominance_filter(seed):
    rng = np.random.default_rng(seed)
    n = int(rng.integers(8, 40))
    # few distinct values, so ties and duplicate points are common
    rows = [
        {"cycles": int(c), "dram_bursts": int(d), "lsq": int(q)}
        for c, d, q in rng.integers(0, 6, size=(n, 3))
    ]
    rows.append(dict(rows[0]))
    for objectives in (("cycles", "dram_bursts"),
                       ("cycles", "dram_bursts", "lsq")):
        front = summary.pareto_front(rows, objectives)
        assert front == _brute_front(rows, objectives)
        tracker = summary.ParetoTracker(objectives)
        for r in rows:
            tracker.update(r)
        assert tracker.front() == [rows[i] for i in front]


@pytest.mark.parametrize("xs, want", [
    ([1.0, 2.0, 4.0], 3 / (1 + 0.5 + 0.25)),
    ([2.0, 0.0, -1.0, 2.0], 2.0),
    ([], 0.0),
], ids=["three", "non-positive-dropped", "empty"])
def test_harmonic_mean(xs, want):
    assert summary.harmonic_mean(xs) == pytest.approx(want, rel=1e-15)


def _row(kernel, mode, cycles, sizing="base", scale=64, dram_bursts=0):
    return {"kernel": kernel, "mode": mode, "cycles": cycles,
            "sizing": sizing, "scale": scale, "dram_bursts": dram_bursts}


def test_sweep_speedups_on_two_kernels():
    rows = [
        _row("a", "STA", 1200), _row("a", "LSQ", 400), _row("a", "FUS2", 100),
        _row("b", "STA", 900), _row("b", "LSQ", 600), _row("b", "FUS2", 300),
        # another sizing is not the reference sizing and is skipped
        _row("a", "FUS2", 50, sizing="big"),
        _row("a", "STA", 5000, sizing="big"),
    ]
    got = summary.sweep_speedups(rows)
    assert got["sizing"] == "base"
    assert got["per_kernel"] == {
        "a": {"FUS2_vs_STA": 12.0, "FUS2_vs_LSQ": 4.0},
        "b": {"FUS2_vs_STA": 3.0, "FUS2_vs_LSQ": 2.0},
    }
    assert got["hmean"] == {
        "FUS2_vs_STA_hmean": round(2 / (1 / 12 + 1 / 3), 3),
        "FUS2_vs_LSQ_hmean": round(2 / (1 / 4 + 1 / 2), 3),
    }


def test_summarize_sweep_keeps_the_fus2_sizing_front():
    rows = [
        _row("a", "FUS2", 100, sizing="s", dram_bursts=30),
        _row("a", "FUS2", 90, sizing="m", dram_bursts=40),
        _row("a", "FUS2", 120, sizing="l", dram_bursts=40),  # dominated
        _row("a", "FUS2", 999, sizing="s", dram_bursts=0),   # repeat sizing
        _row("a", "LSQ", 10, sizing="s", dram_bursts=0),     # not FUS2
    ]
    got = summary.summarize_sweep(rows)
    assert got["pareto_fus2"] == {"a": [
        {"sizing": "s", "cycles": 100, "dram_bursts": 30},
        {"sizing": "m", "cycles": 90, "dram_bursts": 40},
    ]}
    assert summary.summarize_sweep([]) == {
        "speedups": summary.sweep_speedups([]), "pareto_fus2": {},
    }
