"""The comparison that decides ``correct``: exact on float64 bits, so it
rejects one changed word and the float32 control."""

import pathlib
import sys

import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import compare, harness, spec  # noqa: E402

TINY = {"pagerank.rmat": {"scale": 5},
        "spmv.small": {"nx": 16, "ny": 16, "nz": 8},
        "spmv.large": {"nx": 6, "ny": 5, "nz": 4}}


def _instance(workload, seed):
    cell = spec.load_cell(workload)
    params = {**cell.params, **TINY[workload]}
    ref = cell.reference_module()
    arrays, _ = ref.generate(params, harness.instance_rng(seed, 0, 0),
                             harness.base_rng(seed))
    return ref, params, arrays


@pytest.mark.parametrize("workload", sorted(TINY))
def test_identical_result_has_no_word_off(workload):
    ref, params, arrays = _instance(workload, 3)
    want = ref.reference(arrays, params)
    got = {k: v.copy() for k, v in want.items()}
    assert compare.words_off(got, want) == 0


@pytest.mark.parametrize("workload", sorted(TINY))
def test_one_changed_word_is_off(workload):
    ref, params, arrays = _instance(workload, 3)
    want = ref.reference(arrays, params)
    got = {k: v.copy() for k, v in want.items()}
    name = ref.PROTECTED[-1]
    got[name][len(got[name]) // 2] = np.nextafter(
        got[name][len(got[name]) // 2], np.inf
    )
    assert compare.words_off(got, want) == 1


def test_missing_or_short_array_is_off_whole():
    want = {"a": np.zeros(4), "b": np.ones(3)}
    assert compare.words_off({"a": np.zeros(4)}, want) == 3
    assert compare.words_off({"a": np.zeros(2), "b": np.ones(3)}, want) == 4
    # -0.0 and 0.0 differ in their bits
    assert compare.words_off({"a": -np.zeros(4), "b": np.ones(3)}, want) == 4


@pytest.mark.parametrize("workload", sorted(TINY))
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_float32_control_fails(workload, seed):
    """The control, the reference computed in float32 in the program's
    place, reads above the exact limit on every seed."""
    ref, params, arrays = _instance(workload, seed)
    assert compare.control_words_off(ref, params, arrays) > compare.LIMITS["words_off"]


def test_checks_hold_only_within_limits():
    ok = compare.checks(attempted=5, failed=0, compared=5, off=0)
    assert compare.holds(ok)
    assert list(ok) == ["words_off", "calls_failed", "calls_not_compared"]
    assert not compare.holds(compare.checks(5, 0, 5, 1))
    assert not compare.holds(compare.checks(5, 1, 4, 0))
    assert not compare.holds(compare.checks(5, 0, 4, 0))
