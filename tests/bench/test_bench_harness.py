"""The harness end to end on the CPU at tiny sizes: it refuses to run
without a TPU, and with the chip check skipped it drives a whole run and
finds ``correct`` false when the timed path is broken underneath."""

import json
import os
import pathlib
import shutil
import subprocess
import sys
import time
import types

import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import adapter, harness, spec  # noqa: E402

TINY = {"pagerank.rmat": {"scale": 4},
        "spmv.small": {"nx": 8, "ny": 8, "nz": 8, "level": 2},
        "spmv.large": {"nx": 4, "ny": 4, "nz": 3, "level": 0}}


def _cell(workload):
    cell = spec.load_cell(workload)
    cell.traffic = dict(cell.traffic, instance=TINY[workload],
                        warmup_calls=1, pool=2)
    return cell


def _run(workload, call=adapter.call, seed=2**31 + 11):
    import jax

    return harness.run(_cell(workload), seed, 0.05, False,
                       time.perf_counter(), jax.devices()[0], call=call)


def _bench_cmd(cwd, *extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "bench/run.py", "--seconds", "1", "--trace", "0",
         *extra],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=120,
    )


def _result_lines(stdout):
    return [ln for ln in stdout.splitlines() if ln.startswith("{")]


def test_exits_nonzero_without_a_tpu():
    p = _bench_cmd(ROOT, "--workload", "spmv.small", "--seed", "1")
    assert p.returncode != 0
    assert not _result_lines(p.stdout)
    assert "No CPU fallback" in p.stderr


def test_exits_nonzero_for_an_unknown_cell():
    p = _bench_cmd(ROOT, "--workload", "no.such", "--seed", "1")
    assert p.returncode != 0 and not _result_lines(p.stdout)


def test_exits_nonzero_with_only_the_benchmark_files(tmp_path):
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    for d in bench["paths"]:
        shutil.copytree(ROOT / d, tmp_path / d,
                        ignore=shutil.ignore_patterns("__pycache__"))
    p = _bench_cmd(tmp_path, "--workload", "spmv.small", "--seed", "1")
    assert p.returncode != 0 and not _result_lines(p.stdout)


@pytest.mark.parametrize("workload", sorted(TINY))
def test_sound_run_is_correct(workload):
    line = _run(workload)
    assert line["correct"], line["checks"]
    assert line["attempted"] >= 1 and line["failed"] == 0
    names = {m["name"] for m in _cell(workload).end_to_end}
    assert set(line["metrics"]) == names
    assert list(line)[-1] == "checks"
    json.dumps(line)


def _unchanged(program, arrays, params):
    """A timed path whose steps return their state unchanged."""
    return types.SimpleNamespace(arrays=dict(arrays), run=None, stats=None)


def _half_left_out(program, arrays, params):
    """A timed path that leaves the second half of every protected
    array as it came in."""
    res = adapter.call(program, arrays, params)
    for name, a in res.arrays.items():
        if a.dtype == np.float64 and name in arrays:
            a[len(a) // 2:] = arrays[name][len(a) // 2:]
    return res


def _one_answer_altered(program, arrays, params):
    """A timed path that alters one word of its answer where it is
    produced, on every call."""
    res = adapter.call(program, arrays, params)
    name = sorted(k for k, a in res.arrays.items() if a.dtype == np.float64)[0]
    res.arrays[name][0] = np.nextafter(res.arrays[name][0], np.inf)
    return res


class _FailsAfterWarmup:
    """A timed path whose calls raise once the warm-up call is done."""

    __name__ = "fails_after_warmup"

    def __init__(self):
        self.calls = 0

    def __call__(self, program, arrays, params):
        self.calls += 1
        if self.calls > 1:
            raise RuntimeError("planted failure")
        return adapter.call(program, arrays, params)


@pytest.mark.parametrize("fault", [_unchanged, _half_left_out,
                                   _one_answer_altered, _FailsAfterWarmup])
@pytest.mark.parametrize("workload", ["pagerank.rmat", "spmv.small"])
def test_broken_timed_path_is_not_correct(workload, fault):
    if isinstance(fault, type):
        fault = fault()
    line = _run(workload, call=fault)
    assert line["correct"] is False, (fault.__name__, line["checks"])
