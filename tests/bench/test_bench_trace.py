"""The reduction from a profiler trace to busy time, kernel time and the
idle breakdown: on a hand-made trace whose answers can be counted, and
on a small TPU trace recorded on the chip (``fixtures/``)."""

import pathlib
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import devtrace  # noqa: E402

FIXTURE = pathlib.Path(__file__).with_name("fixtures") / "tpu_spmv64.xplane.pb"


def _hand_trace():
    # window [0, 100]; two calls [10, 50] and [60, 95], an instance span
    # [52, 58]; device ops (ns) with one overlap and one op outside
    ops = [
        (20, 25, "gather"),
        (24, 30, "scatter"),
        (40, 45, "copy"),
        (70, 80, "gather"),
        (120, 130, "gather"),
    ]
    modules = [(20, 30, "jit_wave_loop"), (40, 45, "jit_other"),
               (70, 80, "jit_wave_loop"), (120, 130, "jit_wave_loop")]
    spans = [(0, 100, "window"), (10, 50, "call"), (52, 58, "instance"),
             (60, 95, "call")]
    return devtrace.Trace({0: ops}, {0: modules}, spans)


def test_op_names_are_the_hlo_instruction_names():
    assert devtrace.op_name(
        "%fusion.17 = u32[129,2]{1,0} fusion(u32[129,2]{1,0} %x), kind=kLoop"
    ) == "fusion.17"
    assert devtrace.op_name("while") == "while"


def test_merged_clips_and_joins():
    assert devtrace.merged([(5, 8), (1, 3), (2, 4), (9, 20)], 0, 10) == [
        (1, 4), (5, 8), (9, 10)]


def test_busy_and_program_time_on_a_hand_trace():
    t = _hand_trace()
    assert t.window() == (0, 100)
    # union inside the window: [20, 30] + [40, 45] + [70, 80]
    assert devtrace.busy_s(t) == pytest.approx(25e-9)
    assert devtrace.program_s(t, "wave_loop") == pytest.approx(20e-9)
    assert devtrace.program_s(t, "other") == pytest.approx(5e-9)
    assert devtrace.program_s(t, "absent") == 0


def test_breakdown_on_a_hand_trace():
    b = devtrace.breakdown(_hand_trace())
    ops = dict(b["device_ops"])
    assert ops == pytest.approx({"jit_wave_loop/gather": 15e-9,
                                 "jit_wave_loop/scatter": 6e-9,
                                 "jit_other/copy": 5e-9})
    gaps = dict(b["idle_gaps"])
    # heads: [10, 20] + [60, 70]; mid: [30, 40]; tails: [45, 50] + [80, 95];
    # instance [52, 58]; the rest of the 75 idle ns lies outside spans
    assert gaps == pytest.approx({
        "call:head": 20e-9, "call:mid": 10e-9, "call:tail": 20e-9,
        "instance": 6e-9, "outside_spans": 19e-9,
    })
    assert sum(gaps.values()) + devtrace.busy_s(_hand_trace()) == \
        pytest.approx(100e-9)
    assert [v for _, v in b["idle_gaps"]] == sorted(gaps.values(), reverse=True)


def test_trace_without_a_window_is_refused():
    t = devtrace.Trace({}, {}, [(0, 5, "call")])
    with pytest.raises(ValueError):
        t.window()


@pytest.fixture(scope="module")
def tpu_trace():
    """Three 64-row tanh+spmv calls traced on a TPU v5e, in bench.window
    and bench.call spans (``bench/record_trace.py``)."""
    return devtrace.load_file(str(FIXTURE))


def test_tpu_trace_planes_and_spans(tpu_trace):
    lo, hi = tpu_trace.window()
    assert (hi - lo) / 1e9 == pytest.approx(0.574878526)
    assert [n for _, _, n in tpu_trace.spans] == ["window", "call", "call", "call"]
    assert list(tpu_trace.ops) == [0] and len(tpu_trace.ops[0]) == 728
    assert {n for _, _, n in tpu_trace.modules[0]} == {"jit_wave_loop"}
    assert len(tpu_trace.modules[0]) == 21  # 3 calls x 7 segments


def test_tpu_trace_busy_and_kernel_time(tpu_trace):
    from jax.profiler import ProfileData

    assert devtrace.busy_s(tpu_trace) == pytest.approx(0.000133245)
    assert devtrace.program_s(tpu_trace, "wave_loop") == pytest.approx(0.00013935)
    # the programs' runs do not overlap, so their plain sum read straight
    # from the file agrees with the union
    data = ProfileData.from_file(str(FIXTURE))
    plane = data.find_plane_with_name("/device:TPU:0")
    line = next(ln for ln in plane.lines if ln.name == "XLA Modules")
    total = sum(ev.duration_ns for ev in line.events
                if ev.name.startswith("jit_wave_loop("))
    assert devtrace.program_s(tpu_trace, "wave_loop") == pytest.approx(total / 1e9)
    assert devtrace.busy_s(tpu_trace) <= total / 1e9


def test_tpu_trace_breakdown(tpu_trace):
    b = devtrace.breakdown(tpu_trace)
    assert b["device_ops"][0] == ["jit_wave_loop/while.3", pytest.approx(9.1181e-05)]
    assert len(b["device_ops"]) == 10
    assert all(name.startswith("jit_wave_loop/") for name, _ in b["device_ops"])
    gaps = dict(b["idle_gaps"])
    assert gaps == pytest.approx({
        "call:mid": 0.544733066, "call:head": 0.019173168,
        "call:tail": 0.010274427, "outside_spans": 0.00056462,
    })
    lo, hi = tpu_trace.window()
    assert sum(gaps.values()) + devtrace.busy_s(tpu_trace) == \
        pytest.approx((hi - lo) / 1e9)
