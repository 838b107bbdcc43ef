"""The program's spans as the benchmark reads them: the reduction of a
trace's ``repro.*`` spans (``bench/progtrace.py``) on a hand-made trace
whose answers can be counted and on a small TPU trace recorded on the
chip, and the per-layer readers that take the program's per-call
tallies."""

import collections
import json
import pathlib
import sys
import types

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import devtrace, progtrace, spec  # noqa: E402

FIXTURE = (pathlib.Path(__file__).with_name("fixtures")
           / "tpu_spmv64_spans.xplane.pb")


def _hand_trace():
    # window [0, 200]; calls [10, 100] and [110, 190], an instance span
    # [102, 108]; device ops at [62, 68] and [156, 160], one outside
    ops = [(62, 68, "gather"), (156, 160, "gather"), (300, 310, "gather")]
    modules = [(62, 68, "jit_wave_loop"), (156, 160, "jit_wave_loop"),
               (300, 310, "jit_wave_loop")]
    spans = [(0, 200, "window"), (10, 100, "call"), (102, 108, "instance"),
             (110, 190, "call")]
    program = [
        (-50, -10, "repro.execute", {"call": 0}),  # a warm-up call
        (12, 98, "repro.execute", {"call": 1}),
        (14, 40, "repro.plan", {}),
        (16, 30, "repro.plan.walk", {"requests": 7}),
        (50, 95, "repro.device", {"segments": 1, "h2d_bytes": 16}),
        (55, 60, "repro.device.launch", {"new_shape": 1, "h2d_bytes": 100}),
        (60, 70, "repro.device.wait", {}),
        (70, 75, "repro.device.check", {"d2h_bytes": 40}),
        (112, 188, "repro.execute", {"call": 2}),
        (114, 150, "repro.plan", {}),
        (150, 185, "repro.device", {"segments": 1, "h2d_bytes": 16}),
        (152, 156, "repro.device.launch", {"new_shape": 0, "h2d_bytes": 100}),
    ]
    return progtrace.SpanTrace({0: ops}, {0: modules}, spans, program)


def test_span_seconds_on_a_hand_trace():
    t = _hand_trace()
    assert progtrace.span_s(t, "repro.plan") == pytest.approx(62e-9)
    assert progtrace.span_s(t, "repro.execute") == pytest.approx(162e-9)
    assert progtrace.span_s(t, "repro.absent") == 0
    assert progtrace.self_s(t, "repro.plan") == pytest.approx(48e-9)
    assert progtrace.self_s(t, "repro.device") == pytest.approx(56e-9)
    assert progtrace.self_s(t, "repro.execute") == pytest.approx(20e-9)
    assert progtrace.self_s(t, "repro.plan.walk") == pytest.approx(14e-9)
    assert progtrace.stat_sum(t, "repro.device.launch", "new_shape") == 1
    assert progtrace.stat_sum(t, "repro.device.launch", "h2d_bytes") == 200
    # the warm-up call lies outside the window
    assert progtrace.stat_sum(t, "repro.execute", "call") == 3


def test_idle_by_span_on_a_hand_trace():
    t = _hand_trace()
    idle = progtrace.idle_by_span(t)
    assert idle == pytest.approx({
        "outside_spans": 24e-9, "instance": 6e-9, "call:harness": 8e-9,
        "repro.execute": 20e-9, "repro.plan": 48e-9,
        "repro.plan.walk": 14e-9, "repro.device": 52e-9,
        "repro.device.launch": 9e-9, "repro.device.wait": 4e-9,
        "repro.device.check": 5e-9,
    })
    assert sum(idle.values()) + devtrace.busy_s(t) == pytest.approx(200e-9)


def test_idle_by_span_without_program_spans_is_the_harness_split():
    t = _hand_trace()
    t.program = []
    idle = progtrace.idle_by_span(t)
    gaps = dict(devtrace.breakdown(t)["idle_gaps"])
    assert idle["call:harness"] == pytest.approx(
        sum(gaps.get(k, 0) for k in ("call:head", "call:mid", "call:tail")))
    assert idle["instance"] == pytest.approx(gaps["instance"])
    assert idle["outside_spans"] == pytest.approx(gaps["outside_spans"])


def test_breakdown_adds_idle_by_span_and_keeps_the_rest():
    t = _hand_trace()
    b = progtrace.breakdown(t)
    assert {k: v for k, v in b.items() if k != "idle_by_span"} == \
        devtrace.breakdown(t)
    assert b["idle_by_span"][0] == ["repro.device", pytest.approx(52e-9)]
    assert [v for _, v in b["idle_by_span"]] == sorted(
        (v for _, v in b["idle_by_span"]), reverse=True)
    json.dumps(b)


# the per-layer readers ---------------------------------------------------

def _tally(scale, new_shape):
    """One call's tally, as ``repro.trace`` keeps it, its seconds scaled."""
    s = {
        "repro.execute": 10.0, "repro.plan": 5.0, "repro.plan.analyze": 0.5,
        "repro.plan.walk": 2.0, "repro.plan.trace": 0.75,
        "repro.plan.streams": 0.25, "repro.plan.waves": 1.0,
        "repro.plan.coarsen": 0.125, "repro.resolve": 1.5,
        "repro.device": 3.0, "repro.device.pack": 0.5,
        "repro.device.launch": 1.25, "repro.device.wait": 0.375,
        "repro.device.check": 0.625, "repro.unpack": 0.0625,
    }
    stats = {"repro.device": {"h2d_bytes": 1000, "segments": 2},
             "repro.device.launch": {"h2d_bytes": 300,
                                     "new_shape": new_shape},
             "repro.device.check": {"d2h_bytes": 200},
             "repro.unpack": {"d2h_bytes": 1000}}
    return {n: [v * scale, 1, dict(stats.get(n, {}))] for n, v in s.items()}


PER_CALL = {
    "plan_build_s": 5.0, "plan_analyze_s": 0.5, "oracle_walk_s": 2.0,
    "trace_stream_s": 0.75, "plan_streams_s": 0.25, "wave_assign_s": 1.0,
    "coarsen_s": 0.125, "device_pack_s": 0.5, "device_launch_s": 1.25,
    "device_wait_s": 0.375, "device_check_s": 0.625, "unpack_s": 0.0625,
}


@pytest.fixture
def window(monkeypatch):
    """A run of two window calls, after one warm-up call that compiled
    three shapes and a span opened outside any call."""
    from repro import trace

    recent = collections.deque([
        _tally(100.0, 3), _tally(1.0, 1), {"repro.resolve": [9.0, 1, {}]},
        _tally(3.0, 0)])
    monkeypatch.setattr(trace, "RECENT", recent)
    return types.SimpleNamespace(durations=[11.0, 31.0], records=[{}, {}])


@pytest.mark.parametrize("name", sorted(PER_CALL))
def test_span_readers_mean_the_window_calls(window, name):
    read = spec.metric_reader(name)
    # the window's two calls read 1x and 3x the tally's seconds
    assert read(window) == pytest.approx(PER_CALL[name] * (1 + 3) / 2)


def test_transfer_bytes_per_call(window):
    read = spec.metric_reader("transfer_bytes_per_call")
    assert read(window) == 1000 + 300 + 200 + 1000


def test_new_shapes_in_window(window):
    assert spec.metric_reader("new_shapes_in_window")(window) == 1


def test_a_span_no_window_call_opened_reads_nothing(window):
    from repro import trace

    for t in trace.RECENT:
        t.pop("repro.plan.trace", None)
    assert spec.metric_reader("trace_stream_s")(window) is None
    assert spec.metric_reader("plan_build_s")(window) is not None


def test_readers_read_nothing_from_a_program_without_spans(monkeypatch):
    import repro

    monkeypatch.delattr(repro, "trace", raising=False)
    monkeypatch.setitem(sys.modules, "repro.trace", None)
    run = types.SimpleNamespace(durations=[1.0], records=[{}])
    for name in [*PER_CALL, "transfer_bytes_per_call",
                 "new_shapes_in_window"]:
        assert spec.metric_reader(name)(run) is None


def test_readers_read_nothing_from_an_empty_window(window):
    window.durations = []
    assert spec.metric_reader("plan_build_s")(window) is None
    assert spec.metric_reader("new_shapes_in_window")(window) is None


def test_every_new_reader_is_in_the_benchmark():
    names = {m["name"] for m in spec.load_benchmark()["per_layer"]}
    assert {*PER_CALL, "transfer_bytes_per_call",
            "new_shapes_in_window"} <= names


# a TPU trace with program spans -------------------------------------------

@pytest.fixture(scope="module")
def tpu_trace():
    """Three 64-row tanh+spmv calls traced on a TPU v5e, in bench.window
    and bench.call spans, with the program's spans
    (``bench/record_trace.py``)."""
    return progtrace.load_file(str(FIXTURE))


def test_tpu_program_spans_lie_inside_calls(tpu_trace):
    calls = [(s, e) for s, e, n in tpu_trace.spans if n == "call"]
    assert len(calls) == 3 and tpu_trace.program
    for s, e, name, _ in tpu_trace.program:
        assert any(cs <= s and e <= ce for cs, ce in calls), name
    assert sum(n == "repro.execute" for _, _, n, _ in tpu_trace.program) == 3


def test_tpu_launches_are_the_kernel_runs(tpu_trace):
    launches = [x for x in tpu_trace.program if x[2] == "repro.device.launch"]
    runs = [m for m in tpu_trace.modules[0] if m[2] == "jit_wave_loop"]
    assert len(launches) == len(runs) > 0
    assert progtrace.stat_sum(tpu_trace, "repro.device", "segments") == \
        len(runs)


def test_tpu_idle_inside_calls_lies_in_program_spans(tpu_trace):
    idle = progtrace.idle_by_span(tpu_trace)
    lo, hi = tpu_trace.window()
    assert sum(idle.values()) + devtrace.busy_s(tpu_trace) == \
        pytest.approx((hi - lo) / 1e9)
    program = sum(v for k, v in idle.items() if k.startswith("repro."))
    assert program >= 0.95 * (program + idle["call:harness"])


def test_tpu_span_seconds(tpu_trace):
    assert progtrace.span_s(tpu_trace, "repro.execute") == \
        pytest.approx(0.133394042)
    assert progtrace.self_s(tpu_trace, "repro.device") == \
        pytest.approx(0.002362025)
    assert progtrace.self_s(tpu_trace, "repro.device.launch") == \
        progtrace.span_s(tpu_trace, "repro.device.launch")
    assert progtrace.stat_sum(tpu_trace, "repro.device.launch",
                              "new_shape") == 0
    idle = progtrace.idle_by_span(tpu_trace)
    assert idle["repro.plan.walk"] == pytest.approx(0.034691443)
    assert idle["call:harness"] == pytest.approx(0.000158841)


def test_span_report_on_the_tpu_trace(tpu_trace):
    from bench import span_report

    r = span_report.report(tpu_trace)
    assert r["calls"] == 3
    launch = r["spans"]["repro.device.launch"]
    assert launch["count"] == len(tpu_trace.modules[0])
    assert launch["s_per_call"] == pytest.approx(
        progtrace.span_s(tpu_trace, "repro.device.launch") / 3)
    assert r["call_idle_in_program_spans"] >= 0.95
    assert r["idle_by_span"][0][0] == "repro.plan.walk"
    json.dumps(r)
