"""The program's spans (``repro.trace``): where ``execute()`` opens them,
how they nest, what their stats count, and that neither a profiler
session nor the spans change what a call computes."""

import glob

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from bench_programs import bench_program
from repro import trace
from repro.core import executor, programs
from repro.kernels.wave_exec import ops

TREE = [
    ("repro.execute", 0),
    ("repro.plan", 1),
    ("repro.plan.analyze", 2),
    ("repro.plan.walk", 2),
    ("repro.plan.streams", 2),
    ("repro.plan.waves", 2),
    ("repro.plan.analyze", 2),
    ("repro.plan.coarsen", 2),
    ("repro.resolve", 1),
    ("repro.device", 1),
]
SEGMENT = [("repro.device.pack", 2), ("repro.device.launch", 2),
           ("repro.device.wait", 2), ("repro.device.check", 2)]
TAIL = [("repro.device.wait", 2), ("repro.unpack", 1)]


def _spmv():
    return programs.get("tanh+spmv").make(64)


def _pagerank():
    """A SCALE-6 Graph500 graph: its hub steps and its narrow tail run
    as two segments."""
    return bench_program("pagerank_rmat", scale=6)


def _program_spans(logdir):
    """(start_ns, end_ns, name, stats) of every ``repro.*`` host event of
    the one session under ``logdir``, enclosing spans first."""
    (path,) = glob.glob(f"{logdir}/**/*.xplane.pb", recursive=True)
    out = [
        (ev.start_ns, ev.end_ns, ev.name, dict(ev.stats))
        for plane in ProfileData.from_file(path).planes
        for line in plane.lines for ev in line.events
        if ev.name.startswith("repro.")
    ]
    return sorted(out, key=lambda x: (x[0], -x[1]))


def _depths(spans):
    """Each span's name and nesting depth, by containment."""
    out, stack = [], []
    for s, e, name, _ in spans:
        while stack and stack[-1] <= s:
            stack.pop()
        assert not stack or e <= stack[-1], f"{name} overlaps its parent"
        out.append((name, len(stack)))
        stack.append(e)
    return out


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """One warm call, then one call of ``execute()`` inside a profiler
    session: (its result, its program spans, its tally)."""
    prog, arrays, params = _pagerank()
    executor.execute(prog, arrays, params, backend="pallas")
    logdir = tmp_path_factory.mktemp("trace")
    with jax.profiler.trace(str(logdir)):
        res = executor.execute(prog, arrays, params, backend="pallas")
    return res, _program_spans(logdir), trace.RECENT[-1]


def test_span_tree_of_one_call(traced):
    res, spans, _ = traced
    n = res.run.n_segments
    assert n > 1
    assert _depths(spans) == TREE + SEGMENT * n + TAIL
    execute = spans[0][3]
    assert execute["backend"] == "pallas"
    assert execute["n_requests"] == res.plan.n_requests
    assert {s[3]["call"] for s in spans if s[2] == "repro.execute"} == {
        execute["call"]}


def test_span_stats_count_what_the_call_did(traced):
    res, spans, _ = traced
    stats = {}
    for _, _, name, st in spans:
        stats.setdefault(name, []).append(st)
    assert stats["repro.device"][0]["segments"] == res.run.n_segments
    assert len(stats["repro.device.pack"]) == res.run.n_segments
    assert len(stats["repro.device.launch"]) == res.run.n_segments
    assert sum(st["steps"] for st in stats["repro.device.pack"]) == \
        res.stats.n_steps
    assert stats["repro.resolve"][0]["steps"] == res.stats.n_steps
    assert stats["repro.plan.coarsen"][0]["steps"] == res.stats.n_steps
    assert stats["repro.plan.waves"][0]["waves"] == res.stats.n_waves
    assert stats["repro.plan.walk"][0]["requests"] == res.plan.n_requests
    for st in stats["repro.device.pack"]:
        assert st["steps"] <= st["steps_pad"] < 2 * st["steps"]
        assert st["steps"] <= st["lanes"] <= st["steps_pad"] * st["width"]
    assert sum(st["lanes"] for st in stats["repro.device.pack"]) == \
        res.plan.n_requests
    # the first call warmed every shape; image, tables and copies back
    # are counted from the padded shapes
    assert all(st["new_shape"] == 0 for st in stats["repro.device.launch"])
    image = 8 * (res.plan.mem_size + 1)
    assert stats["repro.device"][0]["h2d_bytes"] == image
    assert stats["repro.unpack"][0]["d2h_bytes"] == image
    for pack, launch, check in zip(stats["repro.device.pack"],
                                   stats["repro.device.launch"],
                                   stats["repro.device.check"]):
        lanes = pack["steps_pad"] * pack["width"]
        assert launch["h2d_bytes"] == lanes * (4 + 1 + 8)
        assert check["d2h_bytes"] == lanes * 8


def test_phase_seconds_are_their_spans(traced):
    res, spans, tally = traced
    assert res.run.resolve_s == tally["repro.resolve"][0]
    assert res.run.device_s == tally["repro.device"][0]
    assert res.run.elapsed == res.run.resolve_s + res.run.device_s
    on_clock = {name: (e - s) / 1e9 for s, e, name, _ in spans
                if name in ("repro.resolve", "repro.device")}
    assert on_clock["repro.resolve"] == pytest.approx(res.run.resolve_s,
                                                      abs=1e-3)
    assert on_clock["repro.device"] == pytest.approx(res.run.device_s,
                                                     abs=1e-3)


def test_new_shape_marks_the_first_launch_of_a_shape(monkeypatch):
    monkeypatch.setattr(ops, "_SHAPES_SEEN", set())
    prog, arrays, params = _spmv()
    res = executor.execute(prog, arrays, params, backend="pallas")
    first = trace.RECENT[-1]["repro.device.launch"][2]["new_shape"]
    assert 0 < first == len(ops._SHAPES_SEEN) <= res.run.n_segments
    executor.execute(prog, arrays, params, backend="pallas")
    assert trace.RECENT[-1]["repro.device.launch"][2]["new_shape"] == 0


def test_a_profiler_session_changes_no_result(tmp_path):
    prog, arrays, params = _spmv()
    plain = executor.execute(prog, arrays, params, backend="pallas")
    with jax.profiler.trace(str(tmp_path)):
        traced = executor.execute(prog, arrays, params, backend="pallas")
    assert plain.arrays.keys() == traced.arrays.keys()
    for k in plain.arrays:
        np.testing.assert_array_equal(plain.arrays[k], traced.arrays[k])
        assert plain.arrays[k].dtype == traced.arrays[k].dtype
    assert plain.run.n_segments == traced.run.n_segments


def test_each_call_leaves_one_tally():
    prog, arrays, params = _spmv()
    before = trace.RECENT[-1] if trace.RECENT else None
    res = executor.execute(prog, arrays, params, backend="pallas")
    tally = trace.RECENT[-1]
    assert tally is not before
    n = res.run.n_segments
    assert {k: v[1] for k, v in tally.items()} == {
        "repro.execute": 1, "repro.plan": 1, "repro.plan.analyze": 2,
        "repro.plan.walk": 1, "repro.plan.streams": 1,
        "repro.plan.waves": 1, "repro.plan.coarsen": 1, "repro.resolve": 1,
        "repro.device": 1, "repro.device.pack": n, "repro.device.launch": n,
        "repro.device.wait": n + 1, "repro.device.check": n,
        "repro.unpack": 1,
    }
    assert tally["repro.execute"][0] >= (
        tally["repro.plan"][0] + tally["repro.resolve"][0]
        + tally["repro.device"][0] + tally["repro.unpack"][0])


def test_span_times_itself_and_nests():
    with trace.span("outer", a=2) as outer:
        with trace.span("inner", b=3) as inner:
            inner.set(c=1)
        with trace.span("inner", b=4, label="x"):
            pass
    tally = trace.RECENT[-1]
    assert outer.seconds >= inner.seconds > 0
    assert tally["repro.outer"] == [outer.seconds, 1, {"a": 2}]
    assert tally["repro.inner"][1:] == [2, {"b": 7, "c": 1}]


def test_a_raising_span_still_closes_its_tally():
    with pytest.raises(RuntimeError):
        with trace.span("outer"):
            with trace.span("inner"):
                raise RuntimeError("planted")
    assert set(trace.RECENT[-1]) == {"repro.outer", "repro.inner"}
    with trace.span("next"):
        pass
    assert set(trace.RECENT[-1]) == {"repro.next"}


def test_walk_span_counts_the_generated_walk(monkeypatch):
    """``compiled``: the generated walk ran; ``walk_compiles``: this call
    generated it. The second call on a program reuses the first's; a
    speculative program keeps the interpreter."""
    monkeypatch.setattr(executor, "_WALKS", {})
    prog, arrays, params = _spmv()
    seen = []
    for _ in range(2):
        executor.execute(prog, arrays, params)
        seen.append(trace.RECENT[-1]["repro.plan.walk"][2])
    assert [(s["compiled"], s["walk_compiles"]) for s in seen] == [
        (1, 1), (1, 0)]
    assert seen[0]["requests"] == seen[1]["requests"] > 0

    spec = programs.get(programs.SPEC_KERNELS[0])
    prog, arrays, params = spec.make(spec.default_scale)
    executor.execute(prog, arrays, params, speculation="auto")
    stats = trace.RECENT[-1]["repro.plan.walk"][2]
    assert (stats["compiled"], stats["walk_compiles"]) == (0, 0)


def test_spec_span_counts_the_speculative_trace():
    """``execute()`` on a speculative program opens no ``repro.plan.spec``:
    its stream comes from the walk. ``schedule.trace_program`` still
    opens the span once per speculative PE, with the gates its trace
    opened and the requests it generated."""
    from repro.core import dae as daelib
    from repro.core import schedule

    spec = programs.get("bfs_front")
    prog, arrays, params = spec.make(spec.default_scale)
    res = executor.execute(prog, arrays, params)
    assert "repro.plan.spec" not in trace.RECENT[-1]

    dae = daelib.decouple(prog, speculation="auto")
    plans = []
    with trace.span("outer"):
        traces = schedule.trace_program(
            prog, dae, arrays, params, spec_out=plans,
            spec_span=lambda: trace.span("plan.spec"),
        )
    tally = trace.RECENT[-1]
    spec_ops = [o for pe in dae.spec for o in dae.pes[pe].mem_ops]
    assert tally["repro.plan.spec"][1] == len(dae.spec) >= 1
    assert tally["repro.plan.spec"][2] == {
        "gates": plans[0].n_gates,
        "requests": sum(traces[o].n_req for o in spec_ops),
    }
    assert 0 < tally["repro.plan.spec"][2]["requests"] < res.plan.n_requests
    assert tally["repro.plan.spec"][0] <= tally["repro.outer"][0]


@pytest.mark.parametrize("name", ["spmv", "bfs_front"])
def test_the_walk_supplies_the_stream(name):
    """Decoupled or speculative, a plan's request stream is the walk's:
    ``repro.plan.walk`` counts every request of the plan, and no span
    builds a second stream."""
    if name == "spmv":
        prog, arrays, params = _spmv()
    else:
        spec = programs.get(name)
        prog, arrays, params = spec.make(spec.default_scale)
    res = executor.execute(prog, arrays, params, backend="numpy")
    tally = trace.RECENT[-1]
    assert tally["repro.plan.walk"][1] == 1
    assert tally["repro.plan.walk"][2]["requests"] == res.plan.n_requests > 0
    assert "repro.plan.trace" not in tally
    assert "repro.resolve" not in tally
    with pytest.raises(TypeError, match="trace_mode"):
        executor.execute(prog, arrays, params, trace_mode="interp")
