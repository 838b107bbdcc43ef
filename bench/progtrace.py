"""The program's own spans (``repro.*``, written by ``repro.trace``): in a
profiler trace, and in the per-call tallies the program keeps of them.

``devtrace.Trace`` keeps only the harness's ``bench.*`` spans.
``SpanTrace`` is the same reduction plus ``program``: ``(start_ns,
end_ns, name, stats)`` of every host event whose name starts with
``repro.``, on the clock of the device's operations. On it, ``span_s``,
``self_s``, ``stat_sum`` and ``idle_by_span`` take a span apart, and
``breakdown`` is ``devtrace.breakdown`` with ``idle_by_span`` added.

The per-layer readers (``metrics/<name>.py``) get a ``harness.Run``,
whose trace holds no program spans; they read ``window_tallies``
instead: the tallies ``repro.trace.RECENT`` keeps of the window's
``execute()`` calls, timed by the same spans on the host clock.
"""

from __future__ import annotations

import dataclasses
from collections import defaultdict
from typing import Optional

from bench import devtrace

PREFIX = "repro."


@dataclasses.dataclass
class SpanTrace(devtrace.Trace):
    # (start_ns, end_ns, name, stats dict) of each program span
    program: list = dataclasses.field(default_factory=list)


def load_file(path: str) -> SpanTrace:
    from jax.profiler import ProfileData

    t = devtrace.load_file(path)
    program = []
    for plane in ProfileData.from_file(path).planes:
        if devtrace.DEVICE_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            program += [
                (ev.start_ns, ev.end_ns, ev.name, dict(ev.stats))
                for ev in line.events if ev.name.startswith(PREFIX)
            ]
    return SpanTrace(t.ops, t.modules, t.spans, _tree_order(program))


def _tree_order(spans) -> list:
    """Spans by start, an enclosing span before those it holds."""
    return sorted(spans, key=lambda x: (x[0], -x[1]))


def in_window(trace: SpanTrace, name: str) -> list:
    """(start, end, stats) of the spans ``name``, clipped to the window."""
    lo, hi = trace.window()
    return [(max(s, lo), min(e, hi), st) for s, e, n, st in trace.program
            if n == name and min(e, hi) > max(s, lo)]


def span_s(trace: SpanTrace, name: str) -> float:
    """Summed seconds of the spans ``name`` inside the window."""
    return sum(e - s for s, e, _ in in_window(trace, name)) / 1e9


def stat_sum(trace: SpanTrace, name: str, key: str) -> float:
    """Sum of stat ``key`` over the spans ``name`` inside the window."""
    return sum(st.get(key, 0) for _, _, st in in_window(trace, name))


def _nested(trace: SpanTrace) -> list:
    """Each program span inside the window with the seconds its direct
    children cover: (start, end, name, child_ns). Spans nest by
    containment, as they do on one thread."""
    lo, hi = trace.window()
    out, stack = [], []
    for s, e, n, _ in _tree_order(trace.program):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        while stack and stack[-1][1] <= s:
            stack.pop()
        if stack:
            e = min(e, stack[-1][1])
            stack[-1][3] += e - s
        stack.append([s, e, n, 0])
        out.append(stack[-1])
    return out


def self_s(trace: SpanTrace, name: str) -> float:
    """Seconds of the spans ``name`` that none of their child spans
    cover, inside the window."""
    return sum(e - s - c for s, e, n, c in _nested(trace) if n == name) / 1e9


def idle_by_span(trace: SpanTrace) -> dict:
    """The first chip's idle seconds in the window, each put down to the
    innermost program span open at that moment; inside a ``bench.call``
    but outside every program span ``call:harness``; outside calls the
    harness span's name, or ``outside_spans``. Sums to the window's idle
    time."""
    lo, hi = trace.window()
    chip = min(trace.ops) if trace.ops else None
    busy = devtrace.merged(trace.ops.get(chip, []), lo, hi)
    idle, prev = [], lo
    for s, e in busy + [(hi, hi)]:
        if s > prev:
            idle.append((prev, s))
        prev = max(prev, e)
    idle_m = devtrace._Measure(idle)
    label = {"window": "outside_spans", "call": "call:harness"}
    spans = _tree_order(
        [(s, e, label.get(n, n)) for s, e, n in trace.spans]
        + [(s, e, n) for s, e, n, _ in trace.program]
    )
    # walk the spans as a tree; each stretch of time goes to the
    # innermost span open in it
    out: dict = defaultdict(float)
    stack: list = []  # (end, label)
    pos = lo

    def close(upto):
        nonlocal pos
        while stack and stack[-1][0] <= upto:
            end, n = stack.pop()
            out[n] += idle_m.within(pos, end)
            pos = max(pos, end)

    for s, e, n in spans:
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        close(s)
        if stack:
            out[stack[-1][1]] += idle_m.within(pos, s)
            e = min(e, stack[-1][0])
        else:
            out["outside_spans"] += idle_m.within(pos, s)
        pos = s
        stack.append((e, n))
    close(hi)
    out["outside_spans"] += idle_m.within(pos, hi)
    return {k: v / 1e9 for k, v in out.items()}


def breakdown(trace: SpanTrace, top: int = 10) -> dict:
    """``devtrace.breakdown`` with ``idle_by_span``, largest first."""
    by_span = sorted(idle_by_span(trace).items(), key=lambda kv: -kv[1])
    return {**devtrace.breakdown(trace, top),
            "idle_by_span": [[k, v] for k, v in by_span if v > 0]}


def window_tallies(run) -> Optional[list]:
    """The tallies of the window's ``execute()`` calls, newest last: the
    last ``len(run.durations)`` that ``repro.trace.RECENT`` holds (the
    harness calls nothing else of the program after its window). None
    where the program keeps no tallies."""
    try:
        from repro import trace
    except ImportError:
        return None
    calls = [t for t in trace.RECENT if PREFIX + "execute" in t]
    n = len(run.durations)
    return calls[-n:] if n and calls else None


def per_call_s(run, name: str) -> Optional[float]:
    """Mean seconds per window call of the spans ``name``; None where no
    call of the window opened one."""
    calls = window_tallies(run)
    if not calls or not any(name in t for t in calls):
        return None
    return sum(t[name][0] for t in calls if name in t) / len(calls)


def stat_total(run, name: Optional[str], key: str) -> Optional[float]:
    """Sum over the window's calls of stat ``key`` on the spans ``name``,
    or on every span where ``name`` is None."""
    calls = window_tallies(run)
    if not calls:
        return None
    return sum(v[2].get(key, 0) for t in calls for n, v in t.items()
               if name in (None, n))
