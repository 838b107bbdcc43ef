"""Reduction of a profiler trace to device busy time, kernel time and a
breakdown of where the device sat idle.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` writes. A device
plane is one named ``/device:TPU:<n>``; its ``XLA Ops`` line holds one
event per operation run on the chip, and its ``XLA Modules`` line one per
compiled program run. The harness's own host spans (``bench.window``,
``bench.call``, ``bench.instance``) sit on a host plane, on the same clock.
"""

from __future__ import annotations

import bisect
import dataclasses
import glob
import re
from collections import defaultdict

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench."


@dataclasses.dataclass
class Trace:
    # per chip: (start_ns, end_ns, op name) of each op run
    ops: dict
    # per chip: (start_ns, end_ns, program name) of each program run
    modules: dict
    # (start_ns, end_ns, span name without the prefix) of harness spans
    spans: list

    def window(self) -> tuple[float, float]:
        """Start and end of the harness's ``window`` span."""
        w = [(s, e) for s, e, n in self.spans if n == "window"]
        if not w:
            raise ValueError("trace holds no bench.window span")
        return w[0]


def op_name(text: str) -> str:
    """The short name of an ``XLA Ops`` event: a TPU trace names each op
    by its whole HLO instruction (``%fusion.17 = u32[...] fusion(...)``);
    keep the instruction's name (``fusion.17``)."""
    if text.startswith("%"):
        return text[1:].split(" ", 1)[0]
    return text


def load_file(path: str) -> Trace:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    ops: dict = defaultdict(list)
    modules: dict = defaultdict(list)
    spans = []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m and line.name == OPS_LINE:
                ops[int(m.group(1))] += [
                    (ev.start_ns, ev.end_ns, op_name(ev.name))
                    for ev in line.events
                ]
            elif m and line.name == MODULES_LINE:
                modules[int(m.group(1))] += [
                    (ev.start_ns, ev.end_ns, ev.name.split("(", 1)[0])
                    for ev in line.events
                ]
            elif not m:
                spans += [
                    (ev.start_ns, ev.end_ns, ev.name[len(SPAN_PREFIX):])
                    for ev in line.events if ev.name.startswith(SPAN_PREFIX)
                ]
    return Trace(dict(ops), dict(modules), sorted(spans))


def load(logdir: str) -> Trace:
    """The trace of the one profiling session written under ``logdir``."""
    files = glob.glob(f"{logdir}/**/*.xplane.pb", recursive=True)
    if len(files) != 1:
        raise ValueError(f"expected one .xplane.pb under {logdir}, found {files}")
    return load_file(files[0])


def merged(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    """The union of ``(start, end, ...)`` intervals, clipped to [lo, hi],
    as sorted disjoint (start, end) pairs."""
    out: list[list[float]] = []
    for iv in sorted(intervals):
        s, e = max(iv[0], lo), min(iv[1], hi)
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def busy_s(trace: Trace) -> float:
    """Seconds of the window in which an operation ran on the device,
    averaged over the chips that ran any."""
    lo, hi = trace.window()
    if not trace.ops:
        return 0.0
    total = sum(
        sum(e - s for s, e in merged(evs, lo, hi))
        for evs in trace.ops.values()
    )
    return total / len(trace.ops) / 1e9


def program_s(trace: Trace, program: str) -> float:
    """Device seconds, in the window, of runs of the compiled program
    ``jit_<program>``, summed over chips."""
    lo, hi = trace.window()
    return sum(
        sum(e - s for s, e in merged(
            [ev for ev in evs if ev[2] == f"jit_{program}"], lo, hi))
        for evs in trace.modules.values()
    ) / 1e9


class _Measure:
    """Length of the part of sorted disjoint intervals that lies before
    a time ``t``, by bisection over their cumulative lengths."""

    def __init__(self, intervals):
        self.starts = [s for s, _ in intervals]
        self.ivs = intervals
        self.before = [0.0]
        for s, e in intervals:
            self.before.append(self.before[-1] + (e - s))

    def upto(self, t: float) -> float:
        i = bisect.bisect_right(self.starts, t)
        if i == 0:
            return 0.0
        s, e = self.ivs[i - 1]
        return self.before[i - 1] + min(e, t) - s

    def within(self, a: float, b: float) -> float:
        return self.upto(b) - self.upto(a) if b > a else 0.0


def breakdown(trace: Trace, top: int = 10) -> dict:
    """On the first chip: the device operations that took most time
    (named ``<program>/<op>``; an op's time includes the ops nested in
    it, as a ``while`` holds its body), and
    the window's idle time summed by what the host was doing. Inside a
    call, idle time before the call's first device operation is
    ``call:head`` (plan build and host resolve), between its first and
    last ``call:mid`` (dispatch, transfers and host work between
    programs) and after its last ``call:tail`` (copy back and unpack);
    outside calls it takes the name of the harness span it falls in."""
    lo, hi = trace.window()
    chip = min(trace.ops) if trace.ops else None
    evs = trace.ops.get(chip, [])
    mods = sorted(trace.modules.get(chip, []))
    mod_starts = [s for s, _, _ in mods]
    by_op: dict = defaultdict(float)
    for s, e, name in evs:
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        i = bisect.bisect_right(mod_starts, s) - 1
        if i >= 0 and mods[i][1] >= e:
            name = f"{mods[i][2]}/{name}"
        by_op[name] += (e - s) / 1e9
    busy = merged(evs, lo, hi)
    idle, prev = [], lo
    for s, e in busy + [(hi, hi)]:
        if s > prev:
            idle.append((prev, s))
        prev = max(prev, e)
    idle_m = _Measure(idle)
    busy_starts = [s for s, _ in busy]
    regions = []
    for s, e, name in trace.spans:
        if name == "window" or e <= lo or s >= hi:
            continue
        s, e = max(s, lo), min(e, hi)
        if name != "call":
            regions.append((s, e, name))
            continue
        i = bisect.bisect_left(busy_starts, s)
        j = bisect.bisect_left(busy_starts, e)
        first = busy[i][0] if i < j else e
        last = min(busy[j - 1][1], e) if i < j else e
        regions += [(s, first, "call:head"), (first, last, "call:mid"),
                    (last, e, "call:tail")]
    gaps: dict = defaultdict(float)
    for s, e, name in regions:
        gaps[name] += idle_m.within(s, e) / 1e9
    gaps["outside_spans"] = (
        idle_m.within(lo, hi) / 1e9 - sum(gaps.values())
    )

    def rank(d):
        return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])
                if v > 0][:top]

    return {"device_ops": rank(by_op), "idle_gaps": rank(gaps)}
