"""One run of one cell: instances from the seed, warm-up, the measured
window of back-to-back ``execute()`` calls, then the comparison.

The loop is closed: one caller, each call starts when the previous one
has returned. Every call gets an instance that no earlier call of the
run received (stream ``WINDOW``, index ``k``); the warm-up draws its own
instances (stream ``WARMUP``). Instances are made before the window, and
the reference runs after it, on the instances made again from the seed.

A traffic mix draws each instance's structure (a graph's edge list) from
one of ``bases`` fixed base generators, the same in every run, and the
rest of the instance (labels, values) from the seed, so seeds do not
change how much work a run does. The warm-up calls every base first, so
it meets the shapes the window will meet; the window takes the bases in
an order drawn from the seed, a new order for each round, so each run
does the same set of work in another order. The warm-up goes on past
``warmup_calls`` while its last call still compiled or loaded a program,
up to ``warmup_max``. The window's calls that compiled or loaded a
program, and the seconds that took, are reported apart.
"""

from __future__ import annotations

import dataclasses
import gc
import os
import shutil
import sys
import tempfile
import time
import traceback
from typing import Callable, Optional

import numpy as np

from bench import adapter, compare, devtrace, spec

WINDOW, WARMUP, BASE, ORDER = 0, 1, 2, 3
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def _words(seed: int) -> list:
    return [abs(seed), int(seed < 0)]


def instance_rng(seed: int, stream: int, k: int) -> np.random.Generator:
    """The generator of instance ``k`` of ``stream``: any whole number is
    a seed, and the same seed gives the same instances."""
    return np.random.default_rng([stream, k, *_words(seed)])


def base_of(seed: int, stream: int, k: int, bases: int) -> int:
    """The base of instance ``k``: the warm-up takes them in turn, the
    window in an order drawn from the seed for each round of ``bases``
    calls."""
    if stream == WARMUP:
        return k % bases
    order = np.random.default_rng(
        [ORDER, k // bases, *_words(seed)]).permutation(bases)
    return int(order[k % bases])


def base_rng(b: int) -> np.random.Generator:
    """The generator of base ``b``: the same in every run."""
    return np.random.default_rng([BASE, b])


def make_instance(cell: spec.Cell, ref, seed: int, stream: int, k: int):
    """Instance ``k`` of ``stream`` of a run of ``cell`` with ``seed``:
    (arrays, program params)."""
    b = base_of(seed, stream, k, cell.traffic["bases"])
    return ref.generate(cell.params, instance_rng(seed, stream, k), base_rng(b))


@dataclasses.dataclass
class Run:
    """What the metric readers (``metrics/<name>.py``) read."""

    cell: spec.Cell
    device_kind: str
    setup_s: float
    durations: list  # wall seconds of each call of the window
    records: list  # adapter.record of each call that returned
    words: list  # float64 words each compared call needs moved
    compiles: int  # compilations and compile-cache loads in the window
    compile_s: float = 0.0  # seconds those took
    trace: Optional[devtrace.Trace] = None

    def mean(self, key: str) -> Optional[float]:
        """Mean of ``key`` over the window's call records; None where a
        record lacks it."""
        vals = [r.get(key) for r in self.records]
        if not vals or any(v is None for v in vals):
            return None
        return sum(vals) / len(vals)


class CompileCounter:
    """Counts JAX's backend compilations (persistent-cache loads
    included) while armed, and sums the seconds they took."""

    def __init__(self):
        import jax.monitoring

        self.armed = False
        self.count = 0
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kwargs):
        if self.armed and event == COMPILE_EVENT:
            self.count += 1
            self.seconds += duration


class GcClock:
    """Counts Python's garbage collections while armed, and sums the
    seconds they took."""

    def __init__(self):
        self.armed = False
        self.count = 0
        self.seconds = 0.0
        self._t0 = 0.0
        gc.callbacks.append(self._on)

    def _on(self, phase, info):
        if not self.armed:
            return
        if phase == "start":
            self._t0 = time.perf_counter()
        else:
            self.count += 1
            self.seconds += time.perf_counter() - self._t0

    def close(self):
        gc.callbacks.remove(self._on)


def _split(durations: list, compiled: list) -> str:
    """The mean wall time of the window's calls that compiled or loaded a
    program, beside that of the calls that did not."""
    parts = []
    for name, flag in (("compiled", True), ("did not", False)):
        d = [t for t, c in zip(durations, compiled) if c == flag]
        mean = f"{sum(d) / len(d):.4f} s" if d else "-"
        parts.append(f"{len(d)} calls {name}, mean {mean}")
    return ", ".join(parts)


def _profile_options():
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    return opts


def run(
    cell: spec.Cell,
    seed: int,
    seconds: float,
    traced: bool,
    t_start: float,
    device,
    call: Callable = adapter.call,
) -> dict:
    """One run; returns the result line as a dict (``checks`` last)."""
    import jax

    ref = cell.reference_module()
    params = cell.params
    program = cell.program_module().build(params)
    counter = CompileCounter()

    def instance(stream, k):
        return make_instance(cell, ref, seed, stream, k)

    warmup_min = cell.traffic["warmup_calls"]
    warmup_max = cell.traffic.get("warmup_max", warmup_min)
    counter.armed = True
    for warmed in range(1, warmup_max + 1):
        before = counter.count
        arrays, pp = instance(WARMUP, warmed - 1)
        call(program, arrays, pp)
        if warmed >= warmup_min and counter.count == before:
            break
    counter.armed = False
    warmup_compiles, counter.count, counter.seconds = counter.count, 0, 0.0
    pool = [instance(WINDOW, k) for k in range(cell.traffic["pool"])]

    durations, records, outputs, failed = [], [], {}, 0
    compiled = []  # whether each call of the window compiled or loaded
    gc_clock = GcClock()
    setup_s = time.perf_counter() - t_start
    logdir = tempfile.mkdtemp(prefix="bench_trace_") if traced else None
    if traced:
        jax.profiler.start_trace(logdir, profiler_options=_profile_options())
    counter.armed = gc_clock.armed = True
    with jax.profiler.TraceAnnotation("bench.window"):
        while sum(durations) < seconds:
            k = len(durations)
            if k == len(pool):
                with jax.profiler.TraceAnnotation("bench.instance"):
                    pool.append(instance(WINDOW, k))
            arrays, pp = pool[k]
            pool[k] = None
            before = counter.count
            t0 = time.perf_counter()
            try:
                with jax.profiler.TraceAnnotation("bench.call"):
                    res = call(program, arrays, pp)
            except Exception:  # a failed call is counted, and the run goes on
                res = None
                failed += 1
                if failed == 1:
                    traceback.print_exc(file=sys.stderr)
            durations.append(time.perf_counter() - t0)
            compiled.append(counter.count > before)
            if res is not None:
                outputs[k] = {n: res.arrays.get(n) for n in ref.PROTECTED}
                records.append(adapter.record(res))
    counter.armed = gc_clock.armed = False
    gc_clock.close()
    if traced:
        jax.profiler.stop_trace()
    print(f"bench: {cell.name} seed {seed}: {len(durations)} calls in "
          f"{sum(durations):.3f} s, {counter.count} compiles in the window, "
          f"set-up {setup_s:.3f} s with {warmed} warm-up calls and "
          f"{warmup_compiles} compiles", file=sys.stderr)
    print(f"bench: window: {_split(durations, compiled)}; compiles and "
          f"loads took {counter.seconds:.4f} s; {gc_clock.count} garbage "
          f"collections took {gc_clock.seconds:.4f} s; load average "
          f"{os.getloadavg()[0]:.2f} on {len(os.sched_getaffinity(0))} cores",
          file=sys.stderr)
    stats = device.memory_stats() or {}
    memory_peak = stats.get("peak_bytes_in_use")
    del pool

    trace = None
    if traced:
        try:
            trace = devtrace.load(logdir)
        finally:
            shutil.rmtree(logdir, ignore_errors=True)

    off, words = 0, []
    for k, out in outputs.items():
        arrays, pp = instance(WINDOW, k)
        off += compare.words_off(out, ref.reference(arrays, params))
        words.append(ref.words(arrays, params))
    checks = compare.checks(len(durations), failed, len(outputs), off)

    result = Run(
        cell=cell, device_kind=device.device_kind, setup_s=setup_s,
        durations=durations, records=records, words=words,
        compiles=counter.count, compile_s=counter.seconds, trace=trace,
    )
    metrics = {}
    for m in (cell.per_layer if traced else cell.end_to_end):
        value = spec.metric_reader(m["name"], cell.root)(result)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    dev = {
        "platform": device.platform,
        "kind": device.device_kind,
        "count": len(jax.devices()),
        "memory_peak_bytes": memory_peak,
    }
    line = {
        "correct": compare.holds(checks),
        "attempted": len(durations),
        "failed": failed,
        "metrics": metrics,
        "device": dev,
    }
    if trace is not None:
        lo, hi = trace.window()
        dev["busy_s"] = devtrace.busy_s(trace)
        dev["window_s"] = (hi - lo) / 1e9
        line["breakdown"] = devtrace.breakdown(trace)
    line["checks"] = checks
    return line
