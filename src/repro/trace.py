"""Spans at the program's layer boundaries, on the profiler's clock.

``span(name, **stats)`` opens ``jax.profiler.TraceAnnotation(
f"repro.{name}", **stats)``: inside a profiler session
(``jax.profiler.trace(dir)``) it is a host event on the same clock as the
device's operations, nested by containment on the calling thread; outside
one it costs a few microseconds. The session is the only switch. A span
also times itself on the host clock (``.seconds`` after exit) and adds
its seconds and numeric stats to the tally of the outermost span open on
its thread; ``RECENT`` keeps the newest outermost tallies (one per
``execute()`` call), ``{name: [seconds, count, {stat: sum}]}``.
"""

from __future__ import annotations

import collections
import functools
import threading
import time

RECENT: collections.deque = collections.deque(maxlen=4096)
_local = threading.local()


@functools.cache
def _annotation():
    """jax's ``TraceAnnotation``, imported once a span first opens."""
    from jax.profiler import TraceAnnotation

    return TraceAnnotation


class span:
    def __init__(self, name: str, **stats):
        self.name = f"repro.{name}"
        self.stats = stats
        self.seconds = 0.0

    def set(self, **stats):
        """Add stats that the span's own work yields, such as a count it
        produced; call before exit."""
        self.stats.update(stats)
        self._tm.set_metadata(**stats)

    def __enter__(self):
        self._root = getattr(_local, "tally", None) is None
        if self._root:
            _local.tally = {}
        self._tm = _annotation()(self.name, **self.stats)
        self._tm.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self.seconds = time.perf_counter() - self._t0
        self._tm.__exit__(*exc)
        tally = _local.tally
        entry = tally.setdefault(self.name, [0.0, 0, {}])
        entry[0] += self.seconds
        entry[1] += 1
        for k, v in self.stats.items():
            if isinstance(v, (int, float)):
                entry[2][k] = entry[2].get(k, 0) + v
        if self._root:
            _local.tally = None
            RECENT.append(tally)
        return False
