"""The one place the benchmark calls the system under test.

The timed entry is ``executor.execute(program, arrays, params,
config=RunConfig(backend="pallas"))`` with every other field at its
default: plan build, host resolve, the device segments and unpacking the
final arrays all happen inside it.
"""

from __future__ import annotations

from repro.core import executor
from repro.core.config import RunConfig

CONFIG = RunConfig(backend="pallas")


def call(program, arrays, params):
    """One timed call; returns ``execute``'s result."""
    return executor.execute(program, arrays, params, config=CONFIG)


def record(result) -> dict:
    """What the per-layer readers take from one call's result: the
    device run's host-clock phases and counts. A value the result does
    not carry is left out."""
    run = getattr(result, "run", None)
    stats = getattr(result, "stats", None)
    out = {
        "resolve_s": getattr(run, "resolve_s", None),
        "device_s": getattr(run, "device_s", None),
        "n_segments": getattr(run, "n_segments", None),
        "n_steps": getattr(stats, "n_steps", None),
    }
    return {k: v for k, v in out.items() if v is not None}
