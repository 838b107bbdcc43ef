"""wave_loop_s: device seconds per call of the ``wave_loop`` program (the
union of its runs on the device in the profiler trace, over the window's
calls)."""

from bench import devtrace


def read(run):
    if run.trace is None or not run.durations:
        return None
    s = devtrace.program_s(run.trace, "wave_loop")
    return s / len(run.durations) if s > 0 else None
