"""device_idle_share: percent of the traced window in which no operation
ran on the device (1 minus the union of device-op intervals over the
window)."""

from bench import devtrace


def read(run):
    if run.trace is None:
        return None
    lo, hi = run.trace.window()
    if not run.trace.ops or hi <= lo:
        return None
    return 100.0 * (1.0 - devtrace.busy_s(run.trace) * 1e9 / (hi - lo))
