"""wave_loop_roofline: the least time the chip's HBM needs to move the
bytes the window's instances need (8 bytes for each float64 word that the
sequential program reads or writes in protected arrays, counted from the
instance by the configuration's ``words``), over the device time of
``wave_loop`` in the trace, in percent. Bound by HBM bandwidth: the step
does no arithmetic."""

from bench import devtrace
from bench.peaks import peaks


def read(run):
    if run.trace is None or not run.words:
        return None
    s = devtrace.program_s(run.trace, "wave_loop")
    if s <= 0:
        return None
    need = 8 * sum(run.words) / peaks(run.device_kind)["hbm_bytes_per_s"]
    return 100.0 * need / s
