"""device_check_s: each segment's gathers copied back and compared with the
resolve phase. Mean seconds per window call of the program's
``repro.device.check`` spans (``repro.trace``), host clock."""

from bench import progtrace


def read(run):
    return progtrace.per_call_s(run, "repro.device.check")
