"""device_pack_s: filling each segment's padded step tables on the host.
Mean seconds per window call of the program's ``repro.device.pack``
spans (``repro.trace``), host clock."""

from bench import progtrace


def read(run):
    return progtrace.per_call_s(run, "repro.device.pack")
