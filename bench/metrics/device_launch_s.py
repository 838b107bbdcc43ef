"""device_launch_s: each segment's table transfers and ``wave_loop``
dispatch, with its compile where the shape is new. Mean seconds per
window call of the program's ``repro.device.launch`` spans
(``repro.trace``), host clock."""

from bench import progtrace


def read(run):
    return progtrace.per_call_s(run, "repro.device.launch")
