"""device_wait_s: waiting for the device: each segment's gathers under
``check``, and the final image. Mean seconds per window call of the
program's ``repro.device.wait`` spans (``repro.trace``), host clock."""

from bench import progtrace


def read(run):
    return progtrace.per_call_s(run, "repro.device.wait")
