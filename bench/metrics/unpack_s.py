"""unpack_s: the final image copied back, compared and unpacked into
arrays. Mean seconds per window call of the program's ``repro.unpack``
spans (``repro.trace``), host clock."""

from bench import progtrace


def read(run):
    return progtrace.per_call_s(run, "repro.unpack")
