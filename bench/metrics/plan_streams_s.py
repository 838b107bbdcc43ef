"""plan_streams_s: the FIFO merge and the per-request op, address, ordinal,
valid and value arrays. Mean seconds per window call of the program's
``repro.plan.streams`` spans (``repro.trace``), host clock."""

from bench import progtrace


def read(run):
    return progtrace.per_call_s(run, "repro.plan.streams")
