"""trace_stream_s: the AGU trace compiler's request stream and its count
check (absent under ``trace_mode="interp"``). Mean seconds per window
call of the program's ``repro.plan.trace`` spans (``repro.trace``), host
clock."""

from bench import progtrace


def read(run):
    return progtrace.per_call_s(run, "repro.plan.trace")
