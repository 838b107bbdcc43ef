"""wave_assign_s: the wave sweep over the request stream. Mean seconds per
window call of the program's ``repro.plan.waves`` spans
(``repro.trace``), host clock."""

from bench import progtrace


def read(run):
    return progtrace.per_call_s(run, "repro.plan.waves")
