"""local_deps_s: building the plan's load-to-store edges through locals
(``executor._local_events``). Mean seconds per window call of the
program's ``repro.plan.locals`` spans (``repro.trace``), host clock."""

from bench import progtrace


def read(run):
    return progtrace.per_call_s(run, "repro.plan.locals")
