"""coarsen_s: coarsening waves into batched steps. Mean seconds per window
call of the program's ``repro.plan.coarsen`` spans (``repro.trace``),
host clock."""

from bench import progtrace


def read(run):
    return progtrace.per_call_s(run, "repro.plan.coarsen")
