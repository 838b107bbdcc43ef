"""oracle_walk_s: the hooked oracle walk that yields the reference streams
and the op tables' operands. Mean seconds per window call of the
program's ``repro.plan.walk`` spans (``repro.trace``), host clock."""

from bench import progtrace


def read(run):
    return progtrace.per_call_s(run, "repro.plan.walk")
