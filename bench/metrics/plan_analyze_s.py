"""plan_analyze_s: the plan's analyses: decoupling, FIFO analysis, the
float64 check, the op tables and the symbolic conflict-freedom proofs.
Mean seconds per window call of the program's ``repro.plan.analyze``
spans (``repro.trace``), host clock."""

from bench import progtrace


def read(run):
    return progtrace.per_call_s(run, "repro.plan.analyze")
