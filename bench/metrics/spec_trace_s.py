"""spec_trace_s: the speculative AGU trace of the PEs that lose
decoupling (``speculate.trace_spec_pe``), inside the plan's trace stream.
Mean seconds per window call of the program's ``repro.plan.spec`` spans
(``repro.trace``), host clock; nothing where no call opened one."""

from bench import progtrace


def read(run):
    return progtrace.per_call_s(run, "repro.plan.spec")
