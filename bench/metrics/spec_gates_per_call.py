"""spec_gates_per_call: gates the speculative AGU trace opened per window
call (squash gates for mispredicted values, wait gates where a port's
confidence was low): the ``gates`` stats of the program's
``repro.plan.spec`` spans, summed over the window and divided by its
calls; nothing where no call opened one."""

from bench import progtrace

SPAN = "repro.plan.spec"


def read(run):
    calls = progtrace.window_tallies(run)
    if not calls or not any(SPAN in t for t in calls):
        return None
    return progtrace.stat_total(run, SPAN, "gates") / len(calls)
