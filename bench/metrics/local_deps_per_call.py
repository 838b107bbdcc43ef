"""local_deps_per_call: load-to-store edges through locals the plan
builds per window call (a store reading a register accumulator depends
on each load the register's value was folded from): the ``edges`` stats
of the program's ``repro.plan.locals`` spans, summed over the window
and divided by its calls; nothing where no call opened the span."""

from bench import progtrace

SPAN = "repro.plan.locals"


def read(run):
    calls = progtrace.window_tallies(run)
    if not calls or not any(SPAN in t for t in calls):
        return None
    return progtrace.stat_total(run, SPAN, "edges") / len(calls)
