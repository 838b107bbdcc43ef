"""plan_build_s: plan build, all of ``executor.build_wave_plan``. Mean
seconds per window call of the program's ``repro.plan`` spans
(``repro.trace``), host clock."""

from bench import progtrace


def read(run):
    return progtrace.per_call_s(run, "repro.plan")
