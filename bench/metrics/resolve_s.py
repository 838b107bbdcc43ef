"""resolve_s: mean host-resolve seconds per call, the program's own
``WaveExecResult.resolve_s`` (host clock around ``executor.drive_plan``)."""


def read(run):
    return run.mean("resolve_s")
