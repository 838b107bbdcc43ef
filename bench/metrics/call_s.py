"""call_s: the window's summed call wall time over the number of calls it
completed. Host clock, each call ending when ``execute`` has returned its
final host arrays."""


def read(run):
    if not run.durations:
        return None
    return sum(run.durations) / len(run.durations)
