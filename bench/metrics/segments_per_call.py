"""segments_per_call: mean number of device programs run per call
(``WaveExecResult.n_segments``: one ``wave_loop`` call per segment)."""


def read(run):
    return run.mean("n_segments")
