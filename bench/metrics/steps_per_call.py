"""steps_per_call: mean batched gather-scatter steps per call
(``WaveStats.n_steps`` of the plan)."""


def read(run):
    return run.mean("n_steps")
