"""compile_s_in_window: seconds that the window's backend compilations
and persistent-cache loads took, summed from JAX's own duration events
(``jax.monitoring``). Part of ``call_s`` wherever it is not 0."""


def read(run):
    return run.compile_s
