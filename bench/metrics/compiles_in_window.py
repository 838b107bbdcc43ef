"""compiles_in_window: backend compilations and persistent-cache loads in
the window, counted by a ``jax.monitoring`` listener."""


def read(run):
    return run.compiles
