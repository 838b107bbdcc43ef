"""setup_s: seconds from process start to the first timed call (imports,
TPU start-up, instance generation, compile-cache loads or compiles, and
the warm-up calls). Host clock."""


def read(run):
    return run.setup_s
