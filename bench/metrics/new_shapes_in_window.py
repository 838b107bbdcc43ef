"""new_shapes_in_window: segment launches in the window whose (padded
steps, width, image rows) shape was new to the process, so that
``wave_loop`` traced and compiled or loaded: the ``new_shape`` stats of
the program's ``repro.device.launch`` spans, summed over the window."""

from bench import progtrace


def read(run):
    return progtrace.stat_total(run, "repro.device.launch", "new_shape")
