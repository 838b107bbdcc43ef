"""device_s: mean device-phase seconds per call, the program's own
``WaveExecResult.device_s`` (host clock from the image upload to
``block_until_ready``). It holds table transfers, dispatch and the
``check=True`` copies of every segment's gathers as well as device work:
it is not kernel time."""


def read(run):
    return run.mean("device_s")
