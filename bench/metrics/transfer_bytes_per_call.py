"""transfer_bytes_per_call: bytes moved between host and device per window
call, the program's ``h2d_bytes`` and ``d2h_bytes`` span stats summed
(image upload, step tables, gathers copied back, final image)."""

from bench import progtrace


def read(run):
    calls = progtrace.window_tallies(run)
    if not calls:
        return None
    moved = (progtrace.stat_total(run, None, "h2d_bytes")
             + progtrace.stat_total(run, None, "d2h_bytes"))
    return moved / len(calls)
