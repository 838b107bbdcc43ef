"""plan_s: mean per-call wall time minus the program's ``resolve_s`` and
``device_s``: plan build (oracle walk, wave assignment, coarsening, op
tables) plus the small flat image and unpack. Host clock."""


def read(run):
    if not run.records or len(run.records) != len(run.durations):
        return None
    parts = []
    for d, r in zip(run.durations, run.records):
        if r.get("resolve_s") is None or r.get("device_s") is None:
            return None
        parts.append(d - r["resolve_s"] - r["device_s"])
    return sum(parts) / len(parts)
