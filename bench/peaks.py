"""Published peaks of each chip, keyed by JAX's ``device_kind``
(``peaks.json``, each entry with its source). A device that is not in
the table is an error, never a default."""

from __future__ import annotations

import json
import pathlib

PEAKS = pathlib.Path(__file__).resolve().with_name("peaks.json")


def peaks(device_kind: str) -> dict:
    table = json.loads(PEAKS.read_text())
    if device_kind not in table:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r} in "
            f"{PEAKS.name} (have {sorted(table)})"
        )
    return table[device_kind]
