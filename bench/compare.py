"""The comparison that decides ``correct``.

Every protected array that a timed call returns is compared, word by
word, with the configuration's plain reference (``configs/<config>_ref.py``)
run on the same instance after the window. The system promises results
bit-identical to sequential float64 execution, so the comparison is
exact: a word counts as off when its 64-bit pattern differs, and the
limit on the count is 0.

The control puts the reference computed in float32, the next precision
below the configuration's float64, in the program's place
(``control_words_off``); ``bench/control.py`` reads it at a cell's own
size.
"""

from __future__ import annotations

import numpy as np

# name -> limit; each check holds when its value is at most its limit
LIMITS = {"words_off": 0, "calls_failed": 0, "calls_not_compared": 0}


def words_off(got: dict, want: dict) -> int:
    """Words of the arrays in ``want`` whose float64 bits differ in
    ``got``; an array that is missing or of another length is off whole."""
    off = 0
    for name, ref in want.items():
        ref = np.asarray(ref, dtype=np.float64)
        out = got.get(name)
        if out is None or np.shape(out) != ref.shape:
            off += ref.size
            continue
        out = np.asarray(out, dtype=np.float64)
        off += int(np.count_nonzero(out.view(np.uint64) != ref.view(np.uint64)))
    return off


def checks(attempted: int, failed: int, compared: int, off: int) -> dict:
    """The numbers compared, each beside its limit."""
    values = {
        "words_off": off,
        "calls_failed": failed,
        "calls_not_compared": attempted - compared,
    }
    return {k: {"value": v, "limit": LIMITS[k]} for k, v in values.items()}


def holds(check: dict) -> bool:
    return all(c["value"] <= c["limit"] for c in check.values())


def control_words_off(ref_module, params: dict, arrays: dict) -> int:
    """The control's reading on one instance: the float32 reference in
    the program's place, compared as the program's output is."""
    low = ref_module.reference(arrays, params, dtype=np.float32)
    want = ref_module.reference(arrays, params)
    return words_off({k: v.astype(np.float64) for k, v in low.items()}, want)
