"""The benchmark's programs at test sizes, for tests outside
``tests/bench``: ``bench_program(config, **override)`` builds the
configuration's program (``bench/configs/<config>.py``) with its
parameters overridden, on one fixed instance of its generator
(``<config>_ref.generate``)."""

import importlib
import json
import pathlib
import sys

import numpy as np

ROOT = pathlib.Path(__file__).resolve().parents[1]


def bench_program(config, **override):
    """(program, arrays, params) of one fixed instance of ``config``."""
    sys.path.insert(0, str(ROOT))
    try:
        mod = importlib.import_module(f"bench.configs.{config}")
        ref = importlib.import_module(f"bench.configs.{config}_ref")
    finally:
        sys.path.remove(str(ROOT))
    params = json.loads((ROOT / f"bench/configs/{config}.json").read_text())
    params = dict(params["params"], **override)
    arrays, pp = ref.generate(
        params, np.random.default_rng(11), np.random.default_rng(12)
    )
    return mod.build(params), arrays, pp
