"""Chip smoke test: the wave executor's main path on one TPU.

Drives ``executor.execute(..., config=RunConfig(backend="pallas"))`` —
plan build on the host, device replay through ``kernels/wave_exec`` —
for every registered kernel at 8x the Table-1 benchmark scales
(``benchmarks/paper_table1.scaled(8)``; ``default_scale * 8`` for
kernels outside that table), then for ``tanh+spmv`` at a real size
(a 2^18-row sorted-COO SpMV: 524,288 nonzeros, a 524,288-word image).
Every run's final arrays must equal ``loopir.interpret`` exactly, and
``run_plan``'s default ``check=True`` also pins every device gather
against the host resolve phase.

One line per run gives its sizes and its resolve and device wall times.
Those are bring-up times (device time includes compilation), not
benchmark numbers. The last line of standard output is one JSON object
naming the device. Where JAX finds no TPU, or the repository's sources
are not beside this file, the script prints why on standard error and
exits non-zero with no result line. Everything runs in this one
process: the chip belongs to the first process that touches JAX.

Usage:
    python chip_smoke.py
"""

from __future__ import annotations

import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent
SCALE_MULT = 8
REAL = ("tanh+spmv", 1 << 18)


def _run(name, scale):
    import numpy as np

    from repro.core import executor, loopir, programs
    from repro.core.config import RunConfig

    bench = programs.get(name)
    prog, arrays, params = bench.make(scale)
    oracle = loopir.interpret(prog, arrays, params)
    res = executor.execute(
        prog, arrays, params,
        config=RunConfig(
            backend="pallas",
            speculation="auto" if bench.speculative else "off",
        ),
    )
    for k in oracle:
        np.testing.assert_array_equal(
            res.arrays[k], oracle[k],
            err_msg=f"{name}@{scale}: execute() diverged from the oracle "
            f"on array {k!r}",
        )
    run = res.run
    print(
        f"{name} scale={scale} mem_size={res.plan.mem_size} "
        f"n_requests={res.stats.n_requests} n_steps={res.stats.n_steps} "
        f"segments={run.n_segments} resolve_s={run.resolve_s:.3f} "
        f"device_s={run.device_s:.3f} oracle-exact "
        f"(bring-up wall times, device_s includes compilation; "
        f"not benchmark numbers)",
        flush=True,
    )


def main() -> int:
    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(
            f"chip_smoke: needs a TPU; JAX found platform "
            f"{dev.platform!r} ({dev.device_kind}). No CPU fallback.",
            file=sys.stderr,
        )
        return 1
    if not (ROOT / "src" / "repro").is_dir():
        print(f"chip_smoke: no repository sources under {ROOT}",
              file=sys.stderr)
        return 1
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

    from benchmarks.paper_table1 import scaled
    from repro.compile_cache import use_compile_cache
    from repro.core import programs

    print(f"compile cache: {use_compile_cache()}", flush=True)
    print(f"device: {dev.platform} {dev.device_kind} x{len(devices)}",
          flush=True)
    table = scaled(SCALE_MULT)
    t0 = time.perf_counter()
    for name in programs.all_names():
        scale = table.get(name, programs.get(name).default_scale * SCALE_MULT)
        _run(name, scale)
    _run(*REAL)
    print(f"all runs oracle-exact in {time.perf_counter() - t0:.1f} s "
          f"(bring-up wall time)", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices),
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
