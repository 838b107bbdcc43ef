"""Chip benchmark of ``executor.execute``: one run of one cell.

Usage, from the root of a checkout:

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Prints, as the last line of standard output, one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics, or
with ``--trace 1`` its per-layer metrics), ``device``, with ``--trace 1``
a ``breakdown``, and last ``checks``: each number compared with its
limit, which also close standard error. Where JAX finds no TPU, or fewer
chips than the cell asks for, or the program's sources are missing, it
says why on standard error and exits 1 with no result line.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
# JAX's persistent compilation cache, at a fixed path inside the checkout
CACHE_DIR = ROOT / ".bench_cache" / "jax"


def _fail(msg: str) -> int:
    print(f"bench: {msg}", file=sys.stderr)
    return 1


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    if not (ROOT / "src" / "repro").is_dir():
        return _fail(f"no program sources under {ROOT / 'src'}")
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    os.environ.setdefault("TPU_LOG_DIR", "disabled")

    from bench import spec

    try:
        cell = spec.load_cell(args.workload, ROOT)
    except (KeyError, FileNotFoundError) as e:
        return _fail(str(e))

    import jax

    # set before the backend starts: every wave_loop program is kept,
    # however fast it compiled, with no size limit, so no eviction
    # bookkeeping that entries written without it would break
    CACHE_DIR.mkdir(parents=True, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_compilation_cache_max_size", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        return _fail(
            f"cell {cell.name} needs {cell.chips} TPU chip(s); JAX found "
            f"{len(devices)} {devices[0].platform} device(s). "
            "No CPU fallback."
        )

    from bench import harness

    line = harness.run(
        cell, args.seed, args.seconds, bool(args.trace), T_START, devices[0],
    )
    for name, c in line["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
