"""The control of a cell's comparison, at the cell's own size.

Usage, from the root of a checkout:

    python3 bench/control.py --workload <cell> --calls <n> --seeds <s> [<s> ...]

For each seed it makes the first ``n`` instances of the window as a run
with that seed does, puts the configuration's reference computed in
float32 in the program's place, and prints the comparison's reading
(``words_off`` summed over the calls) beside the limit. The limit is
exact (0), so every seed has to read above it. The benchmark's own runs
do not run this.
"""

import argparse
import json
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--calls", type=int, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import compare, harness, spec

    cell = spec.load_cell(args.workload, ROOT)
    ref = cell.reference_module()
    params = cell.params
    readings = {}
    for seed in args.seeds:
        readings[seed] = sum(
            compare.control_words_off(ref, params, harness.make_instance(
                cell, ref, seed, harness.WINDOW, k)[0])
            for k in range(args.calls)
        )
    limit = compare.LIMITS["words_off"]
    print(json.dumps({
        "workload": cell.name, "calls": args.calls, "limit": limit,
        "words_off": readings,
        "all_fail": all(v > limit for v in readings.values()),
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
