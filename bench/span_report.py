"""One traced run of one cell, read down to the program's own spans.

Usage, from the root of a checkout, on a machine with a TPU:

    python3 bench/span_report.py --workload <cell> --seed <n> --seconds <s>

Runs ``bench/run.py`` with ``--trace 1`` and prints its lines, keeping
the ``repro.*`` spans of the profiler trace, which the harness's own
reduction drops. Then prints, as the last line, one JSON object read
from those spans on the profiler's clock: for each program span its
seconds per call (``span_s``) and the seconds none of its children cover
(``self_s``), its counts and stat sums, and ``idle_by_span``, the
device's idle time put down to the innermost span open, with the share
of the calls' idle time that lies inside program spans. The benchmark's
own runs do not run this.
"""

import argparse
import glob
import json
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[1]


def report(trace) -> dict:
    from bench import progtrace

    lo, hi = trace.window()
    calls = sum(1 for s, e, n in trace.spans
                if n == "call" and lo <= s and e <= hi)
    names = sorted({n for _, _, n, _ in trace.program})
    spans = {}
    for n in names:
        keys = sorted({k for _, _, m, st in trace.program if m == n
                       for k, v in st.items() if isinstance(v, (int, float))})
        spans[n] = {
            "s_per_call": progtrace.span_s(trace, n) / calls,
            "self_s_per_call": progtrace.self_s(trace, n) / calls,
            "count": len(progtrace.in_window(trace, n)),
            "stats": {k: progtrace.stat_sum(trace, n, k) for k in keys},
        }
    idle = dict(progtrace.breakdown(trace)["idle_by_span"])
    in_program = sum(v for k, v in idle.items()
                     if k.startswith(progtrace.PREFIX))
    in_calls = in_program + idle.get("call:harness", 0)
    return {
        "calls": calls,
        "spans": spans,
        "idle_by_span": list(idle.items()),
        "call_idle_in_program_spans": in_program / in_calls if in_calls
        else None,
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import devtrace, progtrace
    from bench import run as bench_run

    kept = []

    def load(logdir):
        (path,) = glob.glob(f"{logdir}/**/*.xplane.pb", recursive=True)
        kept.append(progtrace.load_file(path))
        return kept[-1]

    devtrace.load = load
    rc = bench_run.main([
        "--workload", args.workload, "--seed", str(args.seed),
        "--seconds", str(args.seconds), "--trace", "1",
    ])
    if rc or not kept:
        return rc or 1
    print(json.dumps(report(kept[-1])), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
