"""Record the small TPU trace that ``tests/bench/test_bench_trace.py`` keeps
as its fixture.

Usage, from the root of a checkout, on a machine with a TPU:

    python3 bench/record_trace.py <out.xplane.pb>

One warm-up call, then three ``execute()`` calls of ``spmv.small``
instances (64 rows) inside the harness's ``bench.window`` and
``bench.call`` spans,
with the harness's profiler options. Writes the session's ``.xplane.pb``
to the given path and prints its reduction. The benchmark's own runs do
not run this.
"""

import glob
import json
import pathlib
import shutil
import sys
import tempfile

ROOT = pathlib.Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    out = pathlib.Path((argv or sys.argv[1:])[0])
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import jax

    from bench import adapter, devtrace, harness, spec

    if jax.devices()[0].platform != "tpu":
        print("record_trace: needs a TPU", file=sys.stderr)
        return 1
    cell = spec.load_cell("spmv.small", ROOT)
    params = cell.params
    ref = cell.reference_module()
    program = cell.program_module().build(params)
    inst = [ref.generate(params, harness.instance_rng(5, 0, k),
                         harness.base_rng(k)) for k in range(4)]
    adapter.call(program, *inst[0])
    logdir = tempfile.mkdtemp(prefix="bench_trace_")
    try:
        jax.profiler.start_trace(
            logdir, profiler_options=harness._profile_options())
        with jax.profiler.TraceAnnotation("bench.window"):
            for arrays, pp in inst[1:]:
                with jax.profiler.TraceAnnotation("bench.call"):
                    adapter.call(program, arrays, pp)
        jax.profiler.stop_trace()
        (path,) = glob.glob(f"{logdir}/**/*.xplane.pb", recursive=True)
        shutil.copy(path, out)
    finally:
        shutil.rmtree(logdir, ignore_errors=True)
    t = devtrace.load_file(str(out))
    print(json.dumps({
        "busy_s": devtrace.busy_s(t),
        "wave_loop_s": devtrace.program_s(t, "wave_loop"),
        "breakdown": devtrace.breakdown(t),
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
