"""Benchmark harness entry point: one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows:
  * table1_*       — paper Table 1 (cycles per mode + speedups)
  * fig5_pruning   — hazard-pair pruning on the FFT code (Fig. 5)
  * forwarding_*   — §7.3.2 store-to-load forwarding impact
  * wave_*         — TPU wave-executor parallelism (Fig. 1c analogue)
"""

from __future__ import annotations

import time

import numpy as np


def _t(fn, *args, reps=3, **kw):
    fn(*args, **kw)
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args, **kw)
    return (time.perf_counter() - t0) / reps * 1e6, out


def bench_table1(emit, scale_mult=1, engine="event", scales=None,
                 trace_mode="auto"):
    from benchmarks.paper_table1 import run_table, scaled, summarize

    rows = run_table(
        scales=scales or scaled(scale_mult), engine=engine,
        trace_mode=trace_mode,
    )
    for r in rows:
        emit(
            f"table1_{r['kernel']}",
            r["FUS2_wall_s"] * 1e6,
            f"STA={r['STA']};LSQ={r['LSQ']};FUS1={r['FUS1']};FUS2={r['FUS2']}"
            f";fus2_vs_lsq={r['LSQ']/r['FUS2']:.2f}"
            f";fus2_vs_sta={r['STA']/r['FUS2']:.2f}",
        )
    s = summarize(rows)
    emit(
        "table1_hmean", 0,
        f"fus2_vs_lsq={s['FUS2_vs_LSQ_hmean']:.2f}"
        f";fus2_vs_sta={s['FUS2_vs_STA_hmean']:.2f}"
        f";paper=4x_and_14x",
    )


def bench_pruning(emit):
    from repro.core import dae, hazards, monotonic, programs

    for name in ("fft", "matpower", "pagerank"):
        prog, arrays, params = programs.get(name).make(
            64 if name != "fft" else 64
        )
        d = dae.decouple(prog)
        infos = monotonic.analyze_program(prog)
        us, plan = _t(
            hazards.build_plan, prog, d, infos, True, reps=3
        )
        total = len(plan.pairs) + len(plan.pruned)
        emit(
            f"fig5_pruning_{name}", us,
            f"enumerated={total};kept={len(plan.pairs)};pruned={len(plan.pruned)}",
        )


def bench_forwarding(emit):
    from repro.core import programs, simulator

    for name in ("hist+add", "matpower", "fft"):
        prog, arrays, params = programs.get(name).make(64)
        f1 = simulator.simulate(prog, arrays, params, mode="FUS1")
        f2 = simulator.simulate(prog, arrays, params, mode="FUS2")
        emit(
            f"forwarding_{name}", 0,
            f"fus1={f1.cycles};fus2={f2.cycles}"
            f";speedup={f1.cycles/f2.cycles:.2f};forwards={f2.forwards}",
        )


def bench_waves(emit):
    from repro.core import executor, programs

    for name in programs.all_names():
        scale = 64 if name == "fft" else 96
        prog, arrays, params = programs.get(name).make(scale)
        spec = "auto" if programs.get(name).speculative else "off"
        us, res = _t(
            executor.execute, prog, arrays, params, speculation=spec, reps=1
        )
        emit(
            f"wave_{name}", us,
            f"requests={res.stats.n_requests};waves={res.stats.n_waves}"
            f";parallelism={res.stats.parallelism:.1f}",
        )


def main(argv=None) -> None:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument(
        "--smoke", action="store_true",
        help="small-scale CI smoke: Table 1 + pruning only",
    )
    ap.add_argument(
        "--scale-mult", type=int, default=1,
        help="run Table 1 at N x the default scales (event engine "
        "sustains >= 8x; see BENCH_ENGINE.json)",
    )
    ap.add_argument("--engine", choices=("cycle", "event"), default="event")
    ap.add_argument(
        "--trace-mode", choices=("auto", "compiled", "interp"),
        default="auto", help="AGU/CU front-end path (DESIGN.md §7)",
    )
    args = ap.parse_args(argv)

    print("name,us_per_call,derived")

    def emit(name, us, derived):
        print(f"{name},{us:.1f},{derived}", flush=True)

    if args.smoke:
        from benchmarks.paper_table1 import scaled

        smoke_scales = {k: max(v // 8, 16) for k, v in scaled(1).items()}
        smoke_scales["fft"] = 64
        bench_table1(emit, engine=args.engine, scales=smoke_scales,
                     trace_mode=args.trace_mode)
        bench_pruning(emit)
        return

    bench_table1(emit, scale_mult=args.scale_mult, engine=args.engine,
                 trace_mode=args.trace_mode)
    bench_pruning(emit)
    bench_forwarding(emit)
    bench_waves(emit)


if __name__ == "__main__":
    main()
