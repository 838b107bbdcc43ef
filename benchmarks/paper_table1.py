"""Paper Table 1 reproduction: STA / LSQ / FUS1 / FUS2 on the nine
irregular kernels, as simulated cycles + speedups.

Absolute FPGA wall-clock is not reproducible off-chip; the deliverable
is the *structure* of Table 1 — which approach wins where, and by
roughly how much — under the documented DU timing model
(core/simulator.SimParams). The paper's headline: FUS2 ≈ 14x over STA
and ≈ 4x over LSQ (harmonic means; dominated by bnn/hist-style codes).
"""

from __future__ import annotations

import time

import numpy as np

from repro.core import loopir, programs, simulator

MODES = ("STA", "LSQ", "FUS1", "FUS2")

# benchmark scales sized so the full table runs in ~a minute on CPU with
# the cycle engine; the event engine (default) runs these much faster
# and supports --scale-mult well beyond 8x (see BENCH_ENGINE.json)
SCALES = {
    "RAWloop": 2048, "WARloop": 2048, "WAWloop": 2048,
    "bnn": 64, "pagerank": 96, "fft": 256, "matpower": 64,
    "hist+add": 1024, "tanh+spmv": 256,
}


def scaled(mult: int) -> dict[str, int]:
    """SCALES at an integer multiple (fft stays a power of two)."""
    if mult < 1:
        raise ValueError(f"--scale-mult must be >= 1, got {mult}")
    out = {}
    for k, v in SCALES.items():
        s = v * mult
        if k == "fft":
            s = 1 << (s.bit_length() - 1)
        out[k] = s
    return out


def run_table(scales=None, validate=False, engine="event", trace_mode="auto"):
    scales = scales or SCALES
    rows = []
    for name in programs.TABLE1:
        prog, arrays, params = programs.get(name).make(scales[name])
        oracle = loopir.interpret(prog, arrays, params)
        row = {"kernel": name}
        for mode in MODES:
            t0 = time.time()
            res = simulator.simulate(
                prog, arrays, params, mode=mode,
                validate=validate and mode != "STA", engine=engine,
                trace_mode=trace_mode,
            )
            for k in oracle:
                assert np.allclose(res.arrays[k], oracle[k], atol=1e-9), (
                    name, mode, k,
                )
            row[mode] = res.cycles
            row[f"{mode}_wall_s"] = time.time() - t0
            if mode == "FUS2":
                row["forwards"] = res.forwards
        n_pes = len(simulator.Compiled(prog, False).dae.pes)
        row["PEs"] = n_pes
        rows.append(row)
    return rows


# single implementation lives in the importable library layer
from repro.dse.summary import harmonic_mean  # noqa: E402


def summarize(rows):
    out = {}
    for base in ("STA", "LSQ"):
        speedups = [r[base] / r["FUS2"] for r in rows]
        out[f"FUS2_vs_{base}_hmean"] = harmonic_mean(speedups)
        out[f"FUS2_vs_{base}_max"] = max(speedups)
    out["FUS2_vs_FUS1_hmean"] = harmonic_mean(
        [r["FUS1"] / r["FUS2"] for r in rows]
    )
    return out


def main(csv=True, scale_mult=1, engine="event", trace_mode="auto"):
    rows = run_table(
        scales=scaled(scale_mult), engine=engine, trace_mode=trace_mode
    )
    if csv:
        print("kernel,PEs,STA,LSQ,FUS1,FUS2,fus2_vs_sta,fus2_vs_lsq,forwards")
        for r in rows:
            print(
                f"{r['kernel']},{r['PEs']},{r['STA']},{r['LSQ']},{r['FUS1']},"
                f"{r['FUS2']},{r['STA']/r['FUS2']:.2f},"
                f"{r['LSQ']/r['FUS2']:.2f},{r['forwards']}"
            )
        s = summarize(rows)
        print(
            f"hmean,,,,,,{s['FUS2_vs_STA_hmean']:.2f},"
            f"{s['FUS2_vs_LSQ_hmean']:.2f},"
        )
    return rows


if __name__ == "__main__":
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--scale-mult", type=int, default=1,
                    help="run Table 1 at N x the default scales")
    ap.add_argument("--engine", choices=("cycle", "event"), default="event")
    ap.add_argument(
        "--trace-mode", choices=("auto", "compiled", "interp"), default="auto",
        help="AGU/CU front-end: compiled (vectorized), interp (reference), "
        "or auto (compile where exact, fall back per PE)",
    )
    a = ap.parse_args()
    main(scale_mult=a.scale_mult, engine=a.engine, trace_mode=a.trace_mode)
