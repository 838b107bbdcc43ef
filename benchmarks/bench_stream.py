"""Streaming-kernel benchmark: cross-PE FIFO dataflow programs
(core/fifo.py, DESIGN.md §11) across both simulator engines and both
wave backends, swept over the ``fifo_depth`` axis.

Produces the evidence file committed as ``BENCH_STREAM.json``:

  * per streaming kernel (``stream_dot``, ``filter_pipe``,
    ``stream_join``) at ``--scale-mult`` x the registry default scales:
    event-engine cycle counts and per-edge queue accounting (pushed /
    popped / max occupancy / push+pop stalls) at each swept depth — the
    backpressure evidence: depth 1 pins ``max_occupancy == 1`` and
    serializes the wave plan hardest, deeper queues relax the slot
    WAW/WAR chains into fewer, wider waves,
  * wave-plan stats (requests, waves, steps, parallelism, streamed
    token counts) per depth, ``executor.validate_plan``-checked,
  * bit-exactness everywhere: every engine / backend / depth result is
    asserted array-equal against the hand-written numpy oracles
    (kernels/dynloop/ref.py) — never against each other only,
  * the wave-backend path (``backend="pallas"``) wall-clock at the default
    depth, with the run_sequential one-request-per-step baseline over a
    ``--seq-steps`` prefix (measured and extrapolated numbers never
    share a key, same convention as bench_pallas.py).

``--smoke`` is the tier-1 CI gate: all three kernels at reduced scales
through BOTH engines (cycle + event, cycle counts asserted equal), the
numpy executor and the real wave-backend path at depths 1 and 4,
oracle-asserted, no JSON.

Usage:
    PYTHONPATH=src:. python benchmarks/bench_stream.py \
        --scale-mult 8 --out BENCH_STREAM.json
"""

from __future__ import annotations

import argparse
import json
import time

import numpy as np

from repro.compile_cache import use_compile_cache
from repro.core import executor, loopir as ir, programs, simulator
from repro.kernels import wave_exec
from repro.kernels.dynloop import ref

# tier-1 smoke scales: small enough for the cycle engine and the
# wave backend at two depths inside the tier-1 budget
SMOKE_SCALES = {"stream_dot": 12, "filter_pipe": 48, "stream_join": 32}
DEPTHS = (1, 2, 4)
DEFAULT_DEPTH = 4
# wave-parallelism bar at the default depth: the slot encoding must
# leave real cross-instance parallelism on the table
PAR_BAR = 1.5


def _copies(arrays):
    return {k: v.copy() for k, v in arrays.items()}


def _oracle(name, arrays, params):
    """The hand-written second semantics (kernels/dynloop/ref.py)."""
    if name == "stream_dot":
        return {
            "out": ref.stream_dot_ref(
                arrays["a"], arrays["bv"], arrays["out"],
                params["nb"], params["k"],
            )
        }
    if name == "filter_pipe":
        return {"y": ref.filter_pipe_ref(arrays["x"], arrays["y"])}
    assert name == "stream_join"
    return {"z": ref.stream_join_ref(arrays["u"], arrays["w"], arrays["z"])}


def _assert_oracle(name, label, got, oracle):
    for k, v in oracle.items():
        np.testing.assert_array_equal(
            got[k], v, err_msg=f"{name}: {label} diverged from oracle ({k})"
        )


def run_kernel(name, scale, *, engines=("event",), depths=DEPTHS,
               pallas_depths=(DEFAULT_DEPTH,), seq_steps=0):
    """One streaming kernel through engines + backends + depth sweep."""
    bench = programs.get(name)
    prog, arrays, params = bench.make(scale)
    oracle = _oracle(name, arrays, params)
    _assert_oracle(
        name, "interpret",
        ir.interpret(prog, _copies(arrays), params), oracle,
    )

    row = {"scale": scale, "engines": {}, "depths": {}}
    cycles_seen = {}
    for engine in engines:
        res = simulator.simulate(
            prog, _copies(arrays), params, mode="FUS2", engine=engine
        )
        _assert_oracle(name, f"{engine} engine", res.arrays, oracle)
        row["engines"][engine] = {
            "cycles": res.cycles, "fifo": res.fifo_stats,
        }
        cycles_seen[engine] = res.cycles
    if len(cycles_seen) > 1:
        assert len(set(cycles_seen.values())) == 1, (
            f"{name}: engine cycle counts diverged: {cycles_seen}"
        )

    for depth in depths:
        res_t = simulator.simulate(
            prog, _copies(arrays), params, mode="FUS2", engine="event",
            sim=simulator.SimParams(fifo_depth=depth),
        )
        _assert_oracle(name, f"event@depth={depth}", res_t.arrays, oracle)
        t0 = time.time()
        plan = executor.build_wave_plan(
            prog, _copies(arrays), params, fifo_depth=depth
        )
        t_plan = time.time() - t0
        executor.validate_plan(plan)
        res_np = executor.execute(
            prog, _copies(arrays), params, fifo_depth=depth
        )
        _assert_oracle(name, f"numpy@depth={depth}", res_np.arrays, oracle)
        d = {
            "cycles": res_t.cycles,
            "fifo": res_t.fifo_stats,
            "n_requests": plan.stats.n_requests,
            "n_waves": plan.stats.n_waves,
            "n_steps": plan.stats.n_steps,
            "parallelism": round(plan.stats.parallelism, 2),
            "n_tokens": sum(fe["n_tokens"] for fe in plan.fifo_edges),
            "plan_wall_s": round(t_plan, 3),
        }
        if depth == 1:
            for qs in res_t.fifo_stats:
                assert qs["max_occupancy"] == 1, (
                    f"{name}: depth-1 queue overfilled: {qs}"
                )
        if depth in pallas_depths:
            t0 = time.time()
            res_pl = wave_exec.run_plan(plan, arrays)
            t_wave = time.time() - t0
            assert res_pl.complete
            _assert_oracle(
                name, f"pallas@depth={depth}", res_pl.arrays, oracle
            )
            d["pallas_wall_s"] = round(t_wave, 3)
            d["pallas_steps"] = res_pl.n_steps
            if seq_steps:
                limit = min(seq_steps, plan.stats.n_requests)
                seq = wave_exec.run_sequential(
                    plan, arrays, check=False, max_steps=limit,
                )
                d["seq_extrapolated"] = not seq.complete
                d["seq_steps_measured"] = seq.n_steps
                d["seq_measured_wall_s"] = round(seq.elapsed, 3)
                if seq.complete:
                    d["speedup_vs_sequential"] = round(
                        seq.elapsed / max(t_wave, 1e-9), 2
                    )
                else:
                    est = (seq.elapsed / max(seq.n_steps, 1)
                           * plan.stats.n_requests)
                    d["seq_wall_s_extrapolated"] = round(est, 3)
                    d["speedup_vs_sequential_extrapolated"] = round(
                        est / max(t_wave, 1e-9), 2
                    )
        row["depths"][str(depth)] = d
    return row


def smoke():
    """Tier-1 CI gate: all streaming kernels through both engines and
    both backends at depths 1 and 4, everything oracle-asserted."""
    for name in programs.STREAM_KERNELS:
        scale = SMOKE_SCALES[name]
        row = run_kernel(
            name, scale, engines=("cycle", "event"),
            depths=(1, DEFAULT_DEPTH), pallas_depths=(1, DEFAULT_DEPTH),
        )
        bench = programs.get(name)
        prog, arrays, params = bench.make(scale)
        plan = executor.build_wave_plan(prog, _copies(arrays), params)
        seq = wave_exec.run_sequential(plan, arrays, check=True)
        assert seq.complete
        _assert_oracle(
            name, "sequential", seq.arrays, _oracle(name, arrays, params)
        )
        d1 = row["depths"]["1"]
        d4 = row["depths"][str(DEFAULT_DEPTH)]
        assert d1["n_waves"] > d4["n_waves"], (
            f"{name}: deeper queue did not relax the wave partition"
        )
        print(f"{name:12s} smoke OK: cycles={row['engines']['event']['cycles']}"
              f" (cycle==event), waves d1={d1['n_waves']} "
              f"d{DEFAULT_DEPTH}={d4['n_waves']}, "
              f"stalls d1={d1['fifo'][0]['push_stalls']}", flush=True)
    print(f"smoke OK: {len(programs.STREAM_KERNELS)} streaming kernels "
          "through both engines and both wave backends")


def bench(scale_mult: int = 8, seq_steps: int = 256) -> dict:
    out: dict = {"scale_mult": scale_mult, "seq_steps": seq_steps,
                 "fifo_depths": list(DEPTHS), "kernels": {}}
    for name in programs.STREAM_KERNELS:
        scale = programs.get(name).default_scale * scale_mult
        row = run_kernel(name, scale, seq_steps=seq_steps)
        out["kernels"][name] = row
        d = row["depths"]
        waves = {k: v["n_waves"] for k, v in d.items()}
        stalls = {k: v["fifo"][0]["push_stalls"] for k, v in d.items()}
        print(f"{name:12s} @{scale}: "
              f"{d[str(DEFAULT_DEPTH)]['n_requests']} req, waves {waves}, "
              f"push_stalls {stalls}, event cycles "
              f"{row['engines']['event']['cycles']}", flush=True)
    return out


def check_bar(data: dict) -> None:
    for name, row in data["kernels"].items():
        d = row["depths"]
        # deeper queues can only relax slot WAW/WAR chains
        assert (d["1"]["n_waves"] >= d["2"]["n_waves"]
                >= d[str(DEFAULT_DEPTH)]["n_waves"]), (
            f"{name}: wave count not monotone in fifo_depth"
        )
        assert d["1"]["n_waves"] > d[str(DEFAULT_DEPTH)]["n_waves"], (
            f"{name}: fifo_depth axis is flat — depth has no effect"
        )
        par = d[str(DEFAULT_DEPTH)]["parallelism"]
        assert par >= PAR_BAR, (
            f"{name}: wave parallelism {par} below the {PAR_BAR}x bar "
            f"at depth {DEFAULT_DEPTH}"
        )
        for k, v in d.items():
            for qs in v["fifo"]:
                assert qs["pushed"] == qs["popped"] > 0, (
                    f"{name}@depth={k}: unbalanced queue {qs}"
                )


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="BENCH_STREAM.json")
    ap.add_argument("--scale-mult", type=int, default=8)
    ap.add_argument("--seq-steps", type=int, default=256,
                    help="sequential-baseline steps measured before "
                    "extrapolating")
    ap.add_argument("--no-assert", action="store_true")
    ap.add_argument(
        "--smoke", action="store_true",
        help="tier-1 CI gate: reduced scales, both engines and both "
        "backends, oracle-asserted, no JSON",
    )
    a = ap.parse_args()
    use_compile_cache()
    if a.smoke:
        smoke()
        return
    data = bench(scale_mult=a.scale_mult, seq_steps=a.seq_steps)
    if not a.no_assert:
        check_bar(data)
    with open(a.out, "w") as f:
        json.dump(data, f, indent=2, sort_keys=True)
    waves = {
        k: {d: v["n_waves"] for d, v in row["depths"].items()}
        for k, row in data["kernels"].items()
    }
    print(f"wrote {a.out}: waves by depth {waves}")


if __name__ == "__main__":
    main()
