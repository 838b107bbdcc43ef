"""Design-space sweep benchmark: batched DSE engine vs looped simulate().

Produces the evidence file committed as ``BENCH_DSE.json``:

  * a >=32-point sweep over the nine Table-1 kernels at ``--scale-mult``
    (modes x trace modes x DU sizings, plus an STA engine-axis grid),
  * the **looped baseline**: one standalone ``simulate()`` call per
    point, exactly as a pre-DSE harness would script it,
  * the batched run (``repro.dse.sweep``): cold serial, cold parallel
    (``--workers``), and warm (cache) wall-clock,
  * **bit-identity verification**: every sweep point's SimResult
    (cycles, DRAM traffic, forwards, and a sha256 of every final
    array) equals its standalone call,
  * per-kernel speedups/Pareto sizings (``dse.summary``) and the
    config-batched §5.5 slack profile.

Sweep-service flags (DESIGN.md §13):

  * ``--shard i/n`` runs only shard ``i`` of an ``n``-way
    ``dse.shard_plan`` partition (multi-host use; pair with
    ``--cache-dir`` and merge with ``dse.merge_results``),
  * ``--resume`` re-plans from the surviving ``--cache-dir`` after an
    interrupted run (only missing unique runs execute),
  * ``--stream`` prints each point as it lands plus the live partial
    Pareto front size (``dse.summary.ParetoTracker``),
  * ``--shard-check N`` re-runs the sweep as N shards in fresh caches
    and asserts ``merge_results`` equals the single-host result
    bit-for-bit (the nightly 594-point gate uses ``--shard-check 2``),
  * ``--differential`` turns on per-point differential validation.

Acceptance bars asserted at the end (mirroring bench_trace.py): exact
per-point identity and >=5x cold sweep throughput vs. the loop. The
``--smoke`` CI gate additionally asserts the shard+merge identity, a
kill+resume round trip (child sweep SIGKILLed mid-run, resumed from
the surviving cache, bit-identical to uninterrupted), and that the
streaming Pareto front's every prefix matches the batch recompute.

Usage:
    PYTHONPATH=src:. python benchmarks/sweep.py --out BENCH_DSE.json \
        --scale-mult 8
"""

from __future__ import annotations

import argparse
import hashlib
import json
import os
import signal
import subprocess
import sys
import tempfile
import time

import numpy as np

from benchmarks.paper_table1 import scaled
from repro import dse
from repro.core import programs, simulator
from repro.dse import summary

# DU sizings x calibration knobs. The last three vary parameters only
# some modes read (dse.spec.MODE_SIM_FIELDS): sta-ii-* move the STA
# static-II calibration (dynamic modes provably unaffected), fwd-4 the
# §5.5 forwarding latency (only FUS2 reads it) — the planner re-runs
# exactly the modes each knob can affect, the loop baseline re-runs
# everything.
SIZINGS = {
    "base": {},
    "narrow": {"burst_size": 4, "dram_latency": 100},
    "deep": {"burst_size": 32, "dram_latency": 400},
    "sta-ii-120": {"sta_mem_dep_ii": 120},
    "sta-ii-240": {"sta_mem_dep_ii": 240},
    "fwd-4": {"forward_latency": 4},
}


def build_spec(scales: dict) -> dse.SweepSpec:
    """The evidence sweep: 9 kernels x (3 modes x 3 trace modes x 6
    sizings) + an STA engine-axis grid (STA is engine-invariant — the
    planner dedups it; the loop baseline pays for every point)."""
    kernels = list(programs.TABLE1)
    return dse.SweepSpec(
        kernels=kernels,
        scales=scales,
        modes=("STA", "FUS1", "FUS2"),
        trace_modes=("auto", "compiled", "interp"),
        sizings=SIZINGS,
        extra=(
            dse.SweepSpec(
                kernels=kernels, scales=scales, modes=("STA",),
                engines=("cycle",), trace_modes=("auto", "interp"),
                sizings=SIZINGS,
            ),
        ),
    )


def _sig(res: simulator.SimResult) -> dict:
    """Comparable signature of a SimResult; arrays by content hash so
    the baseline needn't stay resident."""
    h = {}
    for k in sorted(res.arrays):
        a = np.ascontiguousarray(res.arrays[k])
        h[k] = hashlib.sha256(
            a.dtype.str.encode() + repr(a.shape).encode() + a.tobytes()
        ).hexdigest()
    return {
        "cycles": res.cycles, "dram_bursts": res.dram_bursts,
        "dram_requests": res.dram_requests, "forwards": res.forwards,
        "arrays": h,
    }


def run_baseline(points) -> tuple[float, dict]:
    """The pre-DSE harness: one full simulate() per point, re-compiling
    everything every time."""
    sigs = {}
    t0 = time.perf_counter()
    for p in points:
        prog, arrays, params = programs.get(p.kernel).make(p.scale)
        res = simulator.simulate(
            prog, arrays, params, mode=p.mode, sim=p.sim_params(),
            engine=p.engine, trace_mode=p.trace_mode,
        )
        sigs[p.point_id] = _sig(res)
    return time.perf_counter() - t0, sigs


def _same_result(a: dse.SweepResult, b: dse.SweepResult) -> list:
    """Point ids where two sweep results differ (bit-level)."""
    bad = []
    assert len(a.points) == len(b.points)
    for pa, pb in zip(a.points, b.points):
        if (pa is None) != (pb is None):
            bad.append((pa or pb).point.point_id)
        elif pa is not None and _sig(pa.result) != _sig(pb.result):
            bad.append(pa.point.point_id)
    return bad


def check_shard_merge(spec, whole: dse.SweepResult, n_shards: int) -> dict:
    """Run the sweep as ``n_shards`` independent shards (fresh caches),
    merge with ``dse.merge_results``, assert bit-identity with the
    single-host result."""
    plan = dse.shard_plan(spec, n_shards)
    shards = []
    with tempfile.TemporaryDirectory() as td:
        for i in range(n_shards):
            shards.append(dse.sweep_shard(
                spec, i, n_shards, cache_dir=os.path.join(td, f"s{i}"),
            ))
        merged = dse.merge_results(shards)
    bad = _same_result(merged, whole)
    assert not bad, f"shard merge diverged from single-host: {bad[:5]}"
    owned = sum(len([p for p in s.points if p is not None]) for s in shards)
    assert owned == len([p for p in whole.points if p is not None])
    return {
        "n_shards": n_shards,
        "loads": list(plan.loads),
        "merged_bit_identical": True,
    }


_CHILD_CODE = """
import sys
from benchmarks.sweep import build_spec
from benchmarks.paper_table1 import scaled
from repro import dse
scales = {k: max(v // 16, 16) for k, v in scaled(1).items()}
scales["fft"] = 64
print("child: starting", flush=True)
dse.sweep(build_spec(scales), cache_dir=sys.argv[1], workers=1)
print("child: done", flush=True)
"""


def check_kill_resume(spec, whole: dse.SweepResult) -> dict:
    """SIGKILL a child sweep mid-run, resume from its surviving cache,
    assert the resumed run only executes the missing unique runs and is
    bit-identical to the uninterrupted result."""
    with tempfile.TemporaryDirectory() as td:
        cache = os.path.join(td, "cache")
        env = dict(os.environ)
        env["PYTHONPATH"] = os.pathsep.join(
            p for p in ("src", ".", env.get("PYTHONPATH", "")) if p
        )
        child = subprocess.Popen(
            [sys.executable, "-c", _CHILD_CODE, cache],
            env=env, stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL,
        )
        journal = os.path.join(cache, dse.SweepJournal.FILENAME)
        deadline = time.time() + 120.0
        lines = 0
        while time.time() < deadline and child.poll() is None:
            if os.path.exists(journal):
                with open(journal) as f:
                    lines = sum(1 for _ in f)
                if lines >= 2:
                    break
            time.sleep(0.05)
        finished_early = child.poll() is not None
        if not finished_early:
            child.send_signal(signal.SIGKILL)
        child.wait()

        res = dse.sweep(spec, cache_dir=cache, resume=True)
    st = res.stats
    assert st.n_cache_hits + st.n_executed == st.n_unique_runs
    if not finished_early:
        # the kill landed mid-run: the resume must have found surviving
        # work AND had something left to do
        assert st.n_resumed_runs >= 1, "resume found no surviving cache"
        assert st.n_executed >= 1, "child finished before the kill?"
    bad = _same_result(res, whole)
    assert not bad, f"kill+resume diverged from uninterrupted: {bad[:5]}"
    return {
        "journal_lines_at_kill": lines,
        "child_finished_early": finished_early,
        "resumed_runs": st.n_resumed_runs,
        "executed_after_resume": st.n_executed,
        "resume_bit_identical": True,
    }


def check_stream_pareto(spec) -> dict:
    """Drive the sweep through ``on_point`` feeding a ParetoTracker;
    assert every streaming prefix front equals the batch
    ``pareto_front`` recompute over the rows seen so far."""
    tracker = summary.ParetoTracker()
    rows: list = []

    def on_point(pr):
        row = {
            "cycles": pr.result.cycles,
            "dram_bursts": pr.result.dram_bursts,
            "id": pr.point.point_id,
        }
        rows.append(row)
        tracker.update(row)
        batch = [rows[i] for i in summary.pareto_front(rows)]
        assert tracker.front() == batch, (
            f"streaming front diverged at point {len(rows)}"
        )

    dse.sweep(spec, on_point=on_point)
    return {
        "n_points_streamed": len(rows),
        "final_front_size": len(tracker.front()),
        "every_prefix_matches_batch": True,
    }


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--out", default="BENCH_DSE.json")
    ap.add_argument("--scale-mult", type=int, default=8)
    ap.add_argument(
        "--workers", type=int, default=0,
        help="parallel group workers for the headline run (0 = cpu count)",
    )
    ap.add_argument(
        "--skip-serial", action="store_true",
        help="skip the cold serial sweep measurement",
    )
    ap.add_argument(
        "--target-speedup", type=float, default=5.0,
        help="cold-sweep throughput bar to assert (the committed "
        "BENCH_DSE.json evidence uses the default 5.0 at --scale-mult "
        "8; CI canary runs at smaller scales assert a lower bar since "
        "shared-artifact amortization shrinks with scale)",
    )
    ap.add_argument(
        "--smoke", action="store_true",
        help="tiny scales, correctness-only (no speedup bar): CI gate. "
        "Also exercises shard+merge, kill+resume and streaming-Pareto "
        "service checks",
    )
    ap.add_argument(
        "--cache-dir", default=None,
        help="persistent result cache (default: fresh tempdir per phase)",
    )
    ap.add_argument(
        "--shard", default=None, metavar="I/N",
        help="run only shard I of an N-way partition (multi-host use; "
        "pair with --cache-dir, merge with dse.merge_results). Skips "
        "the baseline and speedup bars",
    )
    ap.add_argument(
        "--resume", action="store_true",
        help="re-plan from the surviving --cache-dir (missing runs only)",
    )
    ap.add_argument(
        "--stream", action="store_true",
        help="print each point as it lands + live partial Pareto front",
    )
    ap.add_argument(
        "--shard-check", type=int, default=0, metavar="N",
        help="after the headline run, redo the sweep as N shards and "
        "assert dse.merge_results equals the single-host result",
    )
    ap.add_argument(
        "--differential", action="store_true",
        help="per-point differential validation during the sweep",
    )
    a = ap.parse_args(argv)

    workers = a.workers or (os.cpu_count() or 1)
    if a.smoke:
        scales = {k: max(v // 16, 16) for k, v in scaled(1).items()}
        scales["fft"] = 64
    else:
        scales = scaled(a.scale_mult)
    spec = build_spec(scales)
    points = spec.points()
    print(f"sweep: {len(points)} points over {len(programs.TABLE1)} kernels "
          f"at scales {scales}", flush=True)

    tracker = summary.ParetoTracker()

    def stream_cb(pr):
        row = {"cycles": pr.result.cycles,
               "dram_bursts": pr.result.dram_bursts}
        grew = tracker.update(row)
        print(f"point {pr.point.point_id}: cycles={pr.result.cycles} "
              f"cached={pr.cached} front={len(tracker.front())}"
              f"{' *' if grew else ''}", flush=True)

    on_point = stream_cb if a.stream else None

    # --- shard worker path: run the owned slice, write it, exit -----------
    if a.shard is not None:
        idx, n = (int(x) for x in a.shard.split("/"))
        t0 = time.perf_counter()
        if a.cache_dir:
            res = dse.sweep_shard(
                spec, idx, n, cache_dir=a.cache_dir, workers=workers,
                resume=a.resume, differential=a.differential,
                on_point=on_point,
            )
        else:
            with tempfile.TemporaryDirectory() as td:
                res = dse.sweep_shard(
                    spec, idx, n, cache_dir=td, workers=workers,
                    differential=a.differential, on_point=on_point,
                )
        wall = time.perf_counter() - t0
        st = res.stats
        data = {
            "shard": [idx, n], "wall_s": round(wall, 2),
            "n_points_owned": len([p for p in res.points if p is not None]),
            "n_unique_runs": st.n_unique_runs,
            "n_cache_hits": st.n_cache_hits,
            "n_executed": st.n_executed,
        }
        with open(a.out, "w") as f:
            json.dump(data, f, indent=2, sort_keys=True)
        print(f"wrote {a.out}: shard {idx}/{n}, "
              f"{data['n_points_owned']} points in {wall:.1f}s")
        return data

    base_wall, base_sigs = run_baseline(points)
    print(f"baseline loop: {base_wall:.1f}s "
          f"({base_wall / len(points):.2f}s/point)", flush=True)

    walls = {}
    if not a.skip_serial:
        with tempfile.TemporaryDirectory() as td:
            t0 = time.perf_counter()
            res_serial = dse.sweep(spec, cache_dir=td, workers=1)
            walls["cold_serial_s"] = time.perf_counter() - t0
        print(f"dse cold serial: {walls['cold_serial_s']:.1f}s "
              f"({res_serial.n_unique_runs} unique runs)", flush=True)

    if a.cache_dir:
        td_ctx = None
        cache_dir = a.cache_dir
    else:
        td_ctx = tempfile.TemporaryDirectory()
        cache_dir = td_ctx.name
    try:
        t0 = time.perf_counter()
        res = dse.sweep(
            spec, cache_dir=cache_dir, workers=workers, profile=True,
            resume=a.resume, differential=a.differential,
            on_point=on_point,
        )
        walls["cold_parallel_s"] = time.perf_counter() - t0
        t0 = time.perf_counter()
        res_warm = dse.sweep(spec, cache_dir=cache_dir, workers=1)
        walls["warm_s"] = time.perf_counter() - t0
    finally:
        if td_ctx is not None:
            td_ctx.cleanup()
    print(f"dse cold x{workers} workers: {walls['cold_parallel_s']:.1f}s; "
          f"warm: {walls['warm_s']:.1f}s "
          f"({res_warm.n_cache_hits}/{res_warm.n_unique_runs} hits)",
          flush=True)

    # --- bit-identity of every point vs its standalone call ---------------
    mismatches = []
    for pr in res.points:
        if _sig(pr.result) != base_sigs[pr.point.point_id]:
            mismatches.append(pr.point.point_id)
    identical = not mismatches
    print(f"bit-identity: {len(res.points) - len(mismatches)}/"
          f"{len(res.points)} points identical", flush=True)

    rows = res.rows()
    data = {
        "scale_mult": a.scale_mult if not a.smoke else 0,
        "smoke": a.smoke,
        "scales": scales,
        "n_points": len(points),
        "n_unique_runs": res.n_unique_runs,
        "workers": workers,
        "baseline_loop_s": round(base_wall, 2),
        **{k: round(v, 2) for k, v in walls.items()},
        "speedup_parallel": round(base_wall / walls["cold_parallel_s"], 2),
        "speedup_warm": round(base_wall / max(walls["warm_s"], 1e-9), 1),
        "target_speedup": a.target_speedup,
        "all_points_bit_identical": identical,
        "summary": summary.summarize_sweep(rows),
        "forward_slack_profile": res.profile,
        "group_stats": res.groups,
    }
    if "cold_serial_s" in walls:
        data["speedup_serial"] = round(base_wall / walls["cold_serial_s"], 2)

    # --- sweep-service checks (DESIGN.md §13) ------------------------------
    n_shard_check = a.shard_check or (2 if a.smoke else 0)
    if n_shard_check:
        data["shard_check"] = check_shard_merge(spec, res, n_shard_check)
        print(f"shard check: {n_shard_check}-way merge bit-identical "
              f"(loads {data['shard_check']['loads']})", flush=True)
    if a.smoke:
        data["kill_resume"] = check_kill_resume(spec, res)
        print(f"kill+resume: killed at "
              f"{data['kill_resume']['journal_lines_at_kill']} journal "
              f"lines, resumed {data['kill_resume']['resumed_runs']} runs, "
              f"bit-identical", flush=True)
        data["stream_pareto"] = check_stream_pareto(spec)
        print(f"streaming pareto: {data['stream_pareto']['n_points_streamed']}"
              f" points, every prefix front matches batch recompute",
              flush=True)

    with open(a.out, "w") as f:
        json.dump(data, f, indent=2, sort_keys=True)

    assert identical, f"sweep diverged from standalone simulate(): {mismatches[:5]}"
    if not a.smoke:
        assert data["speedup_parallel"] >= data["target_speedup"], (
            f"sweep throughput regressed: {data['speedup_parallel']}x "
            f"< target {data['target_speedup']}x vs the looped baseline"
        )
    print(
        f"wrote {a.out}: {data['speedup_parallel']}x cold "
        f"(serial {data.get('speedup_serial', '-')}x, warm "
        f"{data['speedup_warm']}x) vs looped simulate(); "
        f"bit-identical={identical}"
    )
    return data


if __name__ == "__main__":
    main()
