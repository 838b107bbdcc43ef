"""Wave-execution backend: drive a ``WavePlan`` on the JAX device.

``run_plan`` is the hardware half of the DESIGN.md §2 split: the plan
(from ``core/executor.build_wave_plan``) carries the batched-step
partition, flat addresses, op tables and captured CU operand streams.
Execution is two-phase:

    resolve — the shared ``executor.drive_plan`` driver runs over a
              host-side image: op-table closures produce each step's
              store values and §6 valid bits from the gathers of
              *strictly earlier* steps (WavePlan contract 5), every
              gather/guard/value is pinned request-exact against the
              oracle reference streams, and the per-step
              (addr, write, sval) tables are recorded,
    device  — the steps are split into segments (``plan_segments``),
              each segment's recorded tables are packed at once into
              padded power-of-two tables, and each segment runs as
              **one** jitted ``wave_loop`` call — a
              ``jax.lax.fori_loop`` over the step tables chaining the
              flat uint32-pair memory image through the carry (XLA
              gather/scatter, the same program on every platform). Final
              arrays are unpacked from the device image (and under
              ``check=True`` each segment's device gathers are compared
              bit for bit, every real lane at once, against the resolve
              phase's).

The split mirrors what the DU is: the resolve phase *disambiguates*
(and owns every divergence check); the device phase only *moves* —
which is why the whole memory schedule compiles to O(segments) kernel
launches instead of one per step. A segment's cost is host work, not
kernel time: about 1.76 ms on a TPU v5e for the transfers, the
dispatch, the wait and the copy back, against 0.03 ms of a narrow
``wave_loop``, plus about 38 ns a padded lane. So ``plan_segments``
trades launches against padding: a segment's width is that of its
widest step, and runs of narrow steps are merged into their wider
neighbours wherever the padding costs less than a launch
(``_LAUNCH_LANES``). Pad lanes target a scratch row past the image; pad
steps are no-ops (see ``kernel.py``).

Spans (``repro.trace``): ``repro.resolve`` and ``repro.device`` bound
the two phases (``resolve_s`` and ``device_s`` are their seconds);
inside the device phase each segment has ``repro.device.pack`` (the
padded tables; ``lanes`` counts its real requests),
``repro.device.launch`` (transfers and the ``wave_loop`` call;
``new_shape`` is 1 where its shape is new to the process, so it
compiles), and under ``check`` ``repro.device.wait``
and ``repro.device.check`` (the copy back and compare); the final
image copy, compare and unpack are ``repro.unpack``.

``run_sequential`` executes the same plan one request per step — the
paper's non-fused baseline on identical hardware (a single bucket-8
segment of ``n_requests`` steps) — and is what
``benchmarks/bench_pallas.py`` compares wave execution against.

Cross-PE FIFO edges (DESIGN.md §11) need no support here: the plan
encodes each edge as circular pseudo-memory slots inside ``mem_size``
(zero-init in ``flat_image``, absent from ``array_order``), so pushes
and pops flow through the ordinary scatter/gather path — a popped
token is literally a gather from the slot its push scattered to, and
the resolve phase's request-exact checks pin the whole queue protocol
against the oracle.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import numpy as np

from repro import trace
from repro.core import executor as execlib

__all__ = ["run_plan", "run_sequential", "WaveExecResult"]

_MIN_BUCKET = 8
# one segment's fixed host cost (transfers, dispatch, wait), counted in
# padded lanes: 1.76 ms a segment over 38.5 ns a padded lane on a TPU
# v5e (derivation in PERF.md)
_LAUNCH_LANES = 45_000
# (padded steps, width, image rows) of every segment launched in this
# process: a key not yet in it makes its launch trace and compile
_SHAPES_SEEN: set = set()


@dataclasses.dataclass
class WaveExecResult:
    """Final arrays + execution profile of one backend run."""

    arrays: dict[str, np.ndarray]
    stats: execlib.WaveStats
    n_steps: int  # executed gather→scatter steps (pad steps excluded)
    elapsed: float  # seconds: resolve + device phases
    complete: bool  # False when max_steps truncated the run
    resolve_s: float = 0.0  # seconds of the repro.resolve span
    device_s: float = 0.0  # seconds of the repro.device span
    n_segments: int = 0  # wave_loop launches (fori_loop calls)


def _pow2(n: int) -> int:
    """Smallest power of two >= ``n`` (1 for ``n`` <= 1)."""
    return 1 << max(n - 1, 0).bit_length()


def _buckets(widths: np.ndarray) -> np.ndarray:
    """Each step's lane bucket: the smallest power of two >= its width,
    and at least ``_MIN_BUCKET``."""
    w = np.asarray(widths, dtype=np.int64)
    # frexp's exponent of w - 1 is its bit length (exact below 2**53)
    bits = np.frexp(np.maximum(w - 1, 0).astype(np.float64))[1]
    return np.maximum(_MIN_BUCKET, np.left_shift(1, bits.astype(np.int64)))


def plan_segments(widths) -> list[tuple[int, int, int]]:
    """Split steps of the given widths (requests a step) into segments,
    ``[(start step, end step, lane width)]``, one ``wave_loop`` each.

    A segment's width is the bucket of its widest step and its step
    count is padded to a power of two, so it costs ``_LAUNCH_LANES +
    steps_pad * width`` under the model, and the split aims at the least
    total. Runs of steps with equal buckets start as segments; left to
    right, the open segment takes in the next run wherever the merged
    segment costs no more than the two apart. Each merge lowers the
    total or keeps it, so no split costs more than the runs alone.
    """
    b = _buckets(widths)
    if not len(b):
        return []
    cut = np.flatnonzero(b[1:] != b[:-1]) + 1
    starts = [0, *cut.tolist()]
    ends = [*cut.tolist(), len(b)]
    segs = []
    for s, e, wd in zip(starts, ends, b[starts].tolist()):
        if segs:
            s0, s1, w0 = segs[-1]
            apart = _LAUNCH_LANES + _pow2(s1 - s0) * w0 + _pow2(e - s) * wd
            if _pow2(e - s0) * max(w0, wd) <= apart:
                segs[-1] = (s0, e, max(w0, wd))
                continue
        segs.append((s, e, wd))
    return segs


def _to_u32(f64: np.ndarray) -> np.ndarray:
    return np.ascontiguousarray(f64, dtype=np.float64).view(
        np.uint32
    ).reshape(-1, 2)


def _from_u32(u32: np.ndarray) -> np.ndarray:
    return np.ascontiguousarray(u32, dtype=np.uint32).view(
        np.float64
    ).reshape(-1)


def _run(
    plan: execlib.WavePlan,
    arrays: dict[str, np.ndarray],
    step_of: Optional[np.ndarray],
    n_steps: Optional[int],
    *,
    compute: str,
    check: bool,
    max_steps: Optional[int],
) -> WaveExecResult:
    import jax.numpy as jnp

    from repro.kernels.wave_exec.kernel import wave_loop

    assert plan.mem_size < 2**31 - 1, "flat image exceeds int32 addressing"
    # flat f64 image plus the scratch row pad/non-write lanes target
    scratch = plan.mem_size
    mem_f64 = np.zeros(plan.mem_size + 1, dtype=np.float64)
    mem_f64[:plan.mem_size] = execlib.flat_image(plan, arrays)[
        :plan.mem_size
    ]

    # --- resolve phase: op-table compute + checks over a host image ------
    # records the per-step memory traffic the device phase will replay
    host_mem = mem_f64.copy()
    rec: list[tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]] = []

    def mem_step(flat_addr, write, sval):
        got = host_mem[flat_addr]  # fancy indexing copies: pre-step state
        host_mem[flat_addr[write]] = sval[write]
        rec.append((flat_addr, write, sval, got))
        return got

    with trace.span("resolve", steps=int(
            plan.stats.n_steps if n_steps is None else n_steps)) as resolve:
        steps, complete = execlib.drive_plan(
            plan, mem_step, frozen=arrays, step_of=step_of, n_steps=n_steps,
            lib="np" if compute == "host" else "jnp", check=check,
            max_steps=max_steps,
        )

    # --- device phase: segments from the cost model, one wave_loop each --
    with trace.span("device", h2d_bytes=mem_f64.nbytes) as device:
        mem_dev = jnp.asarray(_to_u32(mem_f64))
        lens = np.fromiter((len(a) for a, _, _, _ in rec), dtype=np.int64,
                           count=len(rec))
        segments = plan_segments(lens)
        device.set(segments=len(segments))
        for s0, s1, wd in segments:
            seg, n = rec[s0:s1], lens[s0:s1]
            ns, lanes = s1 - s0, int(n.sum())
            ns_pad = _pow2(ns)
            with trace.span("device.pack", steps=ns, steps_pad=ns_pad,
                            width=wd, lanes=lanes):
                # flat (step, lane) slot of every real request: step j's
                # requests fill row j of the padded tables from lane 0
                at = np.arange(lanes) + np.repeat(
                    np.arange(ns) * wd - (np.cumsum(n) - n), n)
                addrs = np.full((ns_pad, wd), scratch, dtype=np.int32)
                writes = np.zeros((ns_pad, wd), dtype=bool)
                svals = np.zeros((ns_pad, wd), dtype=np.float64)
                addrs.reshape(-1)[at] = np.concatenate([r[0] for r in seg])
                writes.reshape(-1)[at] = np.concatenate([r[1] for r in seg])
                svals.reshape(-1)[at] = np.concatenate([r[2] for r in seg])
            key = (ns_pad, wd, len(mem_f64))
            with trace.span(
                "device.launch", new_shape=int(key not in _SHAPES_SEEN),
                h2d_bytes=addrs.nbytes + writes.nbytes + svals.nbytes,
            ):
                _SHAPES_SEEN.add(key)
                mem_dev, vals = wave_loop(
                    mem_dev, jnp.asarray(addrs), jnp.asarray(writes),
                    jnp.asarray(_to_u32(svals).reshape(ns_pad, wd, 2)),
                )
                if check:
                    # the copy back queues behind the kernel now, so the
                    # wait below costs no second trip to the host (about
                    # 0.07 ms a segment on a TPU v5e)
                    vals.copy_to_host_async()
            if check:
                with trace.span("device.wait"):
                    vals.block_until_ready()
                with trace.span("device.check", d2h_bytes=svals.nbytes):
                    # every real lane's gathered bit pattern against the
                    # resolve phase's
                    got = np.asarray(vals).reshape(-1, 2)[at]
                    want = _to_u32(np.concatenate([r[3] for r in seg]))
                    if not np.array_equal(got, want):
                        i = int(at[np.flatnonzero((got != want).any(1))[0]])
                        raise AssertionError(
                            "device gather diverged from resolve phase at "
                            f"step {s0 + i // wd}, lane {i % wd}"
                        )
        with trace.span("device.wait"):
            mem_dev.block_until_ready()

    with trace.span("unpack", d2h_bytes=mem_f64.nbytes):
        mem_out = _from_u32(np.asarray(mem_dev))
        if check:
            np.testing.assert_array_equal(
                mem_out[:plan.mem_size], host_mem[:plan.mem_size],
                err_msg="device image diverged from resolve phase",
            )
        out = execlib.unpack_image(plan, mem_out, arrays)
    return WaveExecResult(
        arrays=out, stats=plan.stats, n_steps=steps,
        elapsed=resolve.seconds + device.seconds, complete=complete,
        resolve_s=resolve.seconds, device_s=device.seconds,
        n_segments=len(segments),
    )


def run_plan(
    plan: execlib.WavePlan,
    arrays: dict[str, np.ndarray],
    *,
    compute: str = "host",
    check: bool = True,
    max_steps: Optional[int] = None,
) -> WaveExecResult:
    """Execute a WavePlan step-parallel on the default JAX device.

    ``compute="host"`` (default) evaluates the op-table closures in
    numpy — elementwise identical to the oracle, so final arrays are
    bit-exact. ``compute="jnp"`` runs the same closures under
    jax.numpy (accelerator dtype semantics; tolerance-checked in
    tests, pair with ``check=False``).
    ``check`` pins every gather, store value and §6 valid bit
    request-exact against the plan's oracle reference streams during
    the resolve phase, then the device gathers and final image
    bit-exact against the resolve phase — leave on except when timing.
    The device phase is plain XLA, so the same compiled program runs on
    the CPU backend (tests) and on a TPU; there is no interpreter mode.
    """
    assert compute in ("host", "jnp"), f"unknown compute {compute!r}"
    return _run(
        plan, arrays, None, None,
        compute=compute, check=check, max_steps=max_steps,
    )


def run_sequential(
    plan: execlib.WavePlan,
    arrays: dict[str, np.ndarray],
    *,
    compute: str = "host",
    check: bool = False,
    max_steps: Optional[int] = None,
) -> WaveExecResult:
    """Execute the plan one request per step, in program order — the
    sequential (non-fused) baseline on the same hardware path (one
    bucket-width-8 segment of ``n_requests`` steps through the same
    ``wave_loop`` driver). ``max_steps`` truncates for timing
    measurement (the result's ``complete`` flag records it; truncated
    arrays are partial)."""
    n = plan.n_requests
    return _run(
        plan, arrays, np.arange(n, dtype=np.int64), n,
        compute=compute, check=check, max_steps=max_steps,
    )
