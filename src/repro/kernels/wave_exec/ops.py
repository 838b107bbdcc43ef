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
    device  — the recorded tables are padded to power-of-two lane
              buckets, stacked into segments of equal width, and each
              segment runs as **one** jitted ``wave_loop`` call — a
              ``jax.lax.fori_loop`` over the step tables chaining the
              flat uint32-pair memory image through the carry (XLA
              gather/scatter, the same program on every platform). Final
              arrays are unpacked from the device image (and the
              per-step device gathers are checked bit-exact against
              the resolve phase under ``check=True``).

The split mirrors what the DU is: the resolve phase *disambiguates*
(and owns every divergence check); the device phase only *moves* —
which is why the whole memory schedule compiles to O(segments) kernel
launches instead of one per step, and why step count no longer
dominates wall-clock (ROADMAP item 1). Pad lanes target a scratch row
past the image; pad steps are no-ops (see ``kernel.py``).

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
import time
from typing import Optional

import numpy as np

from repro.core import executor as execlib

__all__ = ["run_plan", "run_sequential", "WaveExecResult"]

_MIN_BUCKET = 8


@dataclasses.dataclass
class WaveExecResult:
    """Final arrays + execution profile of one backend run."""

    arrays: dict[str, np.ndarray]
    stats: execlib.WaveStats
    n_steps: int  # executed gather→scatter steps (pad steps excluded)
    elapsed: float  # seconds: resolve + device phases
    complete: bool  # False when max_steps truncated the run
    resolve_s: float = 0.0  # host resolution (op tables + checks)
    device_s: float = 0.0  # segmented wave_loop execution
    n_segments: int = 0  # wave_loop launches (fori_loop calls)


def _bucket(n: int) -> int:
    b = _MIN_BUCKET
    while b < n:
        b *= 2
    return b


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

    t0 = time.perf_counter()
    steps, complete = execlib.drive_plan(
        plan, mem_step, frozen=arrays, step_of=step_of, n_steps=n_steps,
        lib="np" if compute == "host" else "jnp", check=check,
        max_steps=max_steps,
    )
    t_resolve = time.perf_counter() - t0

    # --- device phase: segments of equal-width steps, one wave_loop each -
    t0 = time.perf_counter()
    mem_dev = jnp.asarray(_to_u32(mem_f64))
    widths = [_bucket(len(a)) for a, _, _, _ in rec]
    segments: list[tuple[int, int]] = []  # (start step, end step)
    for s, wd in enumerate(widths):
        if segments and widths[segments[-1][0]] == wd:
            segments[-1] = (segments[-1][0], s + 1)
        else:
            segments.append((s, s + 1))
    for s0, s1 in segments:
        wd = widths[s0]
        ns = s1 - s0
        # pad the segment's step count to a power of two as well (pad
        # steps are no-ops) so compile count is O(log steps · log width)
        ns_pad = 1
        while ns_pad < ns:
            ns_pad *= 2
        addrs = np.full((ns_pad, wd), scratch, dtype=np.int32)
        writes = np.zeros((ns_pad, wd), dtype=bool)
        svals = np.zeros((ns_pad, wd), dtype=np.float64)
        for j in range(ns):
            a, w, v, _ = rec[s0 + j]
            addrs[j, :len(a)] = a
            writes[j, :len(a)] = w
            svals[j, :len(a)] = v
        mem_dev, vals = wave_loop(
            mem_dev, jnp.asarray(addrs), jnp.asarray(writes),
            jnp.asarray(_to_u32(svals).reshape(ns_pad, wd, 2)),
        )
        if check:
            vals_h = np.asarray(vals)
            for j in range(ns):
                a, _, _, got = rec[s0 + j]
                np.testing.assert_array_equal(
                    _from_u32(vals_h[j])[:len(a)], got,
                    err_msg="device gather diverged from resolve phase",
                )
    mem_dev.block_until_ready()
    t_device = time.perf_counter() - t0

    mem_out = _from_u32(np.asarray(mem_dev))
    if check:
        np.testing.assert_array_equal(
            mem_out[:plan.mem_size], host_mem[:plan.mem_size],
            err_msg="device image diverged from resolve phase",
        )
    out = execlib.unpack_image(plan, mem_out, arrays)
    return WaveExecResult(
        arrays=out, stats=plan.stats, n_steps=steps,
        elapsed=t_resolve + t_device, complete=complete,
        resolve_s=t_resolve, device_s=t_device, n_segments=len(segments),
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
