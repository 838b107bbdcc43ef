"""Wave-execution backend for the fused executor (DESIGN.md §2;
``backend="pallas"``, a name kept from when the step was a Pallas
kernel): consumes ``core/executor`` WavePlans and executes each batched
step as an XLA gather→scatter on the JAX device. Public surface:
``run_plan``, ``run_sequential``, ``WaveExecResult``."""

from repro.kernels.wave_exec.ops import (  # noqa: F401
    WaveExecResult,
    run_plan,
    run_sequential,
)
