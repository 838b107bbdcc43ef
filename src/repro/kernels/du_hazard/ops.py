"""Public jit'd wrappers for the du_hazard kernel.

``hazard_frontier`` — Pallas kernel (TPU target; interpret=True on CPU),
with ``side`` selecting the hazard merge ("right": RAW/WAR/WAW — all
wait for the equal-address producer) vs the strict-precedence variant
("left"; kernel module docstring).
``hazard_frontier_batch`` — K independent stream pairs in one launch
(the multi-array shape of a fused program).
``hazard_frontier_ref`` / ``hazard_frontier_batch_ref`` — pure-jnp
oracles.
``wave_partition`` — the wave partition composed from frontiers:
given per-pair frontiers, assign each consumer request the earliest wave
in which all its producers have committed.
"""

import jax
import jax.numpy as jnp

from repro.kernels.du_hazard.kernel import (
    hazard_frontier,
    hazard_frontier_batch,
)
from repro.kernels.du_hazard.ref import (
    hazard_frontier_batch_ref,
    hazard_frontier_ref,
)

__all__ = [
    "hazard_frontier",
    "hazard_frontier_batch",
    "hazard_frontier_ref",
    "hazard_frontier_batch_ref",
    "wave_partition",
]


@jax.jit
def wave_partition(frontiers: jax.Array, src_waves: jax.Array) -> jax.Array:
    """Given each dst's required src commit count (``frontiers``, from
    hazard_frontier) and the wave index of every src request, the wave of
    each dst = 1 + wave of its last required producer (0 if none).

    This is the TPU replacement for per-cycle DU stalling: the stall
    condition becomes an index computation (DESIGN.md §2, "stalling →
    partitioning")."""
    last = jnp.maximum(frontiers - 1, 0)
    producer_wave = jnp.where(
        frontiers > 0, jnp.take(src_waves, last, mode="clip"), -1
    )
    return producer_wave + 1
