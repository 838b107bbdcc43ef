"""Device step of the wave backend: batched wave steps as gather→scatter
(DESIGN.md §2 → the backend half of the WavePlan contract).

A step is a batch of conflict-free waves (WavePlan contract 5): no two
requests touch the same address except loads with loads and the WAR
pair whose load's wave strictly precedes the store's. The whole batch
therefore executes data-parallel against a flat protected-memory image
with gather strictly before scatter:

    load_vals[i] = mem[addr[i]]                        (gather, pre-step)
    mem[addr[i]] = sval[i]   where is_store & valid    (scatter)

Bit-exactness is by construction: the step only *moves* data. The
f64 memory image travels as ``(M, 2)`` uint32 bit-pattern rows — TPUs
have no f64 ALU, but a DU does not compute either; it disambiguates
and moves. Store values arrive precomputed by the op tables
(``core/optable``) from the gathers of *strictly earlier* steps
(contract 5), which is what makes the single gather+scatter step
sound: nothing gathered in this step feeds a store of this step.

The scatter touches **only write lanes**: every non-write lane (loads,
§6-invalid stores, padding) is redirected to the scratch row ``M - 1``
past the real image, so a load may share a real address with a store
in the same step (the batch-internal WAR) without racing it through a
duplicate-index scatter. Non-write lanes all write back the scratch
row's own pre-step content, so the scatter's result does not depend on
the order in which duplicate scratch indices are applied. Scratch-row
content is never observed — pad lanes gather it and the caller
discards those lanes.

The step is plain XLA gather/scatter: the image stays in device memory
(HBM on a TPU), so its size is bounded by device memory rather than by
a kernel's fast-memory block, and one program serves every platform —
there is no interpreter mode. ``wave_loop`` drives a whole *segment* of
equal-width steps through one ``jax.lax.fori_loop`` over the stacked
per-step tables, so the host dispatches one call per segment instead
of one per step.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


@jax.jit
def wave_loop(
    mem: jax.Array,    # (M, 2) uint32 — f64 memory image bit patterns
    addrs: jax.Array,  # (S, W) int32 per-step flat addresses in [0, M)
    writes: jax.Array,  # (S, W) bool per-step valid-store lanes
    svals: jax.Array,  # (S, W, 2) uint32 per-step store value patterns
) -> tuple[jax.Array, jax.Array]:
    """Execute S equal-width steps as one ``jax.lax.fori_loop``.

    Caller contract: every lane's address is in [0, M) and no two
    *write* lanes of one step share an address (WavePlan contract 5 —
    one valid store per address per step). Non-write lanes never
    scatter to their own address (they are redirected to the scratch
    row M-1), so load and pad lanes may freely alias any address. Pad
    steps (all lanes scratch, no writes) are no-ops, so the caller may
    pad S to a bucket size to bound compile count. Returns (final mem
    image, (S, W, 2) rows gathered per step against the pre-step image;
    the caller keeps only the load lanes).
    """
    scratch = mem.shape[0] - 1

    def body(s, carry):
        cur, vals = carry
        addr, write = addrs[s], writes[s]
        rows = cur.at[addr].get(mode="promise_in_bounds")  # pre-step
        scat = jnp.where(write, addr, scratch)
        nxt = cur.at[scat].set(
            jnp.where(write[:, None], svals[s], cur[scratch]),
            mode="promise_in_bounds",
        )
        return nxt, vals.at[s].set(rows)

    vals0 = jnp.zeros(svals.shape, jnp.uint32)
    return jax.lax.fori_loop(0, addrs.shape[0], body, (mem, vals0))
