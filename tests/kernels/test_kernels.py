"""Per-kernel allclose sweeps: Pallas (interpret=True) vs pure-jnp refs,
over shapes and dtypes."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

KEY = jax.random.PRNGKey(7)


def keys(n):
    return jax.random.split(KEY, n)


# ---------------------------------------------------------------------------
# du_hazard
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "s,d",
    [
        (64, 33),
        pytest.param(1000, 777, marks=pytest.mark.slow),
        pytest.param(257, 512, marks=pytest.mark.slow),
    ],
)
@pytest.mark.parametrize("hi", [10, 500])
def test_du_hazard_sweep(s, d, hi):
    from repro.kernels.du_hazard.ops import hazard_frontier, hazard_frontier_ref

    k1, k2 = keys(2)
    src = jnp.sort(jax.random.randint(k1, (s,), 0, hi))
    dst = jax.random.randint(k2, (d,), 0, hi + 50)
    got = hazard_frontier(src, dst, block_d=64, block_s=128, interpret=True)
    np.testing.assert_array_equal(got, hazard_frontier_ref(src, dst))


@pytest.mark.parametrize("side", ["right", "left"])
def test_du_hazard_side_sweep(side):
    """Hazard merge ("right") includes the equal-address producer;
    strict precedence ("left") counts only strictly-smaller ones."""
    from repro.kernels.du_hazard.ops import hazard_frontier, hazard_frontier_ref

    k1, k2 = keys(2)
    src = jnp.sort(jax.random.randint(k1, (70,), 0, 25))
    dst = jax.random.randint(k2, (41,), 0, 30)
    got = hazard_frontier(src, dst, side=side, block_d=64, block_s=64,
                          interpret=True)
    np.testing.assert_array_equal(got, hazard_frontier_ref(src, dst, side))
    if side == "left":
        right = hazard_frontier_ref(src, dst, "right")
        assert bool(jnp.any(got < right))  # equal addresses exist


@pytest.mark.parametrize("k,s,d", [(3, 40, 30), (6, 129, 77)])
def test_du_hazard_batch_sweep(k, s, d):
    """K independent stream pairs in one launch == K single merges."""
    from repro.kernels.du_hazard.ops import (
        hazard_frontier_batch,
        hazard_frontier_batch_ref,
        hazard_frontier_ref,
    )

    k1, k2 = keys(2)
    src = jnp.sort(jax.random.randint(k1, (k, s), 0, 50), axis=1)
    dst = jax.random.randint(k2, (k, d), 0, 60)
    got = hazard_frontier_batch(src, dst, block_d=64, block_s=64,
                                interpret=True)
    np.testing.assert_array_equal(
        got, hazard_frontier_batch_ref(src, dst)
    )
    for kk in range(k):
        np.testing.assert_array_equal(
            got[kk], hazard_frontier_ref(src[kk], dst[kk])
        )


# ---------------------------------------------------------------------------
# fused_stream (store-to-load forwarding)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "s,d,mem",
    [
        (100, 77, 64),
        pytest.param(512, 333, 256, marks=pytest.mark.slow),
    ],
)
def test_fused_stream_sweep(s, d, mem):
    from repro.kernels.du_hazard.ops import hazard_frontier_ref
    from repro.kernels.fused_stream.ops import fused_raw_loops, fused_stream_ref

    k1, k2, k3, k4 = keys(4)
    src = jnp.sort(jax.random.randint(k1, (s,), 0, mem))
    val = jax.random.normal(k2, (s,))
    dst = jax.random.randint(k3, (d,), 0, mem)
    memory = jax.random.normal(k4, (mem,))
    got_v, got_h = fused_raw_loops(src, val, dst, memory, interpret=True)
    exp_v, exp_h = fused_stream_ref(
        src, val, hazard_frontier_ref(src, dst), dst, memory
    )
    np.testing.assert_allclose(np.asarray(got_v), np.asarray(exp_v), atol=1e-6)
    np.testing.assert_array_equal(np.asarray(got_h), np.asarray(exp_h))


def test_fused_stream_semantics_vs_loop():
    """End-to-end Fig. 1 semantics: fused == sequential loops."""
    from repro.kernels.fused_stream.ops import fused_raw_loops

    rng = np.random.default_rng(0)
    mem0 = rng.standard_normal(32)
    src = np.sort(rng.integers(0, 32, 40))
    val = rng.standard_normal(40)
    dst = rng.integers(0, 32, 25)
    seq_mem = mem0.copy()
    for a, v in zip(src, val):
        seq_mem[a] = v
    expected = seq_mem[dst]
    got, _ = fused_raw_loops(
        jnp.asarray(src), jnp.asarray(val), jnp.asarray(dst),
        jnp.asarray(mem0), interpret=True,
    )
    np.testing.assert_allclose(np.asarray(got), expected, atol=1e-6)


@pytest.mark.parametrize("valid_rate", [1.0, 0.5, 0.0])
def test_fused_stream_guarded_vs_loop(valid_rate):
    """§6 generalization: guard-failed producers forward nothing — the
    bounded lookback skips them. Oracle is an independent sequential
    loop applying only the landed stores."""
    from repro.kernels.fused_stream.ops import fused_raw_loops, min_lookback

    rng = np.random.default_rng(11)
    mem0 = rng.standard_normal(24).astype(np.float32)
    src = np.sort(rng.integers(0, 24, 50))
    val = rng.standard_normal(50).astype(np.float32)
    valid = (rng.random(50) < valid_rate).astype(np.int32)
    dst = rng.integers(0, 24, 37)
    seq = mem0.copy()
    for a, v, ok in zip(src, val, valid):
        if ok:
            seq[a] = v
    lb = min_lookback(src)
    got, hits = fused_raw_loops(
        jnp.asarray(src), jnp.asarray(val), jnp.asarray(dst),
        jnp.asarray(mem0), jnp.asarray(valid), lookback=lb, interpret=True,
    )
    np.testing.assert_allclose(np.asarray(got), seq[dst], atol=1e-6)
    if valid_rate == 0.0:
        assert not np.asarray(hits).any()


def test_min_lookback_runs():
    from repro.kernels.fused_stream.ops import min_lookback

    assert min_lookback(np.array([], dtype=np.int64)) == 1
    assert min_lookback(np.array([1, 2, 3])) == 1
    assert min_lookback(np.array([1, 1, 2, 2, 2, 5])) == 3
    assert min_lookback(np.array([7, 7, 7, 7])) == 4


# ---------------------------------------------------------------------------
# csr_spmv + histogram
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n,block_r", [(16, 8), (100, 32)])
def test_csr_spmv_sweep(n, block_r):
    from repro.kernels.csr_spmv.ops import spmv_from_csr

    rng = np.random.default_rng(3)
    deg = rng.integers(1, 6, n)
    rp = np.concatenate([[0], np.cumsum(deg)])
    ci = rng.integers(0, n, int(rp[-1]))
    vv = rng.standard_normal(int(rp[-1])).astype(np.float32)
    x = jnp.asarray(rng.standard_normal(n).astype(np.float32))
    y = spmv_from_csr(rp, ci, vv, x, block_r=block_r, interpret=True)
    dense = np.zeros((n, n), np.float32)
    for r in range(n):
        for p in range(rp[r], rp[r + 1]):
            dense[r, ci[p]] += vv[p]
    np.testing.assert_allclose(np.asarray(y), dense @ np.asarray(x), atol=1e-4)


@pytest.mark.parametrize("n,bins,block", [(100, 16, 32), (1000, 64, 128)])
def test_histogram_sweep(n, bins, block):
    from repro.kernels.histogram.ops import histogram, histogram_ref

    d = jax.random.randint(keys(1)[0], (n,), 0, bins)
    got = histogram(d, n_bins=bins, block=block, interpret=True)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(histogram_ref(d, n_bins=bins))
    )


def test_hist_add_fused_matches_numpy():
    from repro.kernels.histogram.ops import hist_add

    rng = np.random.default_rng(5)
    d1 = rng.integers(0, 32, 500)
    d2 = rng.integers(0, 32, 500)
    got = hist_add(jnp.asarray(d1), jnp.asarray(d2), n_bins=32,
                   interpret=True)
    exp = np.bincount(d1, minlength=32) + np.bincount(d2, minlength=32)
    np.testing.assert_allclose(np.asarray(got), exp)

