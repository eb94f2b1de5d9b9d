"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps (interpret mode)."""
import math

import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.chunk_pack.ops import gather_rows, pack_chunks
from repro.kernels.chunk_pack.ref import pack_chunks_ref
from repro.kernels.chunk_router.ops import (dest_histogram, histogram_rows,
                                            route_chunks)
from repro.kernels.chunk_router.ref import (dest_histogram_ref,
                                            route_chunks_ref)
from repro.kernels.fletcher.ops import fletcher_checksum
from repro.kernels.fletcher.ref import fletcher_ref
from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.flash_attention.ref import attention_ref

RNG = np.random.RandomState(7)


@pytest.mark.parametrize("B,S,H,D", [(2, 128, 2, 64), (1, 256, 4, 64),
                                     (2, 96, 3, 80), (1, 512, 1, 128)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_sweep(B, S, H, D, dtype, causal):
    q = jnp.asarray(RNG.randn(B, S, H, D), dtype)
    k = jnp.asarray(RNG.randn(B, S, H, D), dtype)
    v = jnp.asarray(RNG.randn(B, S, H, D), dtype)
    out = flash_attention(q, k, v, causal=causal, block_q=64, block_k=64)
    tb = lambda a: a.transpose(0, 2, 1, 3).reshape(B * H, S, D)
    ref = attention_ref(tb(q), tb(k), tb(v), scale=1 / math.sqrt(D),
                        causal=causal)
    ref = ref.reshape(B, H, S, D).transpose(0, 2, 1, 3)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=tol,
                               rtol=tol)


@pytest.mark.parametrize("n", [8, 100, 1024, 4097])
@pytest.mark.parametrize("mode", [1, 2, 3, 4])
@pytest.mark.parametrize("nodes", [8, 64])
def test_chunk_router_sweep(n, mode, nodes):
    ph = jnp.asarray(RNG.randint(1, 2 ** 30, n), jnp.int32)
    cid = jnp.asarray(RNG.randint(0, 64, n), jnp.int32)
    cl = jnp.asarray(RNG.randint(0, nodes, n), jnp.int32)
    d, c = route_chunks(ph, cid, cl, mode=mode, n_nodes=nodes)
    dr, cr = route_chunks_ref(ph, cid, cl, mode=mode, n_nodes=nodes)
    np.testing.assert_array_equal(np.asarray(d), np.asarray(dr))
    np.testing.assert_array_equal(np.asarray(c), np.asarray(cr))
    assert int(c.sum()) == n


@pytest.mark.parametrize("n,m,w", [(16, 16, 8), (100, 333, 16), (512, 64, 4),
                                   (40, 37, 300)])
@pytest.mark.parametrize("dtype", [jnp.int32, jnp.float32])
def test_chunk_pack_sweep(n, m, w, dtype):
    """The DMA gather in interpret mode at widths that are not a multiple
    of 128 lanes (padded in the wrapper), with sentinel rows mixed in."""
    payload = jnp.asarray(RNG.randn(n, w) * 100, dtype)
    idx = jnp.asarray(RNG.randint(-1, n, m), jnp.int32).at[0].set(-1)
    out = np.asarray(pack_chunks(payload, idx, interpret=True))
    np.testing.assert_array_equal(out,
                                  np.asarray(pack_chunks_ref(payload, idx)))
    assert out.shape == (m, w) and out.dtype == payload.dtype
    assert (out[np.asarray(idx) < 0] == 0).all()


@pytest.mark.parametrize("m", [3, 17, 256, 259])
def test_chunk_pack_sentinel_and_pad_path(m):
    """Sentinel idx rows (-1) must come back zero and must never gather
    row 0 (regression: an earlier kernel padded ``idx`` with 0).  Poison
    row 0 so any such leak is loud.
    """
    n, w = 8, 4
    payload = jnp.full((n, w), 7777, jnp.int32).at[1:].set(
        jnp.arange(1, n, dtype=jnp.int32)[:, None] * jnp.ones((1, w),
                                                             jnp.int32))
    idx = jnp.asarray(RNG.randint(-1, n, m), jnp.int32)
    idx = idx.at[0].set(-1)                            # always one sentinel
    out = np.asarray(pack_chunks(payload, idx, interpret=True))
    ref = np.asarray(pack_chunks_ref(payload, idx))
    np.testing.assert_array_equal(out, ref)
    assert (out[np.asarray(idx) < 0] == 0).all()
    # the engine dispatch path shares the sentinel semantics
    np.testing.assert_array_equal(np.asarray(gather_rows(payload, idx)), ref)


@pytest.mark.parametrize("n", [8, 100, 1024, 4097])
@pytest.mark.parametrize("n_bins", [4, 33])
def test_dest_histogram_sweep(n, n_bins):
    """Histogram kernel vs bincount oracle; out-of-range bins (the compact
    plan's invalid-request sentinel) are counted nowhere."""
    dest = jnp.asarray(RNG.randint(-1, n_bins + 2, n), jnp.int32)
    out = np.asarray(dest_histogram(dest, n_bins=n_bins))
    ref = np.asarray(dest_histogram_ref(dest, n_bins=n_bins))
    np.testing.assert_array_equal(out, ref)
    inb = (np.asarray(dest) >= 0) & (np.asarray(dest) < n_bins)
    assert out.sum() == inb.sum()
    np.testing.assert_array_equal(
        np.asarray(histogram_rows(dest, n_bins=n_bins)), ref)


@pytest.mark.parametrize("shape", [(1, 8), (4, 33), (16, 128)])
@pytest.mark.parametrize("n_bins", [5, 32])
def test_dest_histogram2d_sweep(shape, n_bins):
    """Row-batched histogram kernel vs per-row oracle — the compacted
    plan's per-(source, destination) counting stage and the ragged budget
    sizing both run on it."""
    from repro.kernels.chunk_router.ops import (dest_histogram2d,
                                                histogram_rows2d)
    from repro.kernels.chunk_router.ref import dest_histogram2d_ref
    dest = jnp.asarray(RNG.randint(-1, n_bins + 2, shape), jnp.int32)
    out = np.asarray(dest_histogram2d(dest, n_bins=n_bins))
    ref = np.asarray(dest_histogram2d_ref(dest, n_bins=n_bins))
    np.testing.assert_array_equal(out, ref)
    rows = np.stack([np.asarray(dest_histogram_ref(r, n_bins=n_bins))
                     for r in dest])
    np.testing.assert_array_equal(out, rows)
    np.testing.assert_array_equal(
        np.asarray(histogram_rows2d(dest, n_bins=n_bins)), ref)


@pytest.mark.parametrize("shape", [(2, 4, 3), (8, 16, 8)])
def test_gather_rows_batched_rebase_and_sentinel(shape):
    """The batched gather entry point must equal the per-row oracle: row
    rebasing onto the flat payload must never cross row boundaries, and
    sentinel (-1) columns come back zero."""
    from repro.kernels.chunk_pack.ops import gather_rows_batched
    from repro.kernels.chunk_pack.ref import gather_rows_batched_ref
    L, q, w = shape
    x = jnp.asarray(RNG.randint(0, 9999, (L, q, w)), jnp.int32)
    idx = jnp.asarray(RNG.randint(-1, q, (L, 2 * q)), jnp.int32)
    out = np.asarray(gather_rows_batched(x, idx))
    ref = np.asarray(gather_rows_batched_ref(x, idx))
    np.testing.assert_array_equal(out, ref)
    assert (out[np.asarray(idx) < 0] == 0).all()
    # zero-column plans (no traffic) stay well-formed
    empty = np.asarray(gather_rows_batched(x, jnp.zeros((L, 0), jnp.int32)))
    assert empty.shape == (L, 0, w)


@pytest.mark.parametrize("n", [1, 9, 1023, 1024, 1025, 10000])
def test_fletcher_sweep(n):
    x = jnp.asarray(RNG.randint(-2 ** 31, 2 ** 31 - 1, n, dtype=np.int64),
                    jnp.int32)
    np.testing.assert_array_equal(np.asarray(fletcher_checksum(x)),
                                  fletcher_ref(np.asarray(x)))


def test_fletcher_detects_single_bitflip():
    x = np.asarray(RNG.randint(0, 1000, 1000), np.int32)
    base = fletcher_ref(x)
    x2 = x.copy()
    x2[123] ^= 1
    assert not np.array_equal(fletcher_ref(x2), base)
    # order sensitivity (classic sum-only checksums miss swaps)
    x3 = x.copy()
    x3[[10, 20]] = x3[[20, 10]]
    assert not np.array_equal(fletcher_ref(x3), base)


def test_fletcher_float_inputs():
    x = jnp.asarray(RNG.randn(257), jnp.float32)
    cs1 = fletcher_checksum(x)
    cs2 = fletcher_checksum(x)
    assert np.array_equal(np.asarray(cs1), np.asarray(cs2))
    y = x.at[0].set(x[0] + 1e-6)
    assert not np.array_equal(np.asarray(fletcher_checksum(y)),
                              np.asarray(cs1))
