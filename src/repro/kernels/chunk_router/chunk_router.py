"""Batched chunk routing (Pallas TPU).

TPU-native form of the paper's O(1) client routing layer (DESIGN.md §2):
instead of a per-request function-pointer dispatch, whole batches of
(path_hash, chunk_id) descriptors are FNV-mixed and mapped to destination
nodes in VMEM tiles; per-destination histogram partials come out alongside
so the caller can size the all-to-all without a second pass.

``dest_histogram2d_kernel`` exposes the histogram stage on its own: the
compacted exchange plan (exchange_plan.py) computes mixed-mode
destinations by masked select and only needs the per-(row, destination)
counts to lay out its budgeted send buffers.  ``dest_histogram_kernel`` is
its flat (n,) form.

Integer hashing uses int32 ops (wrapping multiply == uint32 mul mod 2^32;
we mask to 31 bits after every step so shifts stay logical).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

MASK31 = 0x7FFFFFFF
LANES = 128


def mix_hash_i32(a: jax.Array, b: jax.Array) -> jax.Array:
    """int32 version of layouts.mix_hash (bit-identical on 31-bit inputs)."""
    h = jnp.int32(-2128831035)          # 0x811C9DC5 (FNV offset) as int32
    for part in (a, b):
        h = (h ^ part) * jnp.int32(16777619)
        h = h & jnp.int32(MASK31)
        h = h ^ (h >> 15)
    return h & jnp.int32(MASK31)


def _block_counts(dest: jax.Array, n_bins: int) -> jax.Array:
    """Per-block one-hot histogram; out-of-range rows (e.g. -1) match no bin."""
    block = dest.shape[0]
    onehot = (dest[:, None] == jax.lax.broadcasted_iota(
        jnp.int32, (block, n_bins), 1)).astype(jnp.int32)
    return onehot.sum(axis=0)


def _router_kernel(ph_ref, cid_ref, client_ref, dest_ref, counts_ref, *,
                   mode: int, n_nodes: int, n_valid: int, block: int):
    i = pl.program_id(0)
    ph = ph_ref[...]
    cid = cid_ref[...]
    client = client_ref[...]
    if mode in (1, 4):                 # NODE_LOCAL / HYBRID write path: local
        dest = client
    else:                              # CENTRAL_META / DIST_HASH data path
        dest = mix_hash_i32(ph, cid) % n_nodes
    idx = i * block + jax.lax.broadcasted_iota(jnp.int32, (block,), 0)
    valid = idx < n_valid
    dest = jnp.where(valid, dest, -1).astype(jnp.int32)
    dest_ref[...] = dest
    # per-destination histogram for this block (summed by the wrapper);
    # padding rows (dest == -1) match no bin.
    counts_ref[0] = _block_counts(dest, n_nodes)


@functools.partial(jax.jit,
                   static_argnames=("mode", "n_nodes", "block", "interpret"))
def route_chunks_kernel(path_hash: jax.Array, chunk_id: jax.Array,
                        client: jax.Array, *, mode: int, n_nodes: int,
                        block: int = 1024, interpret: bool = True):
    """(n,) int32 descriptors → (dest (n,), counts (n_nodes,))."""
    n = path_hash.shape[0]
    block = min(block, max(8, n))
    nb = pl.cdiv(n, block)
    pad = nb * block - n
    if pad:
        path_hash = jnp.pad(path_hash, (0, pad))
        chunk_id = jnp.pad(chunk_id, (0, pad))
        client = jnp.pad(client, (0, pad))
    kernel = functools.partial(_router_kernel, mode=mode, n_nodes=n_nodes,
                               n_valid=n, block=block)
    dest, counts = pl.pallas_call(
        kernel,
        grid=(nb,),
        in_specs=[pl.BlockSpec((block,), lambda i: (i,)),
                  pl.BlockSpec((block,), lambda i: (i,)),
                  pl.BlockSpec((block,), lambda i: (i,))],
        out_specs=[pl.BlockSpec((block,), lambda i: (i,)),
                   pl.BlockSpec((1, n_nodes), lambda i: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((nb * block,), jnp.int32),
                   jax.ShapeDtypeStruct((nb, n_nodes), jnp.int32)],
        interpret=interpret,
    )(path_hash, chunk_id, client)
    return dest[:n], counts.sum(axis=0)


def _hist_kernel(dest_ref, counts_ref, *, n_bins: int):
    """One source row: (1, q) destinations → (n_bins, 1) counts.

    Destinations stay on lanes and bins go down sublanes, so the one-hot
    block is (n_bins, q) and the count is a lane reduction: no lane ↔
    sublane relayout inside the kernel."""
    d = dest_ref[...]                                        # (1, q)
    bins = jax.lax.broadcasted_iota(jnp.int32, (n_bins, d.shape[1]), 0)
    counts_ref[...] = (d == bins).astype(jnp.int32).sum(axis=1,
                                                        keepdims=True)


@functools.partial(jax.jit,
                   static_argnames=("n_bins", "block", "interpret"))
def dest_histogram_kernel(dest: jax.Array, *, n_bins: int,
                          block: int = 1024, interpret: bool = True
                          ) -> jax.Array:
    """(n,) int32 destinations → per-bin counts (n_bins,).

    Values outside [0, n_bins) — the plan's invalid-request sentinel — are
    counted nowhere.  The vector is cut into rows of ``block`` lanes
    (padded with -1 for the same reason), histogrammed per row by
    ``dest_histogram2d_kernel`` and summed.
    """
    n = dest.shape[0]
    block = min(block, max(LANES, pl.cdiv(n, LANES) * LANES))
    nb = max(1, pl.cdiv(n, block))
    pad = nb * block - n
    if pad:
        dest = jnp.pad(dest, (0, pad), constant_values=-1)
    counts = dest_histogram2d_kernel(dest.reshape(nb, block), n_bins=n_bins,
                                     interpret=interpret)
    return counts.sum(axis=0)


@functools.partial(jax.jit, static_argnames=("n_bins", "interpret"))
def dest_histogram2d_kernel(dest: jax.Array, *, n_bins: int,
                            interpret: bool = True) -> jax.Array:
    """(L, q) int32 destinations → per-row counts (L, n_bins).

    One grid step per source row, so the one-hot block stays (n_bins, q)
    regardless of L.  The rows are viewed as (L, 1, q) and the counts come
    out as (L, n_bins, 1), so each block spans the full trailing two dims
    of its array, as the TPU's (8, 128) tiling demands.  This is the
    histogram stage the compacted exchange plan runs per call — both to
    lay out its budgeted send buffers and (host-side, on the same counts)
    to size the ragged per-destination budgets.  Out-of-range rows (the
    plan's invalid-request sentinel ``-1``) are counted nowhere.
    """
    L, q = dest.shape
    if L == 0 or q == 0:
        return jnp.zeros((L, n_bins), jnp.int32)
    counts = pl.pallas_call(
        functools.partial(_hist_kernel, n_bins=n_bins),
        grid=(L,),
        in_specs=[pl.BlockSpec((None, 1, q), lambda i: (i, 0, 0))],
        out_specs=pl.BlockSpec((None, n_bins, 1), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((L, n_bins, 1), jnp.int32),
        interpret=interpret,
    )(dest.astype(jnp.int32).reshape(L, 1, q))
    return counts.reshape(L, n_bins)
