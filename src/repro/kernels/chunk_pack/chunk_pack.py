"""Destination-ordered chunk packing (Pallas TPU).

Gathers payload rows into all-to-all send order: ``out[i] = payload[idx[i]]``.
The payload stays in HBM; the row ids are scalar-prefetched into SMEM and
drive the input block's index map, so every grid step DMAs one source row
HBM → VMEM and the pipelined output block DMAs it back to its send slot —
the memcpy hot path of the BB client, done as a single fused gather instead
of per-request copies.

A row is viewed as ``(w / 128, 128)`` lane tiles: a one-row slice of a 2-D
``(n, w)`` ref is not aligned to the TPU's (8, 128) tiling, while a leading
row axis of a 3-D ``(n, r, 128)`` view is.  Widths that are not a multiple
of 128 lanes are zero-padded in the wrapper.  Rows wider than
``MAX_BLOCK_TILES`` lane tiles are split into near-equal column blocks of
a multiple of 8 tiles, so the VMEM staging (two input and two output
buffers) stays within four blocks of ``MAX_BLOCK_TILES · 512`` bytes,
whatever the chunk width.

``idx`` rows may be the sentinel ``-1``: those output rows are written as
zeros.  This is what the compacted exchange plan (burst_buffer.py) uses for
per-destination budget slots that hold no request.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

SENTINEL = -1
LANES = 128
#: lane tiles per VMEM block: 1024 tiles × 128 lanes × 4 B = 512 KiB (int32)
MAX_BLOCK_TILES = 1024


def _pack_kernel(idx_ref, row_ref, out_ref):
    ok = idx_ref[pl.program_id(0)] >= 0
    out_ref[...] = jnp.where(ok, row_ref[...],
                             jnp.zeros(row_ref.shape, row_ref.dtype))


def _block_tiles(w: int) -> tuple:
    """(tiles per row after padding, tiles per block) for width ``w``."""
    tiles = max(1, pl.cdiv(w, LANES))
    if tiles <= MAX_BLOCK_TILES:
        return tiles, tiles                 # one block spans the whole row
    n_blocks = pl.cdiv(tiles, MAX_BLOCK_TILES)
    bt = 8 * pl.cdiv(pl.cdiv(tiles, 8), n_blocks)
    return n_blocks * bt, bt


@functools.partial(jax.jit, static_argnames=("interpret",))
def pack_chunks_kernel(payload: jax.Array, idx: jax.Array, *,
                       interpret: bool = True) -> jax.Array:
    """payload: (n, w); idx: (m,) int32 row ids (-1 → zero row) → (m, w)."""
    n, w = payload.shape
    m = idx.shape[0]
    if m == 0 or n == 0:
        return jnp.zeros((m, w), payload.dtype)
    tiles, bt = _block_tiles(w)
    wp = tiles * LANES
    x = payload if wp == w else jnp.pad(payload, ((0, 0), (0, wp - w)))
    x = x.reshape(n, tiles, LANES)
    block = (None, bt, LANES)
    out = pl.pallas_call(
        _pack_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(m, tiles // bt),
            in_specs=[pl.BlockSpec(
                block, lambda i, j, ids: (jnp.maximum(ids[i], 0), j, 0))],
            out_specs=pl.BlockSpec(block, lambda i, j, ids: (i, j, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((m, tiles, LANES), payload.dtype),
        interpret=interpret,
    )(idx.astype(jnp.int32), x)
    out = out.reshape(m, wp)
    return out if wp == w else out[:, :w]
