"""Multi-mode burst-buffer engine: functional, mesh-backed data plane.

The engine operates on *stacked node-major arrays* — every table has a
leading ``N`` (node) axis — so the identical code runs

* on one device (tests / property checks): the cross-node exchange is a
  transpose of the (src, dst) axes, and
* under ``shard_map`` on a real mesh (production / dry-run): the exchange is
  ``jax.lax.all_to_all`` over the ``node`` axis (see mesh_engine.py).

Request routing goes through the vectorized routing triplet (layouts.py):
every batch of I/O requests carries a **per-request mode array** (resolved
from path scopes by a ``LayoutPolicy`` — see policy.py), is vector-routed by
masked select over all four mode formulas, and then crosses the node fabric
through the **unified exchange pipeline** (exchange_plan.py): each entry
point builds ONE fused request buffer and one receiver-side apply closure
and hands both to ``run_exchange``, which plans the routing permutation,
ships the buffer through the executor the planner picked, applies it, and
routes the replies back — including the one shared copy of the lossless
carry round.  The executors (dense broadcast / uniform-budget all_to_all /
packed ragged / the mesh's two-level split, padded or chip-ring) are
interchangeable transports; see exchange_plan.py for the full matrix and docs/exchange.md
for the measured trade-offs.  A single exchange round therefore serves a
*mixed-mode* batch: the Mode-1/4 local fast path, hashed routing, and the
hybrid two-phase read are mask-combined paths over the same plan/execute
plumbing.  Mode semantics:

* Mode 1: all routing → self.  Reads of remote data must broadcast-search
  (the paper's "stranded local data" penalty — structurally visible here).
* Mode 2: file metadata → the md-server subset; data consistent-hashed.
* Mode 3: everything consistent-hashed (fail-safe baseline).
* Mode 4: writes land locally; hashed metadata records data_location_rank;
  reads do a two-phase lookup (meta owner → data owner).

The policy is trace-time static, so the engine still specializes in Python
on ``policy.modes_present()``: a pure Mode-1/4 policy keeps the
zero-exchange local write path, and policies that cannot contain Mode 4 skip
the two-phase read entirely.  ``LayoutPolicy.uniform(m)`` thereby reproduces
the old single-mode engine bit-for-bit (tests/test_policy.py pins this
against seed-engine digests).

``forward_read`` optionally takes a precomputed ``data_loc`` array — the
client's **two-phase hybrid read** runs the metadata probe as its own
call, sizes a measured ragged plan from the resolved destinations, and
passes the locations back in so the engine skips its internal meta phase
(bit-for-bit the same answers, at ragged instead of worst-case budgets).

Prefer the ``BBClient`` facade (client.py) over calling these functions
directly — it owns the mode resolution, the exchange planning and the
``node_ids`` plumbing for both the stacked and the shard_map mesh backends.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import obs
from repro.core.layouts import LayoutMode, route_data, route_meta
from repro.core.policy import LayoutPolicy, as_policy
from repro.kernels.chunk_pack.ops import gather_rows_batched

# the unified exchange pipeline — re-exported here because this module is
# the engine's public face (tests, benchmarks and the client reach the
# planner's vocabulary as ``burst_buffer.*``)
from repro.core.exchange_plan import (  # noqa: F401  (re-exports)
    COMPACTED, DENSE, DenseExecutor, ExchangeConfig, ExchangePlan,
    LOCAL_WRITE_MODES, MeshRaggedSpec, MeshSplitExecutor, PermuteExecutor,
    RaggedExecutor,
    RaggedSpec, UniformExecutor, _auto_budget, _carry_budget, _carry_taken,
    _compact_plan, _compact_plan_ragged, bucketize, build_executor,
    collect_replies, compact_bucketize, compact_collect,
    compact_collect_flat, data_budget, exchange_footprint, fuse_specs,
    fused_send, fused_write_plan, meta_budget, plan_mesh_ragged_spec,
    plan_ragged_spec, ragged_exchange, ragged_reply_exchange, run_exchange,
    stacked_exchange, stacked_shift)

EMPTY = jnp.int32(-1)

# metadata op codes
OP_CREATE, OP_STAT, OP_REMOVE, OP_UPDATE = 0, 1, 2, 3


@jax.tree_util.register_pytree_node_class
@dataclass
class BBState:
    """All node tables, stacked on a leading node axis."""

    data: jax.Array       # (N, cap, words) int32 chunk payloads
    data_keys: jax.Array  # (N, cap, 2) int32 (path_hash, chunk_id); -1 empty
    data_count: jax.Array  # (N,) int32
    meta_key: jax.Array   # (N, mcap) int32 path_hash; -1 empty
    meta_size: jax.Array  # (N, mcap) int32 file size (chunks)
    meta_loc: jax.Array   # (N, mcap) int32 data_location_rank (Mode 4)
    meta_count: jax.Array  # (N,) int32
    dropped: jax.Array    # (N,) int32 capacity-overflow counter

    def tree_flatten(self):
        """Pytree protocol: the eight table arrays, no static aux."""
        return ((self.data, self.data_keys, self.data_count, self.meta_key,
                 self.meta_size, self.meta_loc, self.meta_count, self.dropped),
                None)

    @classmethod
    def tree_unflatten(cls, aux, children):
        """Pytree protocol inverse of ``tree_flatten``."""
        return cls(*children)


def init_state(n_nodes: int, cap: int, words: int, mcap: int) -> BBState:
    """Fresh empty node tables: cap data slots × words, mcap meta."""
    return BBState(
        data=jnp.zeros((n_nodes, cap, words), jnp.int32),
        data_keys=jnp.full((n_nodes, cap, 2), EMPTY, jnp.int32),
        data_count=jnp.zeros((n_nodes,), jnp.int32),
        meta_key=jnp.full((n_nodes, mcap), EMPTY, jnp.int32),
        meta_size=jnp.zeros((n_nodes, mcap), jnp.int32),
        meta_loc=jnp.full((n_nodes, mcap), EMPTY, jnp.int32),
        meta_count=jnp.zeros((n_nodes,), jnp.int32),
        dropped=jnp.zeros((n_nodes,), jnp.int32),
    )


def _add_dropped(state: BBState, extra: jax.Array) -> BBState:
    return BBState(state.data, state.data_keys, state.data_count,
                   state.meta_key, state.meta_size, state.meta_loc,
                   state.meta_count, state.dropped + extra)


# ---------------------------------------------------------------------------
# node-local table ops (operate on (N, ...) stacked tables directly)
# ---------------------------------------------------------------------------
def _append_chunks(state: BBState, keys: jax.Array, data: jax.Array,
                   valid: jax.Array) -> BBState:
    """Append received chunks. keys: (N, m, 2); data: (N, m, w); valid: (N, m).

    Duplicate keys append a new version; lookups return the newest.
    """
    N, cap, _ = state.data.shape
    m = keys.shape[1]
    rank = jnp.cumsum(valid.astype(jnp.int32), axis=1) - 1       # (N, m)
    slot = state.data_count[:, None] + rank
    ok = valid & (slot < cap)
    slot = jnp.where(ok, slot, cap)                              # drop slot
    rows = jnp.broadcast_to(jnp.arange(N)[:, None], (N, m))
    new_keys = state.data_keys.at[rows, slot].set(
        jnp.where(ok[..., None], keys, EMPTY), mode="drop")
    new_data = state.data.at[rows, slot].set(
        jnp.where(ok[..., None], data, 0), mode="drop")
    appended = ok.sum(axis=1).astype(jnp.int32)
    dropped = (valid & ~ok).sum(axis=1).astype(jnp.int32)
    return BBState(new_data, new_keys, state.data_count + appended,
                   state.meta_key, state.meta_size, state.meta_loc,
                   state.meta_count, state.dropped + dropped)


def _lookup_chunks(state: BBState, keys: jax.Array, valid: jax.Array
                   ) -> Tuple[jax.Array, jax.Array]:
    """keys: (N, m, 2) → (payload (N, m, w), found (N, m)). Newest wins."""
    tbl = state.data_keys                                        # (N, cap, 2)
    eq = (tbl[:, None, :, 0] == keys[:, :, None, 0]) & \
         (tbl[:, None, :, 1] == keys[:, :, None, 1]) & \
         (tbl[:, None, :, 0] != EMPTY)                           # (N, m, cap)
    found = eq.any(axis=2) & valid
    idx = jnp.argmax(eq * jnp.arange(1, tbl.shape[1] + 1)[None, None, :],
                     axis=2)
    payload = jnp.take_along_axis(state.data, idx[..., None], axis=1)
    payload = jnp.where(found[..., None], payload, 0)
    return payload, found


def _alloc_meta_slots(mk: jax.Array, new_mask: jax.Array
                      ) -> Tuple[jax.Array, jax.Array]:
    """Assign each new entry a distinct EMPTY slot (ascending, per row).

    mk: (N, mcap) key table; new_mask: (N, m) entries to place.
    Returns (slot (N, m) — ``mcap`` for entries that don't fit, fits (N, m)).

    Slots freed by REMOVE are reused.  With an unfragmented table the empty
    slots are exactly [count, mcap), so this degenerates to the historical
    append-cursor allocation bit-for-bit.
    """
    N, mcap = mk.shape
    empty = mk == EMPTY
    n_empty = empty.sum(axis=1).astype(jnp.int32)                  # (N,)
    # ascending indices of empty slots first, occupied pushed to the back
    empty_idx = jnp.argsort(jnp.where(empty, jnp.arange(mcap)[None, :],
                                      mcap), axis=1).astype(jnp.int32)
    rank = jnp.cumsum(new_mask.astype(jnp.int32), axis=1) - 1      # (N, m)
    fits = new_mask & (rank < n_empty[:, None])
    slot = jnp.take_along_axis(empty_idx,
                               jnp.clip(rank, 0, mcap - 1), axis=1)
    return jnp.where(fits, slot, mcap), fits


def _meta_find(mk: jax.Array, k: jax.Array, ok: jax.Array
               ) -> Tuple[jax.Array, jax.Array]:
    """(N, mcap) table scan: first slot holding each key (argmax of match)."""
    eq = (mk[:, None, :] == k[:, :, None]) & (mk[:, None, :] != EMPTY)
    fnd = eq.any(axis=2) & ok
    idx = jnp.argmax(eq, axis=2)
    return fnd, idx


def _meta_apply(state: BBState, op: jax.Array, key: jax.Array,
                size: jax.Array, loc: jax.Array, valid: jax.Array
                ) -> Tuple[BBState, jax.Array, jax.Array, jax.Array]:
    """Apply a batch of metadata ops to the local tables.

    op/key/size/loc/valid: (N, m).  Returns (state, found, r_size, r_loc).
    Order within the batch: CREATE → UPDATE → STAT → REMOVE.
    """
    N, mcap = state.meta_key.shape
    m = key.shape[1]
    rows = jnp.broadcast_to(jnp.arange(N)[:, None], (N, m))
    find = _meta_find

    mk, ms, ml = state.meta_key, state.meta_size, state.meta_loc
    dropped = state.dropped

    # CREATE (skip if exists — idempotent create)
    c_ok = valid & (op == OP_CREATE)
    exists, _ = find(mk, key, c_ok)
    c_new = c_ok & ~exists
    slot, fits = _alloc_meta_slots(mk, c_new)
    mk = mk.at[rows, slot].set(jnp.where(fits, key, EMPTY), mode="drop")
    ms = ms.at[rows, slot].set(jnp.where(fits, size, 0), mode="drop")
    ml = ml.at[rows, slot].set(jnp.where(fits, loc, EMPTY), mode="drop")
    dropped = dropped + (c_new & ~fits).sum(axis=1).astype(jnp.int32)

    # UPDATE (size := max(size, new); loc := new if >= 0).
    # A write to a file without an entry upserts it (implicit create on
    # first write, as in GekkoFS).
    u_ok = valid & (op == OP_UPDATE)
    fnd_u0, _ = find(mk, key, u_ok)
    missing = u_ok & ~fnd_u0
    slot_m, fits_m = _alloc_meta_slots(mk, missing)
    mk = mk.at[rows, slot_m].set(jnp.where(fits_m, key, EMPTY), mode="drop")
    ms = ms.at[rows, slot_m].set(jnp.where(fits_m, jnp.zeros_like(size), 0),
                                 mode="drop")
    ml = ml.at[rows, slot_m].set(jnp.where(fits_m, loc, EMPTY), mode="drop")
    dropped = dropped + (missing & ~fits_m).sum(axis=1).astype(jnp.int32)

    fnd_u, idx_u = find(mk, key, u_ok)
    cur_sz = jnp.take_along_axis(ms, idx_u, axis=1)
    new_sz = jnp.where(fnd_u, jnp.maximum(cur_sz, size), cur_sz)
    ms = ms.at[rows, jnp.where(fnd_u, idx_u, mcap)].set(new_sz, mode="drop")
    cur_loc = jnp.take_along_axis(ml, idx_u, axis=1)
    new_loc = jnp.where(fnd_u & (loc >= 0), loc, cur_loc)
    ml = ml.at[rows, jnp.where(fnd_u, idx_u, mcap)].set(new_loc, mode="drop")

    # STAT
    s_ok = valid & (op == OP_STAT)
    fnd_s, idx_s = find(mk, key, s_ok)
    r_size = jnp.where(fnd_s, jnp.take_along_axis(ms, idx_s, axis=1), -1)
    r_loc = jnp.where(fnd_s, jnp.take_along_axis(ml, idx_s, axis=1), -1)

    # REMOVE — clear the whole record (key, size, loc), not just the key:
    # a blanked-key slot with stale size/loc could leak into a later STAT
    # after re-CREATE, and never reclaiming slots leaked capacity.
    r_ok = valid & (op == OP_REMOVE)
    fnd_r, idx_r = find(mk, key, r_ok)
    rm_slot = jnp.where(fnd_r, idx_r, mcap)
    mk = mk.at[rows, rm_slot].set(EMPTY, mode="drop")
    ms = ms.at[rows, rm_slot].set(0, mode="drop")
    ml = ml.at[rows, rm_slot].set(EMPTY, mode="drop")

    # live-entry count (removal reclaims; allocation reuses freed slots)
    mc = (mk != EMPTY).sum(axis=1).astype(jnp.int32)

    found = (valid & (op == OP_CREATE) & True) | fnd_u | fnd_s | fnd_r
    new_state = BBState(state.data, state.data_keys, state.data_count,
                        mk, ms, ml, mc, dropped)
    return new_state, found, r_size, r_loc


def _meta_write_apply(state: BBState, key: jax.Array, size: jax.Array,
                      loc: jax.Array, valid: jax.Array, create: jax.Array
                      ) -> BBState:
    """``_meta_apply`` specialized for a write batch and its discarded reply.

    A write's metadata plane carries only CREATE (chunk 0) and UPDATE
    (upsert) ops, and the caller never consumes the reply.  The fused
    round-trip hands the receiver that guarantee statically, so the STAT
    and REMOVE passes — two O(m·mcap) table scans plus their gathers and
    scatters — and the reply outputs never enter the trace.  The CREATE
    and UPDATE passes below are copied verbatim from ``_meta_apply``
    (with ``op == OP_CREATE`` pre-resolved to ``create``), so the
    resulting tables are bit-for-bit those of the generic apply.

    The three metadata columns also travel as ONE (N, mcap, 3) packed
    table so each pass issues a single 3-wide scatter instead of three —
    XLA CPU scatters pay per update row, not per scalar, so a third of
    the scatter count is a third of the apply's wall-clock.  The values
    written per slot are identical, so the unpacked tables match the
    generic apply's exactly.
    """
    N, mcap = state.meta_key.shape
    m = key.shape[1]
    rows = jnp.broadcast_to(jnp.arange(N)[:, None], (N, m))
    find = _meta_find

    tbl = jnp.stack([state.meta_key, state.meta_size, state.meta_loc],
                    axis=-1)                                     # (N, mcap, 3)
    dropped = state.dropped

    # CREATE (skip if exists — idempotent create)
    c_ok = valid & create
    exists, _ = find(tbl[..., 0], key, c_ok)
    c_new = c_ok & ~exists
    slot, fits = _alloc_meta_slots(tbl[..., 0], c_new)
    rec_c = jnp.stack([key, size, loc], axis=-1)                 # (N, m, 3)
    tbl = tbl.at[rows, slot].set(jnp.where(fits[..., None], rec_c, 0),
                                 mode="drop")
    dropped = dropped + (c_new & ~fits).sum(axis=1).astype(jnp.int32)

    # UPDATE upsert on miss (implicit create: size 0, loc as sent)
    u_ok = valid & ~create
    fnd_u0, _ = find(tbl[..., 0], key, u_ok)
    missing = u_ok & ~fnd_u0
    slot_m, fits_m = _alloc_meta_slots(tbl[..., 0], missing)
    rec_m = jnp.stack([key, jnp.zeros_like(size), loc], axis=-1)
    tbl = tbl.at[rows, slot_m].set(jnp.where(fits_m[..., None], rec_m, 0),
                                   mode="drop")
    dropped = dropped + (missing & ~fits_m).sum(axis=1).astype(jnp.int32)

    # UPDATE (size := max(size, new); loc := new if >= 0).  The key
    # column rewrites the key the slot already holds (find matched it),
    # keeping the scatter a single packed 3-wide write.
    fnd_u, idx_u = find(tbl[..., 0], key, u_ok)
    cur = jnp.take_along_axis(tbl, idx_u[..., None], axis=1)     # (N, m, 3)
    new_sz = jnp.where(fnd_u, jnp.maximum(cur[..., 1], size), cur[..., 1])
    new_loc = jnp.where(fnd_u & (loc >= 0), loc, cur[..., 2])
    rec_u = jnp.stack([key, new_sz, new_loc], axis=-1)
    tbl = tbl.at[rows, jnp.where(fnd_u, idx_u, mcap)].set(rec_u, mode="drop")

    mk = tbl[..., 0]
    mc = (mk != EMPTY).sum(axis=1).astype(jnp.int32)
    return BBState(state.data, state.data_keys, state.data_count,
                   mk, tbl[..., 1], tbl[..., 2], mc, dropped)


# ---------------------------------------------------------------------------
# client-visible batched operations — every cross-node phase below is ONE
# ``run_exchange`` call: a fused request buffer plus a receiver-side apply
# closure; the planner (exchange_plan.build_executor) owns all routing
# ---------------------------------------------------------------------------
def _client_ranks(L: int, node_ids: Optional[jax.Array]) -> jax.Array:
    return (jnp.arange(L, dtype=jnp.int32) if node_ids is None
            else node_ids.astype(jnp.int32))[:, None]


def _mode_array(policy: LayoutPolicy, mode: Optional[jax.Array],
                ref: jax.Array) -> jax.Array:
    """Per-request mode array; defaults to the policy's uniform default."""
    if mode is None:
        return jnp.full(ref.shape, int(policy.default_mode), jnp.int32)
    return jnp.asarray(mode).astype(jnp.int32)


def _ones_col(ref: jax.Array) -> jax.Array:
    """The fused occupancy column: arrives as the receiver validity mask
    (empty plan slots gather the sentinel zero row)."""
    return jnp.ones(ref.shape[:-1] + (1,), jnp.int32)


def _fused_write(state: BBState, policy: LayoutPolicy,
                 executors, dest: jax.Array, valid: jax.Array,
                 mode: jax.Array, path_hash: jax.Array,
                 chunk_id: jax.Array, payload: jax.Array, keys: jax.Array,
                 client: jax.Array, exchange: Callable) -> BBState:
    """The fused write round-trip: data + metadata in ONE collective.

    The synchronous write runs a data round (request collective) and then
    a metadata round (request + reply collectives, replies discarded).
    Under the pipeline each plane still packs with its OWN serial plan —
    the data requests toward ``dest`` at the data budgets, the metadata
    upserts toward their owners at the metadata budgets — but the two
    packed buffers concatenate per destination segment into a single
    collective launch (``fused_send``), with no reply round at all since
    a write never consumes its metadata replies.  The receiver slices
    the fused buffer back into per-plane views through static index
    maps, so ``_append_chunks`` and the metadata apply each scan exactly
    the rows the serial rounds handed them — fusion saves launches, not
    by adding receiver-side masking work.  Because the fused plan also
    certifies the op mix (CREATE/UPDATE only, reply discarded), the
    metadata plane applies via ``_meta_write_apply``, which skips the
    generic apply's STAT and REMOVE table scans.

    Parity: per-plane plans and packed row order are bit-identical to
    the serial rounds', so both tables append in the same source-major
    arrival order and state digests are unchanged.  Callers gate on
    ``fused_write_plan`` (compacted + lossless + pipelined,
    overflow-free non-ppermute plans).
    """
    ex_d, ex_m = executors
    N = policy.n_nodes
    w = payload.shape[-1]
    width = max(2 + w, 4)                       # widest plane row, unpadded
    op = jnp.where(chunk_id == 0, OP_CREATE, OP_UPDATE)
    loc = jnp.where(mode == LayoutMode.HYBRID,
                    jnp.broadcast_to(client, dest.shape),
                    jnp.full_like(dest, -1))
    owner = route_meta(mode, N, policy.n_md_servers, path_hash, client,
                       xp=jnp)

    def padded(body):                           # body | pad | mask
        fill = jnp.zeros(body.shape[:-1] + (width - body.shape[-1],),
                         jnp.int32)
        return jnp.concatenate([body, fill, _ones_col(body)], axis=-1)

    fields_d = padded(jnp.concatenate([keys, payload], axis=-1))
    fields_m = padded(jnp.stack([op, path_hash, chunk_id + 1, loc],
                                axis=-1))
    with obs.span("exchange.plan", cat="trace", role="fused_write",
                  kind="compacted"):
        plan_d = ex_d.plan(dest, valid, client=client)
        plan_m = ex_m.plan(owner, valid, client=client)
    with obs.span("exchange.pack", cat="trace", role="fused_write",
                  executor=type(ex_d).__name__):
        recv_d, rv_d, recv_m, rv_m = fused_send(
            ex_d, plan_d, fields_d, ex_m, plan_m, fields_m, exchange)
    with obs.span("exchange.apply", cat="trace", role="fused_write"):
        state = _append_chunks(state, recv_d[..., :2],
                               recv_d[..., 2:2 + w], rv_d)
        state = _meta_write_apply(state, recv_m[..., 1], recv_m[..., 2],
                                  recv_m[..., 3], rv_m,
                                  create=recv_m[..., 0] == OP_CREATE)
    return state


@obs.trace_span("engine.forward_write")
def forward_write(state: BBState, layout, path_hash: jax.Array,
                  chunk_id: jax.Array, payload: jax.Array, valid: jax.Array,
                  mode: Optional[jax.Array] = None,
                  exchange: Callable = stacked_exchange,
                  node_ids: Optional[jax.Array] = None,
                  config: ExchangeConfig = DENSE,
                  global_sum: Callable = jnp.sum,
                  update_meta: bool = True,
                  shift: Callable = stacked_shift) -> BBState:
    """Each node writes a batch of chunks. path_hash/chunk_id/valid: (L, q);
    payload: (L, q, w).  L is the local node count (N stacked, 1 under
    shard_map); ``node_ids`` are the global ranks of the local nodes.

    ``update_meta=False`` (trace-time static) skips the trailing metadata
    create/update round — the relayout path uses it to re-home chunk data
    WITHOUT re-deriving file sizes from chunk ids, because the old
    epoch's exact stat sizes (not a reconstruction) are what dual-epoch
    parity demands; ``migrate_rows`` moves the metadata explicitly.

    ``layout`` is a LayoutPolicy (or legacy LayoutParams); ``mode`` is the
    per-request mode array (policy default when omitted).  Requests of
    different modes share one exchange round.  Mode values MUST be
    members of ``policy.modes_present()`` — the engine specializes its
    fast paths on that static set (``BBClient`` enforces this).

    ``config`` picks the exchange data plane (see exchange_plan.py); the
    planner resolves it to one executor per phase.  ``global_sum`` must
    reduce an (L,) array over ALL nodes (psum-composed under shard_map) —
    it gates the carry round consistently; ``shift`` is the node-axis
    rotation collective the ppermute executor rides (``stacked_shift`` or
    the mesh backend's ``lax.ppermute`` closure)."""
    policy = as_policy(layout)
    N = policy.n_nodes
    client = _client_ranks(state.data.shape[0], node_ids)
    mode = _mode_array(policy, mode, path_hash)
    # tables are int32; converting up front is the same truncation the
    # at-set append applies, and keeps the fused compacted buffer from
    # promoting the routing keys to a float dtype (which would round
    # 31-bit path hashes)
    payload = jnp.asarray(payload).astype(jnp.int32)
    dest = route_data(mode, N, path_hash, chunk_id, client, xp=jnp)
    keys = jnp.stack([path_hash, chunk_id], axis=-1)
    meta_valid = valid
    if update_meta and not (policy.modes_present() <= LOCAL_WRITE_MODES):
        fplan = fused_write_plan(policy, dest.shape[1], config)
        if fplan is not None:
            return _fused_write(state, policy, fplan, dest, valid, mode,
                                path_hash, chunk_id, payload, keys, client,
                                exchange)
    if policy.modes_present() <= LOCAL_WRITE_MODES:
        # every possible mode writes locally: no exchange at all
        # (the Mode-1/4 fast path, decided statically from the policy)
        state = _append_chunks(state, keys, payload, valid)
    else:
        # keys, payload and the occupancy column ride one fused buffer:
        # one gather, one collective per round
        fields = jnp.concatenate([keys, payload, _ones_col(keys)], axis=-1)

        def apply(st, recv, rvalid):
            return _append_chunks(st, recv[..., :2], recv[..., 2:],
                                  rvalid), None

        state, _, served, overflow = run_exchange(
            "data", policy, config, dest, valid, fields, apply,
            exchange=exchange, shift=shift, global_sum=global_sum,
            state=state, client=client)
        if config.kind == "compacted" and not config.lossless:
            state = _add_dropped(state, overflow)
            # a write whose payload overflowed the data budget must not
            # register metadata either — a phantom entry would make
            # stat() report a chunk that read() cannot return
            meta_valid = valid & served
    if not update_meta:
        return state
    # metadata: create/update file entries at their owners
    op = jnp.where(chunk_id == 0, OP_CREATE, OP_UPDATE)
    # mode 4 records the data location (writer rank) in the metadata
    loc = jnp.where(mode == LayoutMode.HYBRID,
                    jnp.broadcast_to(client, dest.shape),
                    jnp.full_like(dest, -1))
    state, _, _, _ = meta_op(state, policy, op, path_hash,
                             chunk_id + 1, loc, meta_valid, mode, exchange,
                             node_ids, config, global_sum, shift)
    return state


@obs.trace_span("engine.forward_read")
def forward_read(state: BBState, layout, path_hash: jax.Array,
                 chunk_id: jax.Array, valid: jax.Array,
                 mode: Optional[jax.Array] = None,
                 exchange: Callable = stacked_exchange,
                 node_ids: Optional[jax.Array] = None,
                 config: ExchangeConfig = DENSE,
                 global_sum: Callable = jnp.sum,
                 data_loc: Optional[jax.Array] = None,
                 shift: Callable = stacked_shift
                 ) -> Tuple[jax.Array, jax.Array]:
    """Each node reads a batch of chunks → (payload (L, q, w), found (L, q)).

    See ``forward_write`` for the ``config``/``global_sum``/``shift``
    semantics; in lossless compacted mode read requests beyond the round-1
    budget are retried in the carry round rather than answered
    found=False.

    ``data_loc`` (optional, (L, q)) short-circuits the hybrid metadata
    phase with precomputed data-location ranks — the client's two-phase
    read runs the probe itself (the identical ``meta_op`` STAT call),
    resolves destinations eagerly, and sizes a measured ragged plan for
    the data round that the one-phase path must over-budget for."""
    policy = as_policy(layout)
    N = policy.n_nodes
    client = _client_ranks(state.data.shape[0], node_ids)
    mode = _mode_array(policy, mode, path_hash)
    present = policy.modes_present()
    keys = jnp.stack([path_hash, chunk_id], axis=-1)

    if LayoutMode.HYBRID in present and data_loc is None:
        # phase 1 (hybrid requests only): metadata lookup for
        # data_location_rank; other modes ride along as invalid slots
        _, found_m, _, loc = meta_op(
            state, policy, jnp.full_like(path_hash, OP_STAT), path_hash,
            jnp.zeros_like(path_hash), jnp.full_like(path_hash, -1),
            valid & (mode == LayoutMode.HYBRID), mode, exchange, node_ids,
            config, global_sum, shift)
        data_loc = jnp.where(found_m & (loc >= 0), loc,
                             jnp.broadcast_to(client, path_hash.shape))
    dest = route_data(mode, N, path_hash, chunk_id, client,
                      data_loc=data_loc, xp=jnp)
    payload, found = routed_lookup(state, policy, dest, keys, valid,
                                   exchange, shift, config, global_sum,
                                   client)
    if present & LOCAL_WRITE_MODES:
        # Stranded-data fallback: broadcast-search all nodes for Mode-1/4
        # misses.  Mode 1: any cross-node read is stranded (the paper's
        # structural penalty).  Mode 4: file-granular data_location_rank
        # cannot resolve multi-writer shared files; residual chunks are
        # searched (costed as a redirect penalty in the simulator).
        miss = valid & ~found & ((mode == LayoutMode.NODE_LOCAL) |
                                 (mode == LayoutMode.HYBRID))

        def search(got):
            bpay, bfound = _broadcast_lookup(state, keys, miss, exchange, N)
            return (jnp.where(bfound[..., None], bpay, got[0]),
                    got[1] | bfound)

        # a read that found every chunk where it looked skips the
        # broadcast; ``global_sum`` reduces over every node, so all
        # devices take one branch and its collectives stay aligned
        payload, found = jax.lax.cond(
            global_sum(miss.astype(jnp.int32)) > 0, search,
            lambda got: got, (payload, found))
    return payload, found


def routed_lookup(state: BBState, layout, dest: jax.Array, keys: jax.Array,
                  valid: jax.Array, exchange: Callable = stacked_exchange,
                  shift: Callable = stacked_shift,
                  config: ExchangeConfig = DENSE,
                  global_sum: Callable = jnp.sum,
                  client: Optional[jax.Array] = None
                  ) -> Tuple[jax.Array, jax.Array]:
    """One planned chunk lookup at explicit destinations → (payload, found).

    The shared read-side data plane: ``forward_read``'s data phase and
    ``migrate_rows``' placement-only probe are the same call — route keys
    to ``dest`` through whatever executor the planner picks, look the
    chunks up, route the fused (payload, found) reply back.  Requests the
    round-1 plan could not serve are retried in the shared carry round
    (lossless configs) or come back found=False (legacy drop plane).
    """
    policy = as_policy(layout)
    if client is None:
        client = _client_ranks(state.data.shape[0], None)
    fields = jnp.concatenate([keys, _ones_col(keys)], axis=-1)

    def apply(st, recv, rvalid):
        pay, fnd = _lookup_chunks(st, recv[..., :2], rvalid)
        return None, jnp.concatenate(
            [pay, fnd[..., None].astype(jnp.int32)], axis=-1)

    _, out, _, _ = run_exchange(
        "data", policy, config, dest, valid, fields, apply,
        exchange=exchange, shift=shift, global_sum=global_sum,
        state=state, client=client)
    return out[..., :-1], (out[..., -1] > 0) & valid


def _broadcast_lookup(state, keys, valid, exchange, N):
    """Query every node (Mode-1 stranded-read path)."""
    L = state.data.shape[0]
    q = keys.shape[1]
    kb = jnp.broadcast_to(keys[:, None], (L, N, q, 2))
    vb = jnp.broadcast_to(valid[:, None], (L, N, q))
    rk = exchange(kb)
    rv = exchange(vb)
    pay, fnd = _lookup_chunks(state, rk.reshape(L, -1, 2), rv.reshape(L, -1))
    pay = exchange(pay.reshape(L, N, q, -1))
    fnd = exchange(fnd.reshape(L, N, q))
    found_any = fnd.any(axis=1)
    # take the reply from the first node that had it
    first = jnp.argmax(fnd, axis=1)                    # (N, q)
    payload = jnp.take_along_axis(
        pay, first[:, None, :, None], axis=1)[:, 0]
    return jnp.where(found_any[..., None], payload, 0), found_any & valid


@obs.trace_span("engine.meta_op")
def meta_op(state: BBState, layout, op: jax.Array,
            path_hash: jax.Array, size: jax.Array, loc: jax.Array,
            valid: jax.Array, mode: Optional[jax.Array] = None,
            exchange: Callable = stacked_exchange,
            node_ids: Optional[jax.Array] = None,
            config: ExchangeConfig = DENSE,
            global_sum: Callable = jnp.sum,
            shift: Callable = stacked_shift
            ) -> Tuple[BBState, jax.Array, jax.Array, jax.Array]:
    """Batched metadata operations routed to their per-request-mode owners.

    Returns (state, found (L,q), size (L,q), loc (L,q)).  Under a compacted
    config, ops beyond the per-owner budget are carried into the lossless
    second round (``config.lossless``, default) or — with
    ``lossless=False`` — dropped: found=False replies, counted in
    ``dropped`` at the requesting node.  The carry round applies the
    residual ops *after* every round-1 op; per-op client batches (one
    opcode per call, CREATE idempotent / UPDATE max-merge) are
    order-insensitive, so replies match the dense plane exactly."""
    policy = as_policy(layout)
    N = policy.n_nodes
    client = _client_ranks(state.data.shape[0], node_ids)
    mode = _mode_array(policy, mode, path_hash)
    owner = route_meta(mode, N, policy.n_md_servers, path_hash, client,
                       xp=jnp)
    fields = jnp.stack([op, path_hash, size, loc, jnp.ones_like(op)],
                       axis=-1)                              # (L, q, 5)

    def apply(st, recv, rvalid):
        st2, fnd, r_size, r_loc = _meta_apply(
            st, recv[..., 0], recv[..., 1], recv[..., 2], recv[..., 3],
            rvalid)
        return st2, jnp.stack([fnd.astype(jnp.int32), r_size, r_loc],
                              axis=-1)

    # fill=-1 matches the dense plane's not-found value for size/loc
    # and still reads as found=False in the first column
    state, out, _, overflow = run_exchange(
        "meta", policy, config, owner, valid, fields, apply,
        exchange=exchange, shift=shift, global_sum=global_sum,
        state=state, client=client, reply_fill=-1)
    if config.kind == "compacted" and not config.lossless:
        state = _add_dropped(state, overflow)
    return state, (out[..., 0] > 0) & valid, out[..., 1], out[..., 2]


# ---------------------------------------------------------------------------
# live relayout: epoch migration of stored chunks between layout modes
#
# The online-adaptation subsystem (repro.core.adapt) re-decides a scope's
# layout mode at runtime and then has to MOVE the scope's already-stored
# chunks from their old-mode placement to the new one — losslessly, in
# bounded installments, while reads keep being served.  ``migrate_rows`` is
# that entry point: one installment of (path, chunk) worklist rows is
# fetched under the old epoch (full read machinery, including the hybrid
# meta phase and the Mode-1/4 stranded-data broadcast), probed at the new
# placement (placement-only — deliberately NO fallback, so a copy that only
# exists at the old placement is not mistaken for an already-migrated one),
# copied through the regular exchange plane, and the old copies are
# tombstoned everywhere except the new owner.  At every intermediate
# watermark the dual-epoch read (try new placement, fall back to old — see
# ``BBClient``) observes exactly the pre-migration data.
# ---------------------------------------------------------------------------
def _clear_chunks(state: BBState, keys: jax.Array,
                  valid: jax.Array) -> BBState:
    """Clear every stored version of the given keys, then re-compact.

    keys: (N, m, 2); valid: (N, m).  All table slots whose (path_hash,
    chunk_id) matches any valid request are blanked (key → EMPTY, payload
    → 0).  Because ``_append_chunks`` allocates at the ``data_count``
    cursor, holes in the middle of the table would be overwritten — so the
    surviving rows are compacted to the front with a *stable* empty-last
    argsort (relative order preserved ⇒ the newest-wins ``argmax`` in
    ``_lookup_chunks`` still resolves duplicates correctly) and the cursor
    becomes the live-row count.  The gather is ``gather_rows_batched`` —
    the chunk_pack Pallas kernel on TPU."""
    tbl = state.data_keys                                     # (N, cap, 2)
    N, cap, _ = tbl.shape
    hit = (tbl[:, None, :, 0] == keys[:, :, None, 0]) & \
          (tbl[:, None, :, 1] == keys[:, :, None, 1]) & \
          (tbl[:, None, :, 0] != EMPTY) & valid[:, :, None]   # (N, m, cap)
    clear = hit.any(axis=1)                                   # (N, cap)
    keep = (tbl[..., 0] != EMPTY) & ~clear
    # stable empty-last permutation: live rows first, original order kept
    order = jnp.argsort(jnp.where(keep, jnp.arange(cap)[None, :], cap),
                        axis=1).astype(jnp.int32)
    kept = jnp.take_along_axis(keep, order, axis=1)
    new_keys = jnp.where(
        kept[..., None], gather_rows_batched(tbl, order), EMPTY)
    new_data = jnp.where(
        kept[..., None], gather_rows_batched(state.data, order), 0)
    count = keep.sum(axis=1).astype(jnp.int32)
    return BBState(new_data, new_keys, count, state.meta_key,
                   state.meta_size, state.meta_loc, state.meta_count,
                   state.dropped)


def _tombstone_broadcast(state: BBState, keys: jax.Array, valid: jax.Array,
                         keep_rank: jax.Array, exchange: Callable,
                         n_nodes: int,
                         node_ids: Optional[jax.Array]) -> BBState:
    """Clear old copies of migrated chunks on every node but the new owner.

    keys/valid: (L, q); keep_rank: (L, q) — the global rank that now holds
    the chunk (its copy survives).  A broadcast is used rather than routing
    to the old owner because Mode-1/4 sources scatter copies by *writer*
    rank, which the migrator cannot reconstruct; migration installments
    are small and off the hot path, so the O(N²) tombstone round is the
    simple-and-correct choice (mirroring ``_broadcast_lookup``)."""
    L, q = valid.shape
    kb = exchange(jnp.broadcast_to(keys[:, None], (L, n_nodes, q, 2)))
    vb = exchange(jnp.broadcast_to(valid[:, None], (L, n_nodes, q)))
    pb = exchange(jnp.broadcast_to(keep_rank[:, None], (L, n_nodes, q)))
    me = _client_ranks(L, node_ids)                           # (L, 1)
    ok = vb.reshape(L, -1) & (pb.reshape(L, -1) != me)
    return _clear_chunks(state, kb.reshape(L, -1, 2), ok)


@obs.trace_span("engine.migrate_rows")
def migrate_rows(state: BBState, layout, path_hash: jax.Array,
                 chunk_id: jax.Array, valid: jax.Array,
                 old_mode: jax.Array, new_mode: jax.Array,
                 exchange: Callable = stacked_exchange,
                 node_ids: Optional[jax.Array] = None,
                 config: ExchangeConfig = COMPACTED,
                 global_sum: Callable = jnp.sum,
                 shift: Callable = stacked_shift
                 ) -> Tuple[BBState, jax.Array, jax.Array]:
    """Move one installment of chunks from old-mode to new-mode placement.

    path_hash/chunk_id/valid: (L, q) worklist rows; ``old_mode``/
    ``new_mode``: (L, q) per-request ``LayoutMode`` arrays (both must be
    members of the policy's ``modes_present()`` — the transition policy a
    ``LiveMigrator`` installs guarantees this).

    Returns (state, moved (L, q), found_old (L, q)).  Sequence per
    installment — lossless at every step:

    1. fetch under the old epoch (``forward_read`` with the old modes:
       hybrid meta phase and stranded-data broadcast included);
    2. placement-only probe at the new destination (``routed_lookup`` —
       the same planned lookup the read path uses, and deliberately NO
       fallback: an unmigrated chunk must NOT appear present via its old
       copy);
    3. copy rows found old but absent new through ``forward_write`` under
       the new modes, data-only (``update_meta=False``);
    4. move the metadata: the old entry's EXACT stat size is propagated
       to the new owner (stat parity demands the old epoch's answer, not
       a reconstruction from chunk ids — and an entry that exists in
       NEITHER epoch, i.e. a concurrently removed file, is never
       resurrected), then the old-owner entry is REMOVEd where the owner
       actually moved;
    5. tombstone old data copies everywhere but the new owner and
       re-compact the node tables (``_clear_chunks``).

    ``config`` must use uniform budgets (ragged specs are sized for ONE
    destination pattern; this entry point routes the same rows under two
    different mode arrays) — the lossless carry round keeps uniform
    budgets exact.
    """
    policy = as_policy(layout)
    if config.kind == "compacted" and (config.data_spec is not None or
                                       config.meta_spec is not None):
        raise ValueError(
            "migrate_rows routes one worklist under two mode arrays; a "
            "ragged spec sized for one of them would drop requests of the "
            "other — use uniform budgets (lossless carry covers overflow)")
    N = policy.n_nodes
    client = _client_ranks(state.data.shape[0], node_ids)
    old_mode = jnp.asarray(old_mode).astype(jnp.int32)
    new_mode = jnp.asarray(new_mode).astype(jnp.int32)
    keys = jnp.stack([path_hash, chunk_id], axis=-1)

    # 1. old-epoch fetch
    payload, found_old = forward_read(
        state, policy, path_hash, chunk_id, valid, mode=old_mode,
        exchange=exchange, node_ids=node_ids, config=config,
        global_sum=global_sum, shift=shift)

    # 2. placement-only probe at the new destination.  ``write_dest`` is
    # where step 3's copy would land (local-row rank for HYBRID/NODE_LOCAL
    # targets, hash placement otherwise); HYBRID targets additionally
    # resolve the new-epoch metadata's recorded data location first — a
    # post-transition write or an earlier installment may already have
    # placed a NEWER version on another rank, and copying the old bytes
    # over its loc record would resurrect stale data.
    write_dest = route_data(new_mode, N, path_hash, chunk_id, client,
                            xp=jnp)
    # new-epoch metadata snapshot (read-only): loc resolves hybrid probe
    # destinations; size carries the exact already-propagated stat size
    # to later installments of the same file (see step 4)
    _, fm_new, sz_new, loc_new = meta_op(
        state, policy, jnp.full_like(path_hash, OP_STAT), path_hash,
        jnp.zeros_like(path_hash), jnp.full_like(path_hash, -1), valid,
        mode=new_mode, exchange=exchange, node_ids=node_ids, config=config,
        global_sum=global_sum, shift=shift)
    probe_dest = write_dest
    if LayoutMode.HYBRID in policy.modes_present():
        probe_dest = jnp.where(
            (new_mode == LayoutMode.HYBRID) & fm_new & (loc_new >= 0),
            loc_new, write_dest)
    _, found_new = routed_lookup(state, policy, probe_dest, keys, valid,
                                 exchange, shift, config, global_sum,
                                 client)

    # 3. copy the missing rows to their new placement — data only
    # (update_meta=False): deriving sizes from chunk ids would "repair"
    # whatever the old epoch's entry actually said, breaking stat parity
    moved = valid & found_old & ~found_new
    state = forward_write(state, policy, path_hash, chunk_id, payload,
                          moved, mode=new_mode, exchange=exchange,
                          node_ids=node_ids, config=config,
                          global_sum=global_sum, update_meta=False,
                          shift=shift)

    # 4. metadata epoch move: the old owner's EXACT stat size at the new
    # owner, then the old entry gone.  The old stat is issued under the
    # old modes, so it is reachable from the worklist row for every mode
    # when the driver writer-aligns the rows (``LiveMigrator`` does —
    # Mode-1 metadata only exists at the writer); once the old entry is
    # REMOVEd by an earlier installment, the new entry already carries
    # the propagated size.
    owner_old = route_meta(old_mode, N, policy.n_md_servers, path_hash,
                           client, xp=jnp)
    owner_new = route_meta(new_mode, N, policy.n_md_servers, path_hash,
                           client, xp=jnp)
    _, found_m, sz_old, _ = meta_op(
        state, policy, jnp.full_like(path_hash, OP_STAT), path_hash,
        jnp.zeros_like(path_hash), jnp.full_like(path_hash, -1), valid,
        mode=old_mode, exchange=exchange, node_ids=node_ids, config=config,
        global_sum=global_sum, shift=shift)
    size_fix = jnp.where(found_m, sz_old, sz_new)
    # hybrid targets record where the copy landed (this row); rows that
    # didn't move keep whatever loc the new epoch already has (-1 = keep)
    loc_fix = jnp.where(moved & (new_mode == LayoutMode.HYBRID),
                        jnp.broadcast_to(client, path_hash.shape),
                        jnp.full_like(path_hash, -1))
    # UPDATE upserts: restrict to rows whose metadata exists in SOME
    # epoch — a speculative worklist row can never mint a phantom entry,
    # and a file removed mid-migration stays removed (its data still
    # migrates, exactly as un-removed data outlives a remove in the
    # single-epoch engine)
    state, _, _, _ = meta_op(
        state, policy, jnp.full_like(path_hash, OP_UPDATE), path_hash,
        size_fix, loc_fix, valid & (found_m | fm_new), mode=new_mode,
        exchange=exchange, node_ids=node_ids, config=config,
        global_sum=global_sum, shift=shift)
    state, _, _, _ = meta_op(
        state, policy, jnp.full_like(path_hash, OP_REMOVE), path_hash,
        jnp.zeros_like(path_hash), jnp.full_like(path_hash, -1),
        valid & (owner_old != owner_new), mode=old_mode, exchange=exchange,
        node_ids=node_ids, config=config, global_sum=global_sum,
        shift=shift)

    # 5. tombstone the old copies — keep the rank that actually holds the
    # surviving new-epoch copy (the write destination for rows copied this
    # installment, the probe destination for rows already in place)
    keep = jnp.where(moved, write_dest, probe_dest)
    state = _tombstone_broadcast(state, keys, valid & found_old, keep,
                                 exchange, N, node_ids)
    return state, moved, found_old


class EngineOps(tuple):
    """The jitted ``(write, read, meta, read_loc)`` programs of one config.

    These four take the state without donating it: a caller may run them
    again on the state it passed.  ``owned`` holds the same four with
    write and meta donating the state (argument 0), so XLA updates the
    node tables in place instead of copying every table into a fresh
    output, and deletes the caller's arrays.  Only a caller that rebinds
    its state to the result may run ``owned``.  The reads return no state
    and never donate; both tuples share them.
    """

    owned: Tuple


def jit_engine_ops(write: Callable, read: Callable, meta: Callable,
                   read_loc: Callable) -> EngineOps:
    """Jit the four engine programs of one config, with ``owned`` twins."""
    ops = EngineOps((jax.jit(write), jax.jit(read), jax.jit(meta),
                     jax.jit(read_loc)))
    ops.owned = (jax.jit(write, donate_argnums=0), ops[1],
                 jax.jit(meta, donate_argnums=0), ops[3])
    return ops
