"""Unified exchange planner: ONE plan → execute pipeline for every backend.

Every engine entry point (``forward_write`` / ``forward_read`` / ``meta_op``
/ ``migrate_rows`` in burst_buffer.py) used to hand-roll its own branching
over exchange modes — dense broadcast vs uniform compacted vs ragged, each
with its own carry-round copy.  This module is the single place where
exchange routing now lives:

* :func:`build_executor` — **the planner**: maps (role, policy, batch
  shape, :class:`ExchangeConfig`) to one executor.  Adding a backend means
  adding an executor here, nowhere else.
* :class:`ExchangePlan` — the per-call routing artifact every executor
  produces: destination permutation (``send_idx``), reply routing
  (``reply_idx``), overflow counters and the receiver validity channel.
* the **executors** — interchangeable transports over one interface
  (``plan`` / ``send`` / ``collect`` / ``served``):

  ==================  =====================================================
  executor            transport
  ==================  =====================================================
  ``DenseExecutor``   PR-1 bucketize broadcast (O(N²·q), the parity oracle)
  ``UniformExecutor`` jit-static per-destination budget B, (L, N, B)
                      buffers — the only shape ``all_to_all`` carries;
                      lossless via the cond-gated carry round
  ``RaggedExecutor``  packed (L, Σbᵢ) histogram-sized segments
                      (:class:`RaggedSpec`), stacked backend
  ``PermuteExecutor`` ``ppermute``-based segmented exchange
                      (:class:`MeshRaggedSpec`): N−1 shift rounds with
                      *measured per-round widths* — the mesh backend's
                      skew-proof ragged plan (round 0 is the free local
                      pass)
  ==================  =====================================================

  The mesh "padded" ragged plan (pad every segment to the psum'd global
  max budget and ride the ordinary ``all_to_all``) is deliberately NOT a
  fifth executor: it *is* ``UniformExecutor`` with the measured
  ``bmax`` — lossless by construction, so the carry round is statically
  elided.

* :func:`run_exchange` — the shared round runner: plan → send →
  receiver-apply → reply collect, plus the ONE copy of the lossless
  carry round and the legacy drop accounting that three entry points
  used to duplicate.

Backend reach: executors see two collective hooks — ``exchange`` (the
src/dst transpose: ``stacked_exchange`` or ``mesh_engine.mesh_exchange``)
and ``shift`` (a k-step rotation over the node axis: :func:`stacked_shift`
or a ``lax.ppermute`` closure).  The same executor code therefore runs
single-device and under ``shard_map``; parity tests exploit that by
digesting the ppermute plan on the stacked backend first.
"""
from __future__ import annotations

import math
from dataclasses import dataclass, replace
from functools import cached_property
from typing import Callable, Dict, Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import obs
from repro.core.layouts import LayoutMode
from repro.core.policy import LayoutPolicy, as_policy
from repro.kernels.chunk_pack.ops import gather_rows_batched
from repro.kernels.chunk_router.ops import histogram_rows2d

#: modes whose writes structurally concentrate a whole batch on one node
LOCAL_WRITE_MODES = frozenset({LayoutMode.NODE_LOCAL, LayoutMode.HYBRID})


# ---------------------------------------------------------------------------
# collective hooks (backend-pluggable)
# ---------------------------------------------------------------------------
def stacked_exchange(x: jax.Array) -> jax.Array:
    """(N_src, N_dst, ...) -> (N_dst, N_src, ...): single-device all_to_all."""
    return jnp.swapaxes(x, 0, 1)


def stacked_shift(x: jax.Array, k: int) -> jax.Array:
    """Single-device twin of a k-step ``ppermute`` over the node axis.

    Row ``j`` of the result holds row ``(j − k) mod N`` of ``x`` — i.e.
    node ``i``'s buffer arrives at node ``(i + k) mod N``, exactly the
    ``[(i, (i + k) % N) for i]`` permutation the mesh backend runs as a
    real ``lax.ppermute`` (see ``mesh_engine.build_mesh_ops``).
    """
    return jnp.roll(x, k, axis=0)


def bucketize(dest: jax.Array, valid: jax.Array, n_nodes: int,
              payloads: Dict[str, jax.Array]
              ) -> Tuple[Dict[str, jax.Array], jax.Array]:
    """Route per-slot requests into per-destination buckets (no compaction).

    dest, valid: (N, q).  payloads: {name: (N, q, ...)}.
    Returns buckets {name: (N, n_nodes, q, ...)} and mask (N, n_nodes, q).
    Slot positions are preserved so replies can be matched back.
    """
    hit = (dest[:, None, :] == jnp.arange(n_nodes)[None, :, None]) & \
        valid[:, None, :]                                  # (N, n_dst, q)
    out = {}
    for name, p in payloads.items():
        extra = (1,) * (p.ndim - 2)
        pb = jnp.broadcast_to(p[:, None],
                              (p.shape[0], n_nodes) + p.shape[1:])
        out[name] = jnp.where(hit.reshape(hit.shape + extra), pb, 0)
    return out, hit


def collect_replies(dest: jax.Array, reply_buckets: jax.Array,
                    n_nodes: int) -> jax.Array:
    """Inverse of bucketize on the requester side.

    reply_buckets: (N, n_nodes, q, ...) — replies in original slot positions.
    Returns (N, q, ...): each slot takes the reply from its destination.
    """
    hit = dest[:, None, :] == jnp.arange(n_nodes)[None, :, None]
    extra = (1,) * (reply_buckets.ndim - 3)
    return jnp.sum(jnp.where(hit.reshape(hit.shape + extra),
                             reply_buckets, 0), axis=1)


# ---------------------------------------------------------------------------
# static budget specs
# ---------------------------------------------------------------------------
@dataclass(frozen=True)
class RaggedSpec:
    """Static ragged per-destination send budgets (one exchange round).

    ``budgets[d]`` is the number of send-buffer columns reserved for
    destination ``d``; the packed buffer is (L, ``total``) with destination
    ``d``'s segment at columns [``offsets[d]``, ``offsets[d]`` + bᵈ).
    Budgets are concrete Python ints (jit-static): build one with
    ``plan_ragged_spec`` on *concrete* destination arrays, outside jit.
    Hash/eq are by budget tuple, so jitted engine ops cache per traffic
    shape.
    """

    budgets: Tuple[int, ...]

    @property
    def n_nodes(self) -> int:
        """Number of destinations (the length of the budget tuple)."""
        return len(self.budgets)

    @property
    def total(self) -> int:
        """Σbᵢ — the packed send-buffer column count."""
        return sum(self.budgets)

    @cached_property
    def bmax(self) -> int:
        """Widest per-destination segment (receive-side padding width)."""
        return max(self.budgets) if self.budgets else 0

    @cached_property
    def offsets(self) -> np.ndarray:
        """(n_nodes,) exclusive prefix sum of ``budgets``."""
        return np.concatenate(
            [[0], np.cumsum(self.budgets[:-1])]).astype(np.int32) \
            if self.budgets else np.zeros(0, np.int32)

    @cached_property
    def dcol(self) -> np.ndarray:
        """(total,) destination owning each packed column."""
        return np.repeat(np.arange(self.n_nodes, dtype=np.int32),
                         self.budgets)

    @cached_property
    def jcol(self) -> np.ndarray:
        """(total,) rank of each packed column within its segment."""
        return np.concatenate(
            [np.arange(b, dtype=np.int32) for b in self.budgets]
        ).astype(np.int32) if self.total else np.zeros(0, np.int32)

    @cached_property
    def recv_cols(self) -> np.ndarray:
        """(n_nodes·bmax,) packed column feeding each padded receive slot.

        Receive slot (d, j) reads packed column ``offsets[d] + j`` when
        ``j < budgets[d]``, else the sentinel ``-1`` (zero-masked).
        """
        col = np.full((self.n_nodes, max(self.bmax, 0)), -1, np.int32)
        for d, b in enumerate(self.budgets):
            col[d, :b] = self.offsets[d] + np.arange(b)
        return col.reshape(-1)

    @cached_property
    def send_cols(self) -> np.ndarray:
        """(total,) padded receive slot holding each packed column's reply."""
        return (self.dcol * max(self.bmax, 1) + self.jcol).astype(np.int32)


@dataclass(frozen=True)
class MeshRaggedSpec:
    """Static mesh-ragged exchange plan: measured budgets, uniform splits.

    The mesh ``all_to_all`` needs equal per-destination splits, so ragged
    Σbᵢ packing cannot cross it directly.  Two measured plans can:

    * ``executor="padded"`` — pad every destination segment to ``bmax``,
      the global maximum of the per-(source, destination) histograms (the
      psum-reduced ``chunk_router`` counts), and ride the ordinary
      ``all_to_all`` at (L, N, bmax).  Cheap when traffic is even; the
      padding approaches uniform ``q`` when one destination is hot.
    * ``executor="ppermute"`` — a segmented exchange of N−1 ``ppermute``
      shift rounds; round k carries only width ``round_widths[k]`` — the
      measured maximum any node sends to its rank+k neighbour — so a
      skewed histogram pays for its one hot (source, destination) pair in
      ONE round instead of padding every pair.  Round 0 (self traffic)
      never crosses the fabric at all.

    ``plan_mesh_ragged_spec`` measures both and picks the executor from
    the measured fabric cost model (``exchange_select.pick_mesh_executor``).
    Budgets/widths are concrete Python ints (jit-static); hash/eq by
    field tuple so jitted ops cache per traffic shape.
    """

    budgets: Tuple[int, ...]       # per-destination global-max budgets
    round_widths: Tuple[int, ...]  # per-shift-k widths; [0] is local
    executor: str = "padded"       # "padded" | "ppermute"

    def __post_init__(self):
        if self.executor not in ("padded", "ppermute"):
            raise ValueError(f"unknown mesh ragged executor "
                             f"{self.executor!r}; pass 'padded' or "
                             "'ppermute'")
        if len(self.round_widths) != len(self.budgets):
            raise ValueError("round_widths and budgets must both have one "
                             "entry per node")

    @property
    def n_nodes(self) -> int:
        """Number of nodes (= destinations = shift rounds)."""
        return len(self.budgets)

    @cached_property
    def bmax(self) -> int:
        """Global max per-destination budget — the padded-path width."""
        return max(self.budgets) if self.budgets else 0

    @property
    def total(self) -> int:
        """Σ round widths — the ppermute plan's packed column count."""
        return sum(self.round_widths)

    @cached_property
    def offsets(self) -> np.ndarray:
        """(n_nodes + 1,) exclusive prefix sum of ``round_widths``.

        The trailing extra entry is the invalid-destination sentinel slot
        used by the reply-index computation.
        """
        return np.concatenate(
            [[0], np.cumsum(self.round_widths)]).astype(np.int32)

    @cached_property
    def col_round(self) -> np.ndarray:
        """(total,) shift round owning each packed column."""
        return np.repeat(np.arange(self.n_nodes, dtype=np.int32),
                         self.round_widths)

    @cached_property
    def col_pos(self) -> np.ndarray:
        """(total,) rank of each packed column within its round."""
        return np.concatenate(
            [np.arange(w, dtype=np.int32) for w in self.round_widths]
        ).astype(np.int32) if self.total else np.zeros(0, np.int32)

    @property
    def exchanged_cols(self) -> int:
        """Columns actually crossing the fabric (round 0 stays local)."""
        return sum(self.round_widths[1:])


# ---------------------------------------------------------------------------
# spec measurement (eager, client-side)
# ---------------------------------------------------------------------------
def _quantize(budgets: np.ndarray, q: int, align: int,
              floor: Optional[np.ndarray]) -> np.ndarray:
    """Round measured budgets up to ``align`` lanes, clamp to q, apply the
    presizing floor (see ``plan_ragged_spec``)."""
    out = np.where(budgets > 0, np.minimum(q, -(-budgets // align) * align),
                   0)
    if floor is not None:
        out = np.minimum(q, np.maximum(out, np.asarray(floor,
                                                       np.int64)))
    return out


def plan_ragged_spec(dest: jax.Array, valid: jax.Array, n_nodes: int,
                     align: int = 8,
                     floor: Optional[np.ndarray] = None) -> RaggedSpec:
    """Measure per-destination traffic and build a lossless ``RaggedSpec``.

    dest/valid: *concrete* (L, q) arrays — budgets become Python ints, so
    this must run eagerly (outside jit); calling it on tracers raises.
    Budget ``d`` is the per-row ``chunk_router`` histogram maximum over all
    source rows — the smallest per-destination segment no row can overflow
    — rounded UP to a multiple of ``align`` (clamped to the row length q;
    zero-traffic destinations stay 0).  Rounding never loses a request; it
    exists to collapse the jit-shape space: exact maxima would mint a
    fresh ``RaggedSpec`` (→ a fresh XLA compile of the engine ops) for
    nearly every hashed batch, while quantized budgets land on a handful
    of shapes per workload.  ``align=1`` gives exact sizing.

    ``floor`` (optional, per-destination) raises budgets to a telemetry-
    seeded minimum — the client's presizing loop feeds its running
    high-water budgets back in, so a steady workload converges to ONE
    spec (one jit specialization) instead of re-planning per batch; a
    floor can only widen segments, never drop a request.
    """
    d = jnp.where(jnp.asarray(valid), jnp.asarray(dest).astype(jnp.int32),
                  n_nodes)
    q = d.shape[1]
    counts = histogram_rows2d(d, n_bins=n_nodes + 1)[:, :n_nodes]
    if counts.shape[0]:
        with obs.span("client.sync.spec", cat="client"):
            counts = np.asarray(counts)
        budgets = counts.max(axis=0)
    else:
        budgets = np.zeros(n_nodes, np.int64)
    budgets = _quantize(budgets, q, align, floor)
    return RaggedSpec(tuple(int(b) for b in budgets))


def plan_mesh_ragged_spec(dest: jax.Array, valid: jax.Array, n_nodes: int,
                          align: int = 8, row_bytes: int = 64,
                          allow_ppermute: bool = True,
                          node_ids: Optional[np.ndarray] = None,
                          floor: Optional[np.ndarray] = None
                          ) -> MeshRaggedSpec:
    """Measure traffic and build the mesh-ragged plan for one call.

    dest/valid: *concrete* global (N, q) arrays — on the single-controller
    client these carry every node's row, so the host-side max below IS the
    psum of the per-node ``chunk_router`` histograms that a
    multi-controller deployment would run on-fabric.  Produces

    * per-destination **budgets** (the padded path's ``bmax``), and
    * per-shift **round widths** ``w_k = max_i hist[i, (i + k) mod N]``
      (the ppermute path: in round k node i talks only to node i+k, so
      only the diagonal-k maximum needs reserving),

    both quantized like ``plan_ragged_spec`` (same jit-shape-space
    argument; ``floor`` raises the per-destination budgets AND the
    matching diagonals).  The executor is picked by the measured fabric
    cost model: ``row_bytes`` (bytes per exchanged column) converts the
    column counts to bytes for ``exchange_select.pick_mesh_executor``;
    ``allow_ppermute=False`` forces the padded plan (the client sets it
    when nodes aren't 1:1 with devices — ``ppermute`` rotates devices).

    ``node_ids`` maps row index → global rank (identity when None, which
    matches both the stacked layout and the client's global view).
    """
    from repro.core import exchange_select
    # host copies first: dest may be the node-sharded output of a mesh op,
    # and a Pallas kernel cannot take a sharded array outside shard_map
    with obs.span("client.sync.spec", cat="client"):
        valid, dest = np.asarray(valid), np.asarray(dest)
    d = jnp.asarray(np.where(valid, dest.astype(np.int32), n_nodes))
    q = d.shape[1]
    hist = histogram_rows2d(d, n_bins=n_nodes + 1)[:, :n_nodes]
    with obs.span("client.sync.spec", cat="client"):
        hist = np.asarray(hist)
    if hist.shape[0] == 0:
        hist = np.zeros((1, n_nodes), np.int64)
    budgets = _quantize(hist.max(axis=0), q, align, floor)
    ranks = (np.arange(hist.shape[0]) if node_ids is None
             else np.asarray(node_ids)).astype(np.int64)
    # w_k: the widest (source → source+k) run over all sources
    widths = np.zeros(n_nodes, np.int64)
    for i, r in enumerate(ranks):
        k = (np.arange(n_nodes) - r) % n_nodes        # dest d ↦ round k
        np.maximum.at(widths, k, hist[i])
    widths = _quantize(widths, q, align,
                       None if floor is None else _ragged_floor_diag(
                           np.asarray(floor), ranks, n_nodes))
    executor = "padded"
    if allow_ppermute:
        executor = exchange_select.pick_mesh_executor(
            n_nodes, int(budgets.max(initial=0)) * n_nodes * row_bytes,
            [int(w) * row_bytes for w in widths[1:] if w > 0])
    return MeshRaggedSpec(tuple(int(b) for b in budgets),
                          tuple(int(w) for w in widths), executor)


def _ragged_floor_diag(floor: np.ndarray, ranks: np.ndarray,
                       n_nodes: int) -> np.ndarray:
    """Per-destination floor folded onto the shift-round diagonals."""
    out = np.zeros(n_nodes, np.int64)
    for r in ranks:
        k = (np.arange(n_nodes) - r) % n_nodes
        np.maximum.at(out, k, floor)
    return out


# ---------------------------------------------------------------------------
# exchange configuration (trace-time static, hashable)
# ---------------------------------------------------------------------------
@dataclass(frozen=True)
class ExchangeConfig:
    """Static data-plane exchange selection (trace-time, hashable).

    kind: "dense" (PR-1 bucketize broadcast, the parity oracle) or
    "compacted".  ``budget``/``meta_budget`` fix the uniform per-destination
    slot counts; ``None`` auto-sizes them: data gets ``capacity·q/N``
    (rounded up to a lane-friendly multiple of 8) under hash-spread modes
    and ``B = q`` when a mode can structurally concentrate a batch on one
    node (local writes, hybrid reads); metadata auto stays ``B = q`` — see
    ``meta_budget``.

    ``lossless`` (default True) carries uniform-budget overflow into a
    cond-skipped second exchange round sized ``q − B`` instead of dropping
    it, making the compacted plane lossless at ANY budget ≥ 1;
    ``lossless=False`` restores the legacy drop-and-account semantics
    (``dropped`` counter, found=False replies, skipped metadata phase).

    ``data_spec``/``meta_spec`` switch the data/metadata exchange to a
    measured ragged plan: a :class:`RaggedSpec` (packed Σbᵢ single round —
    stacked backend only) or a :class:`MeshRaggedSpec` (global-max padded
    ``all_to_all`` or ``ppermute`` segmented rounds — mesh-capable).
    ``BBClient`` measures and attaches these per call; they are part of
    the config's hash so jitted ops specialize per traffic shape.

    ``pipeline`` (default True) enables the async restructurings that keep
    every result bit-for-bit identical: lossless writes fuse the data and
    metadata rounds into one collective round-trip, multi-round ppermute
    transports software-pipeline round k's collective against round k+1's
    gather, and the carry round's plan is hoisted out of its cond so it
    overlaps the main round.  ``pipeline=False`` restores the fully
    synchronous PR-5 call structure (the baseline the parity tests and
    ``make bench-pipeline`` compare against).

    ``carry_budget_hint`` tightens the cond-skipped carry round: the
    worst-case residual is ``q − B``, but a caller that has measured the
    actual per-(row, destination) overflow histogram (``BBClient`` does,
    eagerly, like the ragged specs) can cap the carry width at the
    observed maximum instead of paying the worst case.  The hint is an
    upper bound on the residual, so losslessness is preserved; ``None``
    keeps ``q − B``.
    """

    kind: str = "dense"
    budget: Optional[int] = None
    meta_budget: Optional[int] = None
    capacity: float = 2.0
    lossless: bool = True
    data_spec: Optional[Union[RaggedSpec, MeshRaggedSpec]] = None
    meta_spec: Optional[Union[RaggedSpec, MeshRaggedSpec]] = None
    pipeline: bool = True
    carry_budget_hint: Optional[int] = None

    def __post_init__(self):
        if self.kind not in ("dense", "compacted"):
            raise ValueError(f"unknown exchange kind {self.kind!r}; "
                             "pass 'dense' or 'compacted'")


DENSE = ExchangeConfig("dense")
COMPACTED = ExchangeConfig("compacted")


def _auto_budget(q: int, bins: int, capacity: float) -> int:
    b = int(math.ceil(capacity * q / max(1, bins)))
    return min(q, max(8, -(-b // 8) * 8))


def data_budget(policy: LayoutPolicy, q: int, config: ExchangeConfig) -> int:
    """Per-destination slot budget for the data exchange (static)."""
    if config.budget is not None:
        return max(1, min(q, config.budget))
    if policy.modes_present() & LOCAL_WRITE_MODES:
        # local writes / hybrid data_loc reads can send a whole batch to one
        # node — concentration is structural, not hash-random, so stay exact
        return q
    return _auto_budget(q, policy.n_nodes, config.capacity)


def meta_budget(policy: LayoutPolicy, q: int, config: ExchangeConfig) -> int:
    """Per-destination slot budget for the metadata exchange (static).

    Auto-sizing is lossless (``B = q``): metadata routes on ``path_hash``
    alone, so a batch of chunks of ONE file — the canonical checkpoint
    write — concentrates every op on a single owner no matter how many
    nodes exist.  That is structural concentration, not hash spread, and
    under-budgeting it silently corrupts stat() sizes.  Workloads with
    per-request-distinct paths can opt into hash-spread sizing via an
    explicit ``meta_budget`` (see benchmarks/exchange_bench.py).
    """
    if config.meta_budget is not None:
        return max(1, min(q, config.meta_budget))
    if config.budget is not None:
        return max(1, min(q, config.budget))
    return q


def _carry_budget(q: int, b: int) -> int:
    """Static budget of the lossless carry round after a round at ``b``.

    A destination receives at most ``q`` valid requests from one source
    row, round 1 serves ``min(count, b)`` of them, so the residual per
    (source, destination) pair is at most ``q − b`` — one carry round at
    that budget always terminates with zero residual, which is the
    convergence bound that makes two static rounds sufficient at ANY
    budget ≥ 1.
    """
    return max(0, q - b)


def _carry_taken(overflow: jax.Array, global_sum: Callable) -> jax.Array:
    """Scalar predicate gating the carry round (shared by every node).

    ``global_sum`` must reduce over ALL nodes (``jnp.sum`` on the stacked
    backend where every row is local; a psum-composed reduction under
    shard_map) so the cond takes the same branch on every device and the
    collectives inside stay aligned.
    """
    return global_sum(overflow) > 0


# ---------------------------------------------------------------------------
# the per-call plan and its shared low-level routing machinery
# ---------------------------------------------------------------------------
@dataclass
class ExchangePlan:
    """One call's routing artifact, produced by ``Executor.plan``.

    Traced arrays, built once per engine call and consumed by the same
    executor's ``send``/``collect``/``served``:

    * ``dest``/``valid`` — the (L, q) request routing this plan serves;
    * ``send_idx`` — request slot feeding each send-buffer column
      (-1 = empty pad), shaped per executor;
    * ``reply_idx`` — flat receive column holding each request's reply
      (-1 = unserved this round), consumed by ``compact_collect_flat``;
    * ``overflow`` — (L,) valid requests beyond this plan's budgets
      (feeds the carry predicate; 0 by construction for measured plans);
    * ``recv_perm``/``inv_perm`` — the ppermute plan's round-order ↔
      source-major receive permutations.
    """

    dest: jax.Array
    valid: jax.Array
    send_idx: Optional[jax.Array] = None
    reply_idx: Optional[jax.Array] = None
    overflow: Optional[jax.Array] = None
    recv_perm: Optional[jax.Array] = None
    inv_perm: Optional[jax.Array] = None


def _compact_plan(dest: jax.Array, valid: jax.Array, n_nodes: int,
                  budget: int) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Sort-based routing plan for one uniform-budget exchange round.

    dest/valid: (L, q).  Returns

    * send_idx (L, n_nodes, budget) int32 — request slot feeding each send
      buffer position, -1 for empty budget slots;
    * reply_idx (L, q) int32 — position of each request's reply in the
      flattened (n_nodes·budget) reply buffer, -1 for invalid/overflowed
      requests;
    * overflow (L,) int32 — valid requests beyond their destination budget.

    The stable argsort keeps requests of one (src, dst) pair in original
    slot order, so the receiver sees the same source-major arrival order as
    the dense path and table append order is preserved bit-for-bit.
    """
    L, q = dest.shape
    d = jnp.where(valid, dest, n_nodes).astype(jnp.int32)
    order = jnp.argsort(d, axis=1).astype(jnp.int32)         # stable
    sd = jnp.take_along_axis(d, order, axis=1)
    # per-(row, destination) histogram (the chunk_router histogram stage,
    # row-batched so the kernel's one-hot block stays (q, n_nodes+1)
    # regardless of L — flattening rows into L·(n_nodes+1) bins would grow
    # per-block VMEM quadratically with node count)
    counts = histogram_rows2d(d, n_bins=n_nodes + 1)
    counts = counts[:, :n_nodes]                             # (L, n_nodes)
    start = jnp.cumsum(counts, axis=1) - counts              # exclusive
    take = jnp.minimum(counts, budget)
    b = jnp.arange(budget, dtype=jnp.int32)
    pos = start[:, :, None] + b[None, None, :]               # (L, N, B)
    src = jnp.take_along_axis(order,
                              jnp.clip(pos, 0, q - 1).reshape(L, -1),
                              axis=1).reshape(L, n_nodes, budget)
    send_idx = jnp.where(b[None, None, :] < take[:, :, None], src, -1)
    overflow = (counts - take).sum(axis=1).astype(jnp.int32)
    # reply side: sorted position j holds request order[j]; its reply sits
    # at flat slot dest·B + rank-within-run when it fit the budget
    startx = jnp.concatenate(
        [start, jnp.zeros((L, 1), jnp.int32)], axis=1)       # bin n_nodes
    rank = jnp.arange(q, dtype=jnp.int32)[None, :] - \
        jnp.take_along_axis(startx, sd, axis=1)
    slot = jnp.where((sd < n_nodes) & (rank < budget),
                     sd * budget + rank, -1)
    rows = jnp.broadcast_to(jnp.arange(L)[:, None], (L, q))
    reply_idx = jnp.zeros((L, q), jnp.int32).at[rows, order].set(slot)
    return send_idx, reply_idx, overflow


def _compact_gather(x: jax.Array, send_idx: jax.Array) -> jax.Array:
    """Gather request rows into send order: (L, q, ...) → (L, N, B, ...).

    Empty budget slots (send_idx == -1) come back zero.  On TPU this is the
    chunk_pack Pallas kernel over the row-flattened batch.
    """
    L = x.shape[0]
    out = gather_rows_batched(
        x, send_idx.reshape(L, send_idx.shape[1] * send_idx.shape[2]))
    return out.reshape((L,) + send_idx.shape[1:] + x.shape[2:])


def compact_bucketize(dest: jax.Array, valid: jax.Array, n_nodes: int,
                      budget: int, payloads: Dict[str, jax.Array]
                      ) -> Tuple[Dict[str, jax.Array], jax.Array,
                                 jax.Array]:
    """Compacted twin of ``bucketize``: budgeted send buffers, no broadcast.

    dest, valid: (L, q); payloads: {name: (L, q, ...)}.  Returns
    (buffers {name: (L, n_nodes, budget, ...)}, reply_idx (L, q),
    overflow (L,)).  Exchange the buffers, apply at the receiver, then
    route replies back through ``compact_collect(reply_idx, …)``.  There
    is deliberately no separate occupancy mask: append a ones-column to a
    payload before bucketizing — empty budget slots gather the sentinel
    zero row, so the column arrives as the receiver-side validity mask at
    no extra collective (see the engine call sites).
    """
    send_idx, reply_idx, overflow = _compact_plan(dest, valid, n_nodes,
                                                  budget)
    buffers = {name: _compact_gather(p, send_idx)
               for name, p in payloads.items()}
    return buffers, reply_idx, overflow


def compact_collect_flat(reply_idx: jax.Array, reply: jax.Array,
                         fill: int = 0) -> jax.Array:
    """Scatter replies back to request slots: (L, S, ...) → (L, q, ...).

    ``reply_idx`` indexes the flat reply column axis ``S`` (``n_nodes·B``
    for the uniform plan, the packed ``Σbᵢ`` for the ragged one).
    Unserved requests (reply_idx == -1) get ``fill`` — 0 for payload/found,
    -1 for meta size/loc (the dense path's not-found value).
    """
    L, q = reply_idx.shape
    if reply.shape[1] == 0:                     # no traffic at all this round
        return jnp.full((L, q) + reply.shape[2:], fill, reply.dtype)
    extra = (1,) * (reply.ndim - 2)
    safe = jnp.clip(reply_idx, 0, reply.shape[1] - 1)
    got = jnp.take_along_axis(reply, safe.reshape((L, q) + extra), axis=1)
    return jnp.where((reply_idx >= 0).reshape((L, q) + extra), got, fill)


def compact_collect(reply_idx: jax.Array, reply: jax.Array,
                    fill: int = 0) -> jax.Array:
    """Uniform-budget twin of ``compact_collect_flat``: reply is
    (L, N, B, ...) and is flattened over the (destination, budget) axes."""
    L = reply.shape[0]
    return compact_collect_flat(
        reply_idx,
        reply.reshape((L, reply.shape[1] * reply.shape[2]) + reply.shape[3:]),
        fill)


def _compact_plan_ragged(dest: jax.Array, valid: jax.Array, n_nodes: int,
                         spec: RaggedSpec
                         ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Ragged twin of ``_compact_plan``: per-destination segment widths.

    Returns (send_idx (L, Σbᵢ), reply_idx (L, q), overflow (L,)).  When
    ``spec`` comes from ``plan_ragged_spec`` on the same dest/valid,
    overflow is zero by construction; it is still computed so property
    tests can assert the invariant.
    """
    L, q = dest.shape
    d = jnp.where(valid, dest, n_nodes).astype(jnp.int32)
    order = jnp.argsort(d, axis=1).astype(jnp.int32)         # stable
    sd = jnp.take_along_axis(d, order, axis=1)
    counts = histogram_rows2d(d, n_bins=n_nodes + 1)[:, :n_nodes]
    start = jnp.cumsum(counts, axis=1) - counts              # exclusive
    dcol = jnp.asarray(spec.dcol)                            # (S,)
    jcol = jnp.asarray(spec.jcol)                            # (S,)
    if spec.total:
        pos = start[:, dcol] + jcol[None, :]                 # (L, S)
        src = jnp.take_along_axis(order, jnp.clip(pos, 0, q - 1), axis=1)
        send_idx = jnp.where(jcol[None, :] < counts[:, dcol], src, -1)
    else:
        send_idx = jnp.zeros((L, 0), jnp.int32)
    b_arr = jnp.asarray(np.asarray(spec.budgets + (0,), np.int32))
    off_arr = jnp.asarray(np.concatenate([spec.offsets, [0]]).astype(
        np.int32))
    take = jnp.minimum(counts, b_arr[None, :n_nodes])
    overflow = (counts - take).sum(axis=1).astype(jnp.int32)
    startx = jnp.concatenate(
        [start, jnp.zeros((L, 1), jnp.int32)], axis=1)       # bin n_nodes
    rank = jnp.arange(q, dtype=jnp.int32)[None, :] - \
        jnp.take_along_axis(startx, sd, axis=1)
    slot = jnp.where((sd < n_nodes) & (rank < b_arr[sd]),
                     off_arr[sd] + rank, -1)
    rows = jnp.broadcast_to(jnp.arange(L)[:, None], (L, q))
    reply_idx = jnp.zeros((L, q), jnp.int32).at[rows, order].set(slot)
    return send_idx, reply_idx, overflow


def ragged_exchange(x: jax.Array, spec: RaggedSpec,
                    n_nodes: int) -> jax.Array:
    """Stacked (single-device) exchange of a packed ragged send buffer.

    x: (L = n_nodes, Σbᵢ, ...) — source-major packed segments.  Returns the
    receiver view (n_nodes, n_nodes·bmax, ...): destination ``d`` sees its
    own segment from every source, padded to the widest segment ``bmax``
    with zero rows (the pad slots carry the sentinel occupancy 0, so the
    fused ones-column trick marks them invalid at no extra traffic).

    Only the Σbᵢ packed columns are modeled as crossing the exchange — the
    pad-to-bmax happens on the receiver.  There is deliberately no mesh
    twin: ``lax.all_to_all`` needs uniform splits, which is exactly why
    the mesh backend uses a ``MeshRaggedSpec`` (padded or ppermute plan)
    instead.
    """
    col = jnp.asarray(spec.recv_cols)                    # (n_nodes·bmax,)
    if col.shape[0] == 0:
        return jnp.zeros((n_nodes, 0) + x.shape[2:], x.dtype)
    xg = jnp.take(x, jnp.maximum(col, 0), axis=1)        # (L, N·bmax, ...)
    mask = (col >= 0).reshape((1, -1) + (1,) * (x.ndim - 2))
    xg = jnp.where(mask, xg, 0)
    xg = xg.reshape((x.shape[0], n_nodes, spec.bmax) + x.shape[2:])
    return jnp.swapaxes(xg, 0, 1).reshape(
        (n_nodes, x.shape[0] * spec.bmax) + x.shape[2:])


def ragged_reply_exchange(reply: jax.Array, spec: RaggedSpec,
                          n_nodes: int) -> jax.Array:
    """Inverse of ``ragged_exchange`` for the reply direction.

    reply: (n_nodes, n_nodes·bmax, ...) — replies computed at the receiver
    in padded receive order.  Returns (n_nodes, Σbᵢ, ...): each source's
    packed reply columns, ready for ``compact_collect_flat``.
    """
    if spec.total == 0:
        return jnp.zeros((n_nodes, 0) + reply.shape[2:], reply.dtype)
    r = reply.reshape((n_nodes, n_nodes, spec.bmax) + reply.shape[2:])
    rT = jnp.swapaxes(r, 0, 1)                       # (src, dst, bmax, ...)
    flat = rT.reshape((n_nodes, n_nodes * spec.bmax) + reply.shape[2:])
    return jnp.take(flat, jnp.asarray(spec.send_cols), axis=1)


# ---------------------------------------------------------------------------
# executors: one interface, four transports
# ---------------------------------------------------------------------------
@dataclass(frozen=True)
class DenseExecutor:
    """The PR-1 bucketize broadcast — O(N²·q), kept as the parity oracle."""

    n_nodes: int
    carry_budget: int = 0
    drop: bool = False

    def plan(self, dest: jax.Array, valid: jax.Array,
             client: Optional[jax.Array] = None) -> ExchangePlan:
        """Dense needs no permutation: the plan is the routing itself."""
        return ExchangePlan(dest, valid)

    def send(self, plan: ExchangePlan, fields: jax.Array,
             exchange: Callable, shift: Callable
             ) -> Tuple[jax.Array, jax.Array]:
        """Broadcast-bucketize the fused fields; the trailing ones-column
        arrives as the receiver validity mask (it equals the hit mask)."""
        buckets, _ = bucketize(plan.dest, plan.valid, self.n_nodes,
                               {"f": fields})
        rf = exchange(buckets["f"])                 # (L, N_src, q, F)
        L = rf.shape[0]
        recv = rf.reshape(L, rf.shape[1] * rf.shape[2], rf.shape[3])
        return recv[..., :-1], recv[..., -1] > 0

    def collect(self, plan: ExchangePlan, reply: jax.Array,
                exchange: Callable, shift: Callable,
                fill: int = 0) -> jax.Array:
        """Reply buckets travel back and each slot takes its destination's
        answer (``fill`` unused: every in-range dest matches one bucket)."""
        L, M = reply.shape[:2]
        q = M // self.n_nodes
        r = exchange(reply.reshape((L, self.n_nodes, q) + reply.shape[2:]))
        return collect_replies(plan.dest, r, self.n_nodes)

    def served(self, plan: ExchangePlan) -> jax.Array:
        """Dense serves every valid request in one round."""
        return plan.valid


@dataclass(frozen=True)
class UniformExecutor:
    """Jit-static per-destination budget B — the ``all_to_all`` shape.

    Doubles as the mesh "padded" ragged plan when ``budget`` is the
    measured global-max ``bmax`` (``carry_budget=0``: overflow is
    impossible by construction, so the carry round is statically elided).
    """

    n_nodes: int
    budget: int
    carry_budget: int = 0
    drop: bool = False

    def plan(self, dest: jax.Array, valid: jax.Array,
             client: Optional[jax.Array] = None) -> ExchangePlan:
        """Destination-stable argsort + budget clip (``_compact_plan``)."""
        send_idx, reply_idx, overflow = _compact_plan(
            dest, valid, self.n_nodes, self.budget)
        return ExchangePlan(dest, valid, send_idx, reply_idx, overflow)

    def send(self, plan: ExchangePlan, fields: jax.Array,
             exchange: Callable, shift: Callable
             ) -> Tuple[jax.Array, jax.Array]:
        """Gather into (L, N, B) budgeted buffers, one collective."""
        rf = exchange(_compact_gather(fields, plan.send_idx))
        L = rf.shape[0]
        recv = rf.reshape(L, rf.shape[1] * rf.shape[2], rf.shape[3])
        return recv[..., :-1], recv[..., -1] > 0

    def collect(self, plan: ExchangePlan, reply: jax.Array,
                exchange: Callable, shift: Callable,
                fill: int = 0) -> jax.Array:
        """One reply collective, scattered through the inverse plan."""
        L, M = reply.shape[:2]
        r = exchange(reply.reshape(
            (L, self.n_nodes, M // self.n_nodes) + reply.shape[2:]))
        return compact_collect(plan.reply_idx, r, fill)

    def served(self, plan: ExchangePlan) -> jax.Array:
        """Requests whose reply slot fit this round's budget."""
        return plan.reply_idx >= 0


@dataclass(frozen=True)
class RaggedExecutor:
    """Packed (L, Σbᵢ) histogram-sized segments — stacked backend only."""

    n_nodes: int
    spec: RaggedSpec
    carry_budget: int = 0
    drop: bool = False

    def plan(self, dest: jax.Array, valid: jax.Array,
             client: Optional[jax.Array] = None) -> ExchangePlan:
        """Segment-packed routing plan (``_compact_plan_ragged``)."""
        send_idx, reply_idx, overflow = _compact_plan_ragged(
            dest, valid, self.n_nodes, self.spec)
        return ExchangePlan(dest, valid, send_idx, reply_idx, overflow)

    def send(self, plan: ExchangePlan, fields: jax.Array,
             exchange: Callable, shift: Callable
             ) -> Tuple[jax.Array, jax.Array]:
        """Only the Σbᵢ packed columns cross; pad-to-bmax at the receiver."""
        recv = ragged_exchange(gather_rows_batched(fields, plan.send_idx),
                               self.spec, self.n_nodes)
        return recv[..., :-1], recv[..., -1] > 0

    def collect(self, plan: ExchangePlan, reply: jax.Array,
                exchange: Callable, shift: Callable,
                fill: int = 0) -> jax.Array:
        """Packed reply columns back to their request slots."""
        rr = ragged_reply_exchange(reply, self.spec, self.n_nodes)
        return compact_collect_flat(plan.reply_idx, rr, fill)

    def served(self, plan: ExchangePlan) -> jax.Array:
        """Measured segments cover every request (lossless by plan)."""
        return plan.valid


@dataclass(frozen=True)
class PermuteExecutor:
    """Segmented ``ppermute`` exchange: N−1 shift rounds, measured widths.

    Round k ships only what any node sends to its rank+k neighbour
    (``spec.round_widths[k]``), so a skewed destination histogram pays
    for its hot (source, destination) pair once instead of padding every
    pair to the global max; round 0 — self traffic, e.g. the node-local
    half of a hybrid batch — never crosses the fabric.  Received columns
    are re-permuted to source-major order before the table apply, so the
    arrival order (hence every digest) is bit-for-bit the dense path's.

    ``pipeline=True`` (default) software-pipelines the shift rounds with
    the ragx double-buffer discipline: each round's send buffer is a
    *load* (the ``chunk_pack`` gather) and its collective a *store*; the
    loop keeps one round of lookahead — round k+1's load is issued
    before round k's store — with a one-round prologue (first load) and
    epilogue (last store).  Every round then depends only on its own
    gather instead of one fused all-rounds gather, so the scheduler can
    run round k's collective while round k+1 packs.  Identical values
    either way; ``pipeline=False`` keeps the synchronous single-gather
    structure for A/B benchmarking.
    """

    n_nodes: int
    spec: MeshRaggedSpec
    carry_budget: int = 0
    drop: bool = False
    pipeline: bool = True

    def plan(self, dest: jax.Array, valid: jax.Array,
             client: Optional[jax.Array] = None) -> ExchangePlan:
        """Routing plan over the shift-round diagonals.

        ``client``: (L, 1) global ranks of the local rows — round k's
        target for row of rank r is ``(r + k) mod N``, which is also how
        a received column's source is recovered on the other side.
        Required: without the true ranks every shift round would
        mis-route under shard_map (where L=1 and the row index is NOT
        the rank), so a missing ``client`` is an error, not a default.
        """
        if client is None:
            raise ValueError(
                "PermuteExecutor.plan needs the local rows' global ranks "
                "(client); engine entry points thread them — pass "
                "_client_ranks(L, node_ids) when calling run_exchange "
                "with a ppermute spec directly")
        N, spec = self.n_nodes, self.spec
        L, q = dest.shape
        rank = client[:, 0]                                      # (L,)
        d = jnp.where(valid, dest, N).astype(jnp.int32)
        order = jnp.argsort(d, axis=1).astype(jnp.int32)         # stable
        sd = jnp.take_along_axis(d, order, axis=1)
        counts = histogram_rows2d(d, n_bins=N + 1)[:, :N]
        start = jnp.cumsum(counts, axis=1) - counts              # exclusive
        col_round = jnp.asarray(spec.col_round)                  # (S,)
        col_pos = jnp.asarray(spec.col_pos)                      # (S,)
        w_arr = jnp.asarray(np.asarray(spec.round_widths + (0,), np.int32))
        off_arr = jnp.asarray(spec.offsets)                      # (N+1,)
        if spec.total:
            t = (rank[:, None] + col_round[None, :]) % N         # (L, S)
            cnt = jnp.take_along_axis(counts, t, axis=1)
            pos = jnp.take_along_axis(start, t, axis=1) + col_pos[None, :]
            src = jnp.take_along_axis(order, jnp.clip(pos, 0, q - 1),
                                      axis=1)
            send_idx = jnp.where(col_pos[None, :] < cnt, src, -1)
            # receive side: the column shipped in round k came from rank−k;
            # stable-sort columns by source to restore dense arrival order
            src_rank = (rank[:, None] - col_round[None, :]) % N
            recv_perm = jnp.argsort(src_rank, axis=1).astype(jnp.int32)
            rows = jnp.broadcast_to(jnp.arange(L)[:, None],
                                    (L, spec.total))
            inv_perm = jnp.zeros((L, spec.total), jnp.int32).at[
                rows, recv_perm].set(jnp.broadcast_to(
                    jnp.arange(spec.total, dtype=jnp.int32)[None, :],
                    (L, spec.total)))
        else:
            send_idx = jnp.zeros((L, 0), jnp.int32)
            recv_perm = inv_perm = jnp.zeros((L, 0), jnp.int32)
        # a request with destination d rides round (d − rank) mod N
        k_sorted = jnp.where(sd < N, (sd - rank[:, None]) % N, N)
        startx = jnp.concatenate(
            [start, jnp.zeros((L, 1), jnp.int32)], axis=1)
        run_rank = jnp.arange(q, dtype=jnp.int32)[None, :] - \
            jnp.take_along_axis(startx, sd, axis=1)
        slot = jnp.where((sd < N) & (run_rank < w_arr[k_sorted]),
                         off_arr[k_sorted] + run_rank, -1)
        rows = jnp.broadcast_to(jnp.arange(L)[:, None], (L, q))
        reply_idx = jnp.zeros((L, q), jnp.int32).at[rows, order].set(slot)
        # overflow (0 by construction when spec measured this dest/valid)
        darange = jnp.arange(N, dtype=jnp.int32)
        cap = w_arr[(darange[None, :] - rank[:, None]) % N]
        overflow = (counts - jnp.minimum(counts, cap)).sum(
            axis=1).astype(jnp.int32)
        return ExchangePlan(dest, valid, send_idx, reply_idx, overflow,
                            recv_perm, inv_perm)

    def _segments(self):
        off = self.spec.offsets
        return [(k, int(off[k]), int(w))
                for k, w in enumerate(self.spec.round_widths) if w > 0]

    def _ship_rounds(self, segments, load_fn, store_fn):
        """Software-pipelined round loop (shared by send and collect).

        ``load_fn(k, off, w)`` packs round k's buffer (the chunk gather
        on the send side, the reply slice on the collect side);
        ``store_fn(k, buf)`` ships it through the collective.  With
        ``pipeline`` on, the loop keeps ragx-style one-round lookahead —
        prologue issues load 0, each iteration issues load k+1 *before*
        store k, the epilogue stores the final load — so no store ever
        waits on a later round's pack.  Off, it degrades to the strict
        load-all-then-store order of the synchronous plan.  Either way
        the returned per-round buffers are value-identical.
        """
        if not self.pipeline:
            loads = [load_fn(k, off, w) for k, off, w in segments]
            return [store_fn(k, buf)
                    for (k, _, _), buf in zip(segments, loads)]
        parts = []
        load_tag = None                                  # prologue: empty
        for i, (k, off, w) in enumerate(segments):
            with obs.span("exchange.pipeline.load", cat="trace", round=k):
                next_load = load_fn(k, off, w)
            if load_tag is not None:
                prev_k = segments[i - 1][0]
                with obs.span("exchange.pipeline.store", cat="trace",
                              round=prev_k):
                    parts.append(store_fn(prev_k, load_tag))
            load_tag = next_load
        if load_tag is not None:                         # epilogue
            last_k = segments[-1][0]
            with obs.span("exchange.pipeline.store", cat="trace",
                          round=last_k):
                parts.append(store_fn(last_k, load_tag))
        return parts

    def send(self, plan: ExchangePlan, fields: jax.Array,
             exchange: Callable, shift: Callable
             ) -> Tuple[jax.Array, jax.Array]:
        """Pack and shift each nonzero round, restore source order.

        Pipelined: per-round ``chunk_pack`` gathers, one round of
        lookahead.  Synchronous: one fused gather of every round before
        any shift (the PR-5 structure, where the first collective waits
        on the whole pack).  Round 0 is self traffic, no collective.
        """
        segments = self._segments()
        if not self.pipeline:
            gathered = gather_rows_batched(fields, plan.send_idx)
            parts = [gathered[:, off:off + w] if k == 0
                     else shift(gathered[:, off:off + w], k)
                     for k, off, w in segments]
        else:
            def load(k, off, w):
                return gather_rows_batched(fields,
                                           plan.send_idx[:, off:off + w])

            def store(k, buf):
                return buf if k == 0 else shift(buf, k)

            parts = self._ship_rounds(segments, load, store)
        if not parts:
            L = fields.shape[0]
            return (jnp.zeros((L, 0, fields.shape[-1] - 1), fields.dtype),
                    jnp.zeros((L, 0), bool))
        recv = jnp.concatenate(parts, axis=1)           # round order
        recv = jnp.take_along_axis(recv, plan.recv_perm[..., None], axis=1)
        return recv[..., :-1], recv[..., -1] > 0

    def collect(self, plan: ExchangePlan, reply: jax.Array,
                exchange: Callable, shift: Callable,
                fill: int = 0) -> jax.Array:
        """Shift each round's replies home and scatter to request slots."""
        if self.spec.total == 0:
            L, q = plan.reply_idx.shape
            return jnp.full((L, q) + reply.shape[2:], fill, reply.dtype)
        back = jnp.take_along_axis(
            reply, plan.inv_perm.reshape(plan.inv_perm.shape +
                                         (1,) * (reply.ndim - 2)), axis=1)

        def load(k, off, w):
            return back[:, off:off + w]

        def store(k, buf):
            return buf if k == 0 else shift(buf, -k)

        parts = self._ship_rounds(self._segments(), load, store)
        home = jnp.concatenate(parts, axis=1)           # round order
        return compact_collect_flat(plan.reply_idx, home, fill)

    def served(self, plan: ExchangePlan) -> jax.Array:
        """Measured round widths cover every request (lossless by plan)."""
        return plan.valid


Executor = Union[DenseExecutor, UniformExecutor, RaggedExecutor,
                 PermuteExecutor]


def build_executor(role: str, policy, q: int,
                   config: ExchangeConfig) -> Executor:
    """THE planner: one routing decision shared by every entry point.

    ``role`` is "data" or "meta" (it selects the budget rule and which
    spec slot of ``config`` applies).  This is the only function that
    inspects ``ExchangeConfig`` to choose a transport — entry points and
    backends never branch on exchange modes themselves.
    """
    policy = as_policy(policy)
    N = policy.n_nodes
    if config.kind != "compacted":
        return DenseExecutor(N)
    spec = config.data_spec if role == "data" else config.meta_spec
    if isinstance(spec, MeshRaggedSpec):
        if spec.executor == "ppermute":
            return PermuteExecutor(N, spec, pipeline=config.pipeline)
        # padded path: uniform all_to_all at the measured global max —
        # lossless by construction, so no carry round is traced
        return UniformExecutor(N, max(1, spec.bmax))
    if isinstance(spec, RaggedSpec):
        return RaggedExecutor(N, spec)
    B = (data_budget(policy, q, config) if role == "data"
         else meta_budget(policy, q, config))
    carry = _carry_budget(q, B) if (config.lossless and B < q) else 0
    if carry and config.carry_budget_hint is not None:
        # measured overflow histogram: cap the carry round at the observed
        # residual (still an upper bound, so losslessness is preserved);
        # a zero hint elides the carry round statically
        carry = min(carry, max(0, int(config.carry_budget_hint)))
    return UniformExecutor(N, B, carry_budget=carry,
                           drop=not config.lossless)


def fuse_specs(data_spec, meta_spec):
    """Summed ragged spec for the fused write collective (None = not fusable).

    The fused write ships both planes through ONE packed buffer whose
    per-destination segment is the data segment followed by the metadata
    segment, so the combined spec's budgets are the planewise sums: each
    (source, destination) pair sends at most ``b_d[i] + b_m[i]`` fused
    rows, which the summed budgets cover exactly — the fused plan stays
    lossless whenever both component plans were.  Only stacked
    ``RaggedSpec`` pairs need a summed spec (``ragged_exchange`` runs on
    it); mesh padded plans fuse as two uniform budgets concatenated on
    the ``all_to_all`` budget axis, and ppermute plans never fuse — see
    ``fused_write_plan``.
    """
    if isinstance(data_spec, RaggedSpec) and isinstance(meta_spec,
                                                        RaggedSpec):
        if data_spec.n_nodes != meta_spec.n_nodes:
            return None
        return RaggedSpec(tuple(bd + bm for bd, bm in
                                zip(data_spec.budgets, meta_spec.budgets)))
    return None


def _fused_pack_cols(spec_d: RaggedSpec, spec_m: RaggedSpec) -> np.ndarray:
    """(Σbᵈ+Σbᵐ,) column of ``concat([data_packed, meta_packed])`` feeding
    each fused packed column (destination-major, data plane first)."""
    cols = []
    for d in range(spec_d.n_nodes):
        od, om = int(spec_d.offsets[d]), int(spec_m.offsets[d])
        cols.append(np.arange(od, od + spec_d.budgets[d]))
        cols.append(spec_d.total + np.arange(om, om + spec_m.budgets[d]))
    return (np.concatenate(cols).astype(np.int32) if cols
            else np.zeros(0, np.int32))


def _fused_recv_cols(spec_d: RaggedSpec, spec_m: RaggedSpec,
                     fused: RaggedSpec
                     ) -> Tuple[np.ndarray, np.ndarray]:
    """Per-plane receive maps into the fused ``ragged_exchange`` view.

    Returns (data (N, N·bmaxᵈ), meta (N, N·bmaxᵐ)) int32 maps: entry
    ``[i, s·bmaxᵖ + j]`` is the fused receive column holding receiver
    ``i``'s j-th row from source ``s`` on plane p, or -1 for a pad slot
    (zero-masked, so the occupancy column marks it invalid).  Each map
    reproduces exactly the plane's serial receive view — source-major,
    padded to the plane's own ``bmax`` — from the fused buffer, so the
    receiver applies scan the same rows the serial rounds handed them.
    """
    n = spec_d.n_nodes
    bf = max(fused.bmax, 0)

    def plane(spec: RaggedSpec, base) -> np.ndarray:
        bp = max(spec.bmax, 0)
        idx = np.full((n, n * bp), -1, np.int32)
        for i in range(n):
            b = spec.budgets[i]
            for s in range(n):
                idx[i, s * bp:s * bp + b] = \
                    s * bf + base[i] + np.arange(b)
        return idx

    return (plane(spec_d, [0] * n), plane(spec_m, list(spec_d.budgets)))


def _take_recv_cols(recv: jax.Array, cols: np.ndarray) -> jax.Array:
    """Static per-row column gather with -1 → zero-row masking."""
    col = jnp.asarray(cols)
    if col.shape[1] == 0:
        return jnp.zeros((recv.shape[0], 0) + recv.shape[2:], recv.dtype)
    ext = col.reshape(col.shape + (1,) * (recv.ndim - 2))
    got = jnp.take_along_axis(recv, jnp.maximum(ext, 0), axis=1)
    return jnp.where(ext >= 0, got, 0)


def fused_write_plan(policy, q: int, config: ExchangeConfig
                     ) -> Optional[Tuple[Executor, Executor]]:
    """Per-plane executors for the fused write round-trip (None = elided).

    Returns ``(data_executor, meta_executor)`` when the write's data and
    metadata rounds can ship through one collective (``fused_send``), or
    ``None`` when fusion is elided: dense kind, pipelining off, the drop
    plane (``lossless=False`` skips overflowed metadata anyway),
    measured specs of mismatched types, a ppermute plane (fusing would
    serialize both planes' packs behind the 2(N−1) shift rounds the
    serial path overlaps, and the receive split is not static across
    rounds), or any plan that could overflow into a carry round.  The
    overflow rule is a parity requirement, not a performance one: a
    fused carry would re-split the metadata batch across two
    ``_meta_apply`` calls, and within-batch duplicate keys allocate
    differently in one call than in two — so only provably overflow-free
    plans fuse (measured specs, which size every segment from the actual
    histogram, or uniform budgets already at ``B = q`` on both planes).
    The default client path measures specs, so stacked and mesh-padded
    writes always fuse.
    """
    if config.kind != "compacted" or not config.pipeline \
            or not config.lossless or q == 0:
        return None
    policy = as_policy(policy)
    N = policy.n_nodes
    ds, ms = config.data_spec, config.meta_spec
    if ds is not None or ms is not None:
        if isinstance(ds, MeshRaggedSpec) and isinstance(ms,
                                                         MeshRaggedSpec):
            if ds.n_nodes != ms.n_nodes \
                    or "ppermute" in (ds.executor, ms.executor):
                return None
            return (UniformExecutor(N, max(1, ds.bmax)),
                    UniformExecutor(N, max(1, ms.bmax)))
        if isinstance(ds, RaggedSpec) and isinstance(ms, RaggedSpec) \
                and fuse_specs(ds, ms) is not None:
            return RaggedExecutor(N, ds), RaggedExecutor(N, ms)
        return None
    if data_budget(policy, q, config) < q \
            or meta_budget(policy, q, config) < q:
        return None
    return UniformExecutor(N, q), UniformExecutor(N, q)


def fused_send(ex_d: Executor, plan_d: ExchangePlan, fields_d: jax.Array,
               ex_m: Executor, plan_m: ExchangePlan, fields_m: jax.Array,
               exchange: Callable
               ) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Ship two planes' packed request buffers through ONE collective.

    Returns ``(recv_d, rvalid_d, recv_m, rvalid_m)`` — each plane's
    receive view and validity mask, exactly as the plane's own
    ``Executor.send`` would have produced them over two collectives.
    The per-plane plans and packed row order are the serial rounds'
    (same ``_compact_plan`` / ``_compact_plan_ragged`` on the same
    routing), and the receiver split hands each apply only its own
    plane's rows — so both applies see bit-identical inputs to the
    serial two-round write while the fabric sees a single launch.

    Supported pairs (all ``fused_write_plan`` ever builds): two
    ``UniformExecutor``\\ s — uniform budgets and the mesh padded plan,
    whose segments concatenate on the static budget axis the
    ``all_to_all`` splits — and two stacked ``RaggedExecutor``\\ s,
    whose static per-destination offsets make the packed interleave and
    the receive split constant index maps (``_fused_pack_cols`` /
    ``_fused_recv_cols``).
    """
    exchange = _spanned_collective(exchange, "exchange.all_to_all")
    if isinstance(ex_d, UniformExecutor):
        buf = jnp.concatenate(
            [_compact_gather(fields_d, plan_d.send_idx),
             _compact_gather(fields_m, plan_m.send_idx)], axis=2)
        r = exchange(buf)                       # (L, N, B_d + B_m, F)
        L, n = r.shape[0], r.shape[1]
        rd = r[:, :, :ex_d.budget].reshape(
            (L, n * ex_d.budget) + r.shape[3:])
        rm = r[:, :, ex_d.budget:].reshape(
            (L, n * ex_m.budget) + r.shape[3:])
    else:
        spec_d, spec_m = ex_d.spec, ex_m.spec
        fused = fuse_specs(spec_d, spec_m)
        packed = jnp.concatenate(
            [gather_rows_batched(fields_d, plan_d.send_idx),
             gather_rows_batched(fields_m, plan_m.send_idx)], axis=1)
        packed = jnp.take(packed,
                          jnp.asarray(_fused_pack_cols(spec_d, spec_m)),
                          axis=1)
        recv = ragged_exchange(packed, fused, ex_d.n_nodes)
        cols_d, cols_m = _fused_recv_cols(spec_d, spec_m, fused)
        rd = _take_recv_cols(recv, cols_d)
        rm = _take_recv_cols(recv, cols_m)
    return rd[..., :-1], rd[..., -1] > 0, rm[..., :-1], rm[..., -1] > 0


def _spanned_collective(fn: Callable, name: str) -> Callable:
    """Wrap a collective hook so each trace-time call records a span.

    The wrapper exists for the duration of one ``run_exchange`` trace, so
    span identity never leaks into jit cache keys (the collective itself
    is unchanged).
    """
    def wrapped(*args, **kwargs):
        with obs.span(name, cat="trace"):
            return fn(*args, **kwargs)
    return wrapped


def run_exchange(role: str, policy, config: ExchangeConfig,
                 dest: jax.Array, valid: jax.Array, fields: jax.Array,
                 apply_fn: Callable, *, exchange: Callable,
                 shift: Callable, global_sum: Callable, state,
                 client: Optional[jax.Array] = None, reply_fill: int = 0
                 ) -> Tuple[object, Optional[jax.Array], jax.Array,
                            jax.Array]:
    """One planned exchange round (+ the shared carry epilogue).

    The single pipeline every engine entry point routes through:

    1. ``build_executor`` picks the transport for (role, config);
    2. the executor plans the routing and ships ``fields`` (a fused
       (L, q, F) int32 buffer whose trailing ones-column becomes the
       receiver validity mask);
    3. ``apply_fn(state, recv, rvalid) -> (new_state | None, reply | None)``
       runs the receiver-side table op — returning ``None`` state means
       the op is read-only, ``None`` reply means no reply round is needed;
    4. replies are transported back and scattered to request slots;
    5. a lossless uniform under-budget plan *carries* the residual into a
       cond-skipped second round at ``q − B`` — the one copy of the carry
       logic three entry points used to duplicate.

    Returns ``(state, out, served, overflow)``: the (possibly updated)
    state, the collected (L, q, R) reply (None when ``apply_fn`` produced
    none), the round-1 served mask and the round-1 overflow counter — the
    engine's shared wrapper turns the latter into ``dropped`` accounting
    under the legacy ``lossless=False`` plane.  ``global_sum`` must
    reduce over ALL nodes so the carry cond branches identically
    everywhere; ``client`` carries the local rows' global ranks for the
    shift-round executor.

    When a flight recorder is active (``obs.activate``) or a profiler
    capture runs, each pipeline stage records a ``cat="trace"`` span —
    ``exchange.plan`` → ``exchange.pack`` (wrapping the
    ``exchange.all_to_all`` / ``exchange.ppermute`` collective spans) →
    ``exchange.apply`` → ``exchange.collect`` → ``exchange.carry``.  This
    code runs while jax is *tracing*, so the spans fire once per
    specialization and measure plan/lowering cost, giving the recording
    its nested structure.
    """
    exchange = _spanned_collective(exchange, "exchange.all_to_all")
    shift = _spanned_collective(shift, "exchange.ppermute")
    with obs.span("exchange.plan", cat="trace", role=role,
                  kind=config.kind):
        ex = build_executor(role, policy, dest.shape[1], config)
        plan = ex.plan(dest, valid, client=client)
    with obs.span("exchange.pack", cat="trace", role=role,
                  executor=type(ex).__name__):
        recv, rvalid = ex.send(plan, fields, exchange, shift)
    with obs.span("exchange.apply", cat="trace", role=role):
        new_state, reply = apply_fn(state, recv, rvalid)
    mutates = new_state is not None
    st = new_state if mutates else state
    with obs.span("exchange.collect", cat="trace", role=role):
        out = (None if reply is None
               else ex.collect(plan, reply, exchange, shift, reply_fill))
    served = ex.served(plan)
    if ex.carry_budget:
        resid = valid & ~served
        ex2 = UniformExecutor(ex.n_nodes, ex.carry_budget)
        # pipelined carry: the residual plan only depends on round-1 plan
        # outputs, so hoisting it out of the cond lets it overlap the main
        # round's collective instead of serializing behind the cond gate
        hoisted = None
        if config.pipeline:
            with obs.span("exchange.carry.plan", cat="trace", role=role):
                hoisted = ex2.plan(dest, resid, client=client)

        def _carry(op):
            st_in = op if mutates else state
            plan2 = (hoisted if hoisted is not None
                     else ex2.plan(dest, resid, client=client))
            recv2, rvalid2 = ex2.send(plan2, fields, exchange, shift)
            st2, reply2 = apply_fn(st_in, recv2, rvalid2)
            res = (st2,) if mutates else ()
            if out is not None:
                res += (ex2.collect(plan2, reply2, exchange, shift,
                                    reply_fill),)
            return res

        def _skip(op):
            res = (op,) if mutates else ()
            if out is not None:
                res += (jnp.full_like(out, reply_fill),)
            return res

        with obs.span("exchange.carry", cat="trace", role=role,
                      carry_budget=int(ex.carry_budget)):
            got = jax.lax.cond(_carry_taken(plan.overflow, global_sum),
                               _carry, _skip,
                               st if mutates else jnp.int32(0))
        i = 0
        if mutates:
            st = got[i]
            i += 1
        if out is not None:
            out = jnp.where(resid.reshape(resid.shape +
                                          (1,) * (out.ndim - 2)),
                            got[i], out)
    overflow = (plan.overflow if plan.overflow is not None
                else jnp.zeros(dest.shape[0], jnp.int32))
    return st, out, served, overflow


# ---------------------------------------------------------------------------
# modeled footprint
# ---------------------------------------------------------------------------
def _spec_cols(spec, n_nodes: int, uniform_b: int) -> int:
    """Exchanged send-buffer columns per source row for one plan."""
    if isinstance(spec, MeshRaggedSpec):
        return (spec.exchanged_cols if spec.executor == "ppermute"
                else n_nodes * max(1, spec.bmax))
    if isinstance(spec, RaggedSpec):
        return spec.total
    return n_nodes * uniform_b


def exchange_footprint(policy, q: int, words: int,
                       config: ExchangeConfig) -> Dict[str, int]:
    """Modeled int32 elements crossing the exchange per engine call.

    Counts every exchanged buffer (requests, masks and replies) for one
    write, one read (no broadcast fallback) and one metadata round; the
    benchmark harness converts these to bytes.  Dense buffers carry q slots
    per (src, dst) pair; uniform compacted ones the per-destination budget;
    ragged ones the measured packed columns per source row — Σbᵢ for the
    stacked plan, N·bmax for the mesh padded plan, and the Σ of the
    nonzero off-diagonal round widths for the ppermute plan (round 0 is
    node-local and crosses nothing).  The ``*_carry_elems`` fields are
    the worst case of the cond-skipped lossless carry round — 0 when no
    overflow occurs (the common case) and 0 by construction for measured
    ragged plans and lossless B=q.

    When the pipelined write fusion applies (``fused_write_plan``), the
    write ships both planes' packed columns through one collective and
    no metadata replies: the element count is the two planes' request
    columns at the common fused row width (metadata rows are padded to
    the payload width) — one launch instead of three, which is exactly
    the trade ``make bench-pipeline`` measures.
    """
    policy = as_policy(policy)
    N = policy.n_nodes
    if config.kind == "compacted":
        bd, bm = data_budget(policy, q, config), meta_budget(policy, q,
                                                             config)
    else:
        bd = bm = q
    cols_d = (_spec_cols(config.data_spec, N, bd)
              if config.kind == "compacted" else N * bd)
    cols_m = (_spec_cols(config.meta_spec, N, bm)
              if config.kind == "compacted" else N * bm)
    w_meta, w_wr, w_rd = (4 + 1) + 3, (2 + words + 1), (2 + 1) + (words + 1)
    w_fused = max(2 + words, 4) + 1           # widest plane row + mask
    meta = N * cols_m * w_meta                # op/key/size/loc+mask → replies
    write = N * cols_d * w_wr + meta          # keys+payload+mask, then meta
    read = N * cols_d * w_rd
    carry = {"write_carry_elems": 0, "read_carry_elems": 0,
             "meta_carry_elems": 0}
    fplan = fused_write_plan(policy, q, config)
    if fplan is not None:
        write = N * (cols_d + cols_m) * w_fused     # one launch, no replies
    if config.kind == "compacted" and config.lossless:
        cd = 0 if config.data_spec is not None else _carry_budget(q, bd)
        cm = 0 if config.meta_spec is not None else _carry_budget(q, bm)
        if config.carry_budget_hint is not None:
            cd = min(cd, max(0, int(config.carry_budget_hint)))
            cm = min(cm, max(0, int(config.carry_budget_hint)))
        wc = N * N * cd * w_wr + N * N * cm * w_meta
        if fplan is not None:
            wc = 0          # fused plans are overflow-free by construction
        carry = {"write_carry_elems": wc,
                 "read_carry_elems": N * N * cd * w_rd,
                 "meta_carry_elems": N * N * cm * w_meta}
    return {"kind": config.kind, "data_budget": bd, "meta_budget": bm,
            "lossless": config.lossless,
            "write_elems": write, "read_elems": read, "meta_elems": meta,
            **carry}
