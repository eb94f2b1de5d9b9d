"""BBClient: the unified burst-buffer facade — ``(policy, backend)``.

Construct from a ``LayoutPolicy`` and a backend and get batched
``write/read/stat/create/remove`` with per-request layout modes resolved from
path scopes.  The facade owns everything that used to leak into call sites:
the exchange implementation, global ``node_ids``, reshape plumbing and the
per-request mode arrays.

Backends:

* ``"stacked"`` — single-device execution; the cross-node exchange is a
  transpose of the (src, dst) axes.  Tests, probes, CPU-only quickstarts.
* a ``jax.sharding.Mesh`` — the node axis is sharded 1-per-device under
  ``shard_map`` and the exchange is ``lax.all_to_all`` (mesh_engine.py).
  This is the production data plane.

Both backends run the *identical* engine code (burst_buffer.py), so results
are element-for-element equal — asserted in tests/test_policy.py.
Orthogonally, ``exchange=`` picks the exchange data plane *per call*:

* ``"auto"`` (default) — selects dense vs compacted per call from the
  measured (N, q, words) crossover of the committed benchmark sweep
  (exchange_select.py); dense wins tiny exchanges, compacted wins at scale.
* ``"compacted"`` — sort-based routing + budgeted Pallas gather, O(N·q)
  exchange volume.  Budgets are *ragged* by default on BOTH backends:
  sized per destination from the measured ``chunk_router`` histograms of
  each call (lossless by construction).  The stacked backend packs them
  into one (L, Σbᵢ) buffer; the mesh backend — whose ``all_to_all`` needs
  uniform splits — plans a ``MeshRaggedSpec`` instead: pad to the global
  max budget for the ordinary ``all_to_all``, or run the ``ppermute``
  segmented rounds when the measured histogram is skewed (the executor
  pick keys on the measured fabric model — ``exchange_select``).  With an
  explicit ``budget=``/``ragged=False`` budgets are uniform and
  jit-static, and overflow is carried into a rarely-taken second exchange
  round (``lossless=True``, default) instead of dropped.  Hybrid reads —
  whose destinations come from the metadata tables — go **two-phase**:
  the client runs the metadata probe as its own call, resolves the data
  destinations eagerly, and sizes a measured ragged plan for the data
  round (``two_phase=False`` restores the single-call uniform plan).
* ``"dense"`` — the PR-1 O(N²·q) bucketize broadcast, kept as the
  bit-for-bit parity oracle.

Requests are batched structs (``BBRequest``): node-major arrays shaped
``(n_nodes, q)``.  ``BBClient.encode`` builds one from path strings, hashing
each path and resolving its scope against the policy at the client boundary
(the only place where paths exist as strings).

Online adaptation (``telemetry=True`` + repro.core.adapt): the client
additionally folds every call into per-scope intent counters (jit-side
dense array — production traffic is the probe), keeps a host-side write
registry (which files/chunks each scope holds, who wrote them), and
supports **epoch-versioned policies**: ``install_policy`` swaps the plan
mid-run, and while a ``LiveMigrator`` relocates a scope's stored chunks
the armed dual-epoch fallback re-issues read/stat misses of that scope
under the old mode — lossless at every migration watermark.
"""
from __future__ import annotations

import dataclasses
import functools
from dataclasses import dataclass
from typing import Dict, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import burst_buffer as bb
from repro.core import exchange_select
from repro.core import obs
from repro.core.layouts import LayoutMode, route_data, route_meta, str_hash
from repro.core.policy import SCOPE_NONE, LayoutPolicy, as_policy

EXCHANGE_KINDS = ("auto", "dense", "compacted")


@dataclass(frozen=True)
class EpochFallback:
    """Dual-epoch read/stat routing during a live relayout.

    While a scope migrates, a chunk may still sit at its old-mode
    placement; the client re-issues read/stat *misses* of the migrating
    scope with ``old_mode`` so they are served from the old epoch (the
    engine's Mode-1/4 stranded-data broadcast included).  Armed and
    disarmed by ``BBClient.install_policy``.
    """

    scope_hash: int
    old_mode: int


@dataclass
class BBRequest:
    """A batched I/O request: node-major arrays shaped (n_nodes, q).

    ``path_hash``: (N, q) int32 31-bit FNV path hashes (see ``str_hash``).
    ``chunk_id``: (N, q) int32 chunk index within the file; 0 when omitted.
    ``payload``: (N, q, words) chunk data — writes only.
    ``valid``: (N, q) bool request-slot mask; all-true when omitted.
    ``scope_hash``: (N, q) int32 policy-scope hashes (``encode`` fills
    these); resolved to per-request modes via ``policy.resolve``.
    ``mode``: (N, q) int32 explicit per-request ``LayoutMode`` values —
    overrides scope resolution; must stay within ``policy.modes_present()``.
    ``size``/``loc``: (N, q) int32 metadata fields (create/update size,
    Mode-4 data-location rank) — metadata ops only.
    """

    path_hash: jax.Array
    chunk_id: Optional[jax.Array] = None
    payload: Optional[jax.Array] = None
    valid: Optional[jax.Array] = None
    scope_hash: Optional[jax.Array] = None
    mode: Optional[jax.Array] = None
    size: Optional[jax.Array] = None
    loc: Optional[jax.Array] = None


@functools.lru_cache(maxsize=256)
def _stacked_ops_for(engine_key, config: bb.ExchangeConfig) -> bb.EngineOps:
    """Jitted stacked ops, cached per engine specialization.

    Keyed on ``policy.engine_key()`` (not the policy object) × the full
    ``ExchangeConfig`` — scope strings never reach the engine, so every
    client whose policy traces to the same program shares one set of
    jitted ops and XLA's trace cache.  Ragged configs carry their
    ``RaggedSpec`` in the key, so each measured traffic shape gets (and
    re-uses) its own specialization.  The entry holds both the
    state-keeping ops and their donating ``owned`` twins (``EngineOps``);
    only the ones a caller runs are compiled.
    """
    policy = LayoutPolicy.for_engine_key(engine_key)

    def _write(state, mode, ph, cid, payload, valid):
        return bb.forward_write(state, policy, ph, cid, payload, valid,
                                mode=mode, config=config)

    def _read(state, mode, ph, cid, valid):
        return bb.forward_read(state, policy, ph, cid, valid, mode=mode,
                               config=config)

    def _meta(state, mode, op, ph, size, loc, valid):
        return bb.meta_op(state, policy, op, ph, size, loc, valid, mode=mode,
                          config=config)

    def _read_loc(state, mode, ph, cid, valid, data_loc):
        return bb.forward_read(state, policy, ph, cid, valid, mode=mode,
                               config=config, data_loc=data_loc)

    return bb.jit_engine_ops(_write, _read, _meta, _read_loc)


def _build_stacked_ops(policy: LayoutPolicy,
                       config: bb.ExchangeConfig = bb.DENSE) -> bb.EngineOps:
    """Resolve ``policy`` to its engine key and fetch the cached ops."""
    return _stacked_ops_for(policy.engine_key(), config)


@functools.lru_cache(maxsize=256)
def _stacked_probe_for(engine_key, config: bb.ExchangeConfig):
    """Jitted hybrid-read probe: STAT → (found, loc) ONLY.

    The two-phase read must not pay for state outputs it discards — a
    jit returning the full post-STAT ``BBState`` materializes a copy of
    every table per read.  Tracing ``meta_op`` but returning only the
    two reply arrays lets XLA dead-code-eliminate the table outputs.
    """
    policy = LayoutPolicy.for_engine_key(engine_key)

    def _probe(state, mode, ph, valid):
        shape = ph.shape
        op = jnp.full(shape, bb.OP_STAT, jnp.int32)
        _, found, _, loc = bb.meta_op(
            state, policy, op, ph, jnp.zeros(shape, jnp.int32),
            jnp.full(shape, -1, jnp.int32), valid, mode=mode,
            config=config)
        return found, loc

    return jax.jit(_probe)


@functools.lru_cache(maxsize=64)
def _stacked_migrate_for(engine_key, config: bb.ExchangeConfig):
    """Jitted stacked ``migrate_rows``, cached like ``_stacked_ops_for``.

    Its only caller, ``BBClient.migrate_rows``, rebinds the client's
    state to the result, so the state is always donated."""
    policy = LayoutPolicy.for_engine_key(engine_key)

    def _migrate(state, ph, cid, valid, old_mode, new_mode):
        return bb.migrate_rows(state, policy, ph, cid, valid, old_mode,
                               new_mode, config=config)

    return jax.jit(_migrate, donate_argnums=0)


class BBClient:
    """Facade over the multi-mode burst-buffer engine.

    >>> policy = LayoutPolicy.from_scopes(
    ...     {"ckpt": LayoutMode.HYBRID, "shared": LayoutMode.DIST_HASH},
    ...     n_nodes=8, default=LayoutMode.DIST_HASH)
    >>> client = BBClient(policy)                  # or BBClient(policy, mesh)
    >>> req = client.encode(paths, chunk_id=cids, payload=chunks)
    >>> client.write(req)
    >>> out, found = client.read(req)

    The client owns ``self.state``.  Its mutating calls (``write``,
    ``create``, ``stat``, ``remove``, ``migrate_rows``) donate the state to
    the engine program and rebind ``self.state`` to the result, so the
    node tables are updated in place and the arrays of the old state are
    deleted.  Keep no reference to ``client.state`` across such a call:
    read it again afterwards.  A state adopted through ``state=`` is handed
    over the same way; the first mutating call deletes the caller's
    arrays.  ``read`` and the two-phase probe return no state and donate
    nothing.  The state-explicit entries (``_write``, ``_read``, ``_meta``
    and the programs of ``_ops``) take the state from their caller, who
    may reuse it, and never donate.
    """

    def __init__(self, policy, backend: Union[str, "jax.sharding.Mesh"]
                 = "stacked", *, cap: int = 256, words: int = 16,
                 mcap: int = 256, state: Optional[bb.BBState] = None,
                 exchange: str = "auto", budget: Optional[int] = None,
                 meta_budget: Optional[int] = None, capacity: float = 2.0,
                 lossless: bool = True, ragged: bool = True,
                 two_phase: bool = True, pipeline: bool = True,
                 telemetry: bool = False,
                 trace: Optional[obs.TraceRecorder] = None):
        """Build a client holding fresh (or adopted) node tables.

        Args:
          policy: ``LayoutPolicy`` (or legacy ``LayoutParams``/mode) — the
            per-scope layout plan; fixes ``n_nodes``.
          backend: ``"stacked"`` or a ``jax.sharding.Mesh``.
          cap/words/mcap: per-node data-slot count, chunk width (int32
            words) and metadata-slot count of the held ``BBState``.
          state: adopt an existing ``BBState`` instead of ``init_state``.
            It is handed over: the first mutating call deletes its arrays.
          exchange: ``"auto"`` (default — pick dense vs compacted per call
            from the measured benchmark crossover), ``"dense"``, or
            ``"compacted"``.
          budget/meta_budget: explicit uniform per-destination slot counts
            for the compacted data/metadata exchange (disables ragged
            sizing for that exchange); ``None`` auto-sizes.
          capacity: headroom factor of the uniform auto budgets over the
            uniform-hash expectation ``q/N``.
          lossless: carry uniform-budget overflow into a second exchange
            round (default) instead of the legacy drop-and-account
            semantics (``dropped`` counter, found=False replies).
          ragged: size compacted budgets per destination from each call's
            measured histograms (jit ops then specialize per traffic
            shape).  The stacked backend packs them (``RaggedSpec``); a
            mesh backend plans a ``MeshRaggedSpec`` — global-max padded
            ``all_to_all``, or the ``ppermute`` segmented exchange when
            the measured fabric model says the histogram is skewed enough
            to pay for the extra rounds.
          two_phase: run hybrid reads as metadata probe → ragged data
            round (both backends); ``False`` keeps the single-call
            uniform-budget plan.  Only meaningful with ``ragged=True``.
          pipeline: enable the async exchange restructurings (default) —
            fused write round-trips, software-pipelined ppermute rounds,
            hoisted carry plans, and measured carry-width hints.  Every
            result stays bit-for-bit identical; ``False`` restores the
            synchronous PR-5 call structure (the A/B baseline).
          telemetry: accumulate per-scope intent counters on every call
            (jit-side — see repro.core.adapt.telemetry) and maintain the
            host-side write registry the ``LiveMigrator`` builds its
            worklists from.  On a mesh backend the counters are kept
            per-node so ``mesh_engine.build_telemetry_reduce`` can psum
            them fleet-wide (drift fires from any host).  Adds a small
            host loop per call; off by default for hot-path clients that
            don't adapt.
          trace: an ``obs.TraceRecorder`` flight recorder.  Every engine
            call then records a fenced ``client.*`` span, byte/carry/drop
            accounting lands in ``trace.metrics``, and selector picks are
            audited into ``trace.audit`` (see docs/observability.md).
            With ``None`` (default) the same spans reach only a running
            ``jax.profiler`` capture, unfenced, and cost two checks each
            when none runs.
        """
        self.policy = as_policy(policy)
        self.backend = backend
        self.n_nodes = self.policy.n_nodes
        self.words = words
        if exchange not in EXCHANGE_KINDS:
            raise ValueError(f"unknown exchange {exchange!r}; pass one of "
                             f"{EXCHANGE_KINDS}")
        self.exchange_mode = exchange
        self.pipeline = bool(pipeline)
        self.exchange_config = bb.ExchangeConfig(
            kind=exchange if exchange != "auto" else "compacted",
            budget=budget, meta_budget=meta_budget, capacity=capacity,
            lossless=lossless, pipeline=self.pipeline)
        self._path_codes = functools.lru_cache(maxsize=1 << 16)(
            self._path_codes_uncached)
        self._pick_cache: Dict[int, str] = {}
        self.obs = trace
        # modeled-footprint memo per (q, config) — accounting must not
        # re-derive budgets on every traced call
        self._foot_cache: Dict[Tuple[int, bb.ExchangeConfig],
                               Dict[str, int]] = {}
        self._is_mesh = not isinstance(backend, str)
        if not self._is_mesh and backend != "stacked":
            raise ValueError(f"unknown backend {backend!r}; pass "
                             "'stacked' or a jax.sharding.Mesh")
        if state is None and self._is_mesh:
            # created sharded: no chip ever holds the whole table
            from repro.core.mesh_engine import init_mesh_state
            state = init_mesh_state(backend, self.n_nodes, cap, words, mcap)
        self.state = (state if state is not None
                      else bb.init_state(self.n_nodes, cap, words, mcap))
        self._mesh_ops: Dict[bb.ExchangeConfig, Tuple] = {}
        self._mesh_migrate: Dict[bb.ExchangeConfig, object] = {}
        self._mesh_probe: Dict[bb.ExchangeConfig, object] = {}
        self.ragged = bool(ragged)
        self.two_phase = bool(two_phase) and self.ragged
        # ppermute segmented plans rotate the device ring, so they need
        # nodes 1:1 with mesh devices; otherwise only the padded plan runs
        self._ppermute_ok = (self._is_mesh and
                             dict(backend.shape).get("node") == self.n_nodes)
        # telemetry-seeded ragged presizing: running per-destination
        # high-water budgets per (role, q) — a steady workload converges
        # to ONE spec (one jit specialization) instead of re-planning
        self._spec_floor: Dict[Tuple[str, int], np.ndarray] = {}
        # measured carry-width floor per q (see _carry_hint): same
        # converge-to-one-specialization discipline as _spec_floor
        self._hint_floor: Dict[int, int] = {}
        # suggest_align syncs the device (telemetry snapshot): refresh it
        # every _ALIGN_REFRESH plans instead of per plan
        self._align_state: Dict[int, Tuple[int, int]] = {}
        # ---- online adaptation state (repro.core.adapt) ----
        self.epoch = 0
        self.epoch_log: list = []
        self.fallback: Optional[EpochFallback] = None
        self.telemetry = None
        # write registry: scope_hash → {path_hash: size}; path_hash → writer
        self._files: Dict[int, Dict[int, int]] = {}
        self._writer: Dict[int, int] = {}
        if telemetry:
            from repro.core.adapt.telemetry import ScopeTelemetry
            self.telemetry = ScopeTelemetry(
                self.policy,
                per_node=self.n_nodes if self._is_mesh else 0)

    # ---- request construction ----------------------------------------------
    def _path_codes_uncached(self, path: str) -> Tuple[int, int]:
        """Uncached path → (path_hash, scope_hash) resolution."""
        return str_hash(path), self.policy.scope_hash_of(path)

    def encode(self, paths: Sequence[Sequence[str]],
               chunk_id=None, payload=None, valid=None) -> BBRequest:
        """Hash a (n_nodes, q) nest of path strings into a BBRequest.

        Path and scope hashes are computed once here, at the client
        boundary; everything downstream is integer array routing.  The
        path → (hash, scope-hash) resolution is LRU-memoized per client
        (``self._path_codes``), so steady-state batches over a stable
        working set of paths do no per-path Python FNV loop or prefix
        matching at all.
        """
        rows = [[self._path_codes(p) for p in row] for row in paths]
        # reshape keeps the trailing pair axis even for empty (q=0) rows
        codes = np.asarray(rows, np.int32).reshape(len(rows), -1, 2)
        ph, sh = codes[..., 0], codes[..., 1]
        return BBRequest(
            path_hash=jnp.asarray(ph),
            chunk_id=(None if chunk_id is None else jnp.asarray(
                chunk_id, jnp.int32)),
            payload=None if payload is None else jnp.asarray(payload),
            valid=None if valid is None else jnp.asarray(valid, bool),
            scope_hash=jnp.asarray(sh))

    def _modes(self, req: BBRequest) -> jax.Array:
        """Resolve the per-request mode array for one request batch."""
        if req.mode is not None:
            # the engine specializes its fast paths on the STATIC set
            # policy.modes_present(); an override outside that set would be
            # routed by its mode array but stored/searched by the policy's
            # paths — reject it here rather than silently losing data
            allowed = {int(m) for m in self.policy.modes_present()}
            with obs.span("client.sync.mode", cat="client"):
                got = set(np.unique(np.asarray(req.mode)).tolist())
            if not got <= allowed:
                raise ValueError(
                    f"request modes {sorted(got - allowed)} not in this "
                    f"policy's modes_present() {sorted(allowed)}; add the "
                    "mode to a policy scope (or the default) instead")
            return jnp.asarray(req.mode, jnp.int32)
        if req.scope_hash is not None:
            return self.policy.resolve(req.scope_hash, xp=jnp)
        return self.policy.mode_array(req.path_hash.shape, xp=jnp)

    @staticmethod
    def _valid(req: BBRequest) -> jax.Array:
        """Request-slot mask; all-true when the request omits one."""
        return (jnp.ones(req.path_hash.shape, bool) if req.valid is None
                else req.valid)

    def _chunk_id(self, req: BBRequest) -> jax.Array:
        """Chunk-id array; zeros (metadata convention) when omitted."""
        return (jnp.zeros(req.path_hash.shape, jnp.int32)
                if req.chunk_id is None else req.chunk_id)

    # ---- online adaptation: telemetry, registry, policy epochs --------------
    def _scope_hashes(self, req: BBRequest) -> np.ndarray:
        """Host copy of the request's scope hashes (SCOPE_NONE if absent)."""
        if req.scope_hash is None:
            return np.full(req.path_hash.shape, SCOPE_NONE, np.int32)
        return np.asarray(req.scope_hash)

    def _record_writes(self, req: BBRequest, valid: np.ndarray) -> None:
        """Fold one write batch into the registry (worklists, affinity)."""
        ph = np.asarray(req.path_hash)
        cid = np.asarray(self._chunk_id(req))
        sh = self._scope_hashes(req)
        for i, j in zip(*np.nonzero(valid)):
            p = int(ph[i, j])
            files = self._files.setdefault(int(sh[i, j]), {})
            files[p] = max(files.get(p, 0), int(cid[i, j]) + 1)
            self._writer.setdefault(p, int(i))

    def _self_hint(self, req: BBRequest) -> np.ndarray:
        """Per-request "was written by this row" mask (locality signal)."""
        ph = np.asarray(req.path_hash)
        writer = self._writer
        return np.fromiter(
            (writer.get(int(p)) == i
             for i, row in enumerate(ph) for p in row),
            bool, count=ph.size).reshape(ph.shape)

    def _observe(self, req: BBRequest, kind: str) -> None:
        """Accumulate one call into the per-scope telemetry counters."""
        mode = self._modes(req)
        valid = self._valid(req)
        ph, cid = req.path_hash, self._chunk_id(req)
        ranks = self._client_ranks()
        if kind == "meta":
            dest = route_meta(mode, self.n_nodes, self.policy.n_md_servers,
                              ph, ranks, xp=jnp)
        else:
            dest = route_data(mode, self.n_nodes, ph, cid, ranks, xp=jnp)
        hint = None
        if kind == "read":
            hint = jnp.asarray(self._self_hint(req))
        if kind == "write":
            self._record_writes(req, np.asarray(valid))
        self.telemetry.record(
            kind, req.scope_hash, ph, cid, dest, valid,
            words=0 if kind == "meta" else self.words, self_hint=hint,
            n_nodes=self.n_nodes, capacity=self.exchange_config.capacity)

    def scope_files(self, scope: str) -> Dict[int, int]:
        """Registry view of one scope: {path_hash: size-in-chunks}.

        Everything this client has routed into ``scope`` since
        construction (requires ``telemetry=True`` for the registry to be
        meaningful) — the ``LiveMigrator``'s worklist source.
        """
        return dict(self._files.get(str_hash(scope.rstrip("/") or "/"),
                                    {}))

    def writer_of(self, path_hash: int) -> Optional[int]:
        """Registry view: the first rank that wrote ``path_hash`` (or
        None).  Migration installments writer-align worklist rows so the
        old epoch's metadata is reachable under every mode — Mode-1
        entries only exist on the writer's node."""
        return self._writer.get(int(path_hash))

    def install_policy(self, policy, *, migrating: Optional[str] = None,
                       old_mode: Optional[int] = None,
                       new_mode: Optional[int] = None) -> "BBClient":
        """Swap the layout plan mid-run — one policy epoch.

        With ``migrating`` (a scope name) the dual-epoch fallback is
        armed: read/stat misses of that scope are re-issued under
        ``old_mode`` until the next ``install_policy`` (normally the
        ``LiveMigrator.finish()`` call) disarms it.  Scope-string caches
        are invalidated; telemetry rows follow the new scope set.
        """
        policy = as_policy(policy)
        if policy.n_nodes != self.n_nodes:
            raise ValueError(
                f"policy n_nodes {policy.n_nodes} != client {self.n_nodes}"
                " — a node-count change is a re-deployment, not an epoch")
        self.policy = policy
        self.epoch += 1
        self._path_codes.cache_clear()
        self._mesh_ops.clear()          # mesh ops close over the policy
        self._mesh_migrate.clear()
        self._mesh_probe.clear()
        self._spec_floor.clear()        # routing changed; floors are stale
        self._hint_floor.clear()
        self._align_state.clear()
        self._foot_cache.clear()        # budgets key on the policy
        self.fallback = (None if migrating is None else
                         EpochFallback(str_hash(migrating), int(old_mode)))
        if self.telemetry is not None:
            self.telemetry.rebind(policy)
        from repro.core.adapt.migrate import PolicyEpoch
        self.epoch_log.append(PolicyEpoch(
            self.epoch, policy, migrating,
            None if old_mode is None else LayoutMode(old_mode),
            None if new_mode is None else LayoutMode(new_mode)))
        if self.obs is not None:
            self.obs.metrics.set_gauge("policy_epoch", float(self.epoch))
            self.obs.audit.record(
                "policy_epoch", f"epoch-{self.epoch}",
                inputs={"migrating": migrating,
                        "old_mode": None if old_mode is None
                        else int(old_mode),
                        "new_mode": None if new_mode is None
                        else int(new_mode)},
                evidence={"grade": "runtime", "source": "install_policy"})
        return self

    def _migrate_config(self) -> bb.ExchangeConfig:
        """Exchange config for relayout calls: uniform and lossless.

        Ragged specs are sized for ONE destination pattern, but
        ``migrate_rows`` routes the same worklist under two mode arrays —
        so migration always uses uniform budgets with the carry round
        (or the dense oracle when the client is pinned dense).
        """
        if self.exchange_mode == "dense":
            return bb.DENSE
        return dataclasses.replace(self.exchange_config, kind="compacted",
                                   data_spec=None, meta_spec=None,
                                   lossless=True)

    def migrate_rows(self, path_hash, chunk_id, valid, *, old_mode: int,
                     new_mode: int) -> Tuple[jax.Array, jax.Array]:
        """One relayout installment: move chunks old-mode → new-mode.

        Thin jitted dispatch over ``burst_buffer.migrate_rows`` (stacked)
        or ``mesh_engine.build_mesh_migrate`` (mesh); drive it through a
        ``LiveMigrator`` rather than directly.  Returns (moved,
        found_old) masks.
        """
        allowed = {int(m) for m in self.policy.modes_present()}
        if not {int(old_mode), int(new_mode)} <= allowed:
            raise ValueError(
                f"migration modes ({old_mode}, {new_mode}) must be in the "
                f"installed policy's modes_present() {sorted(allowed)}; "
                "install the transition policy first")
        shape = path_hash.shape
        old = jnp.full(shape, int(old_mode), jnp.int32)
        new = jnp.full(shape, int(new_mode), jnp.int32)
        cfg = self._migrate_config()
        if self._is_mesh:
            op = self._mesh_migrate.get(cfg)
            if op is None:
                from repro.core.mesh_engine import build_mesh_migrate
                op = build_mesh_migrate(self.backend, self.policy, cfg)
                self._cache_put(self._mesh_migrate, cfg, op)
        else:
            op = _stacked_migrate_for(self.policy.engine_key(), cfg)
        with obs.activate(self.obs), \
                obs.span("client.migrate", cat="client",
                         old_mode=int(old_mode), new_mode=int(new_mode)) as h:
            with obs.span("client.dispatch", cat="client"):
                out = op(self.state, jnp.asarray(path_hash),
                         jnp.asarray(chunk_id, jnp.int32),
                         jnp.asarray(valid, bool), old, new)
            self.state, moved, found_old = h.fence(out)
        if self.obs is not None:
            m = self.obs.metrics
            m.inc("migrate_calls_total", epoch=self.epoch)
            m.inc("migrate_moved_total", float(np.asarray(moved).sum()))
        return moved, found_old

    # ---- per-call exchange dispatch -----------------------------------------
    def _select_kind(self, q: int) -> str:
        """Exchange kind for one call: fixed, or the measured crossover."""
        if self.exchange_mode != "auto":
            return self.exchange_mode
        kind = self._pick_cache.get(q)
        if kind is None:
            kind = exchange_select.pick_backend(self.n_nodes, q, self.words)
            self._pick_cache[q] = kind
        elif self.obs is not None:
            self.obs.metrics.inc("exchange_pick_cache_hits_total", kind=kind)
        return kind

    def _client_ranks(self) -> jax.Array:
        return jnp.arange(self.n_nodes, dtype=jnp.int32)[:, None]

    def _plan_spec(self, role: str, dest, valid, row_bytes: int):
        """Measure one call's ragged spec, with convergent presizing.

        The measured per-destination budgets are maxed into a running
        per-(role, q) floor that seeds every later plan — so a steady
        workload's specs grow monotonically to a fixed point (ONE jit
        specialization) instead of re-planning per hashed batch.  When
        telemetry rides the client, its live extent histogram picks the
        quantization step (``suggest_align``), seeding the convergence
        coarser for large steady workloads.  Mesh backends plan a
        ``MeshRaggedSpec`` (padded vs ppermute picked from the measured
        fabric model via ``row_bytes`` per exchanged column).
        """
        key = (role, dest.shape[1])
        floor = self._spec_floor.get(key)
        align = self._suggest_align(dest.shape[1])
        if self._is_mesh:
            spec = bb.plan_mesh_ragged_spec(
                dest, valid, self.n_nodes, align=align,
                row_bytes=row_bytes, allow_ppermute=self._ppermute_ok,
                floor=floor)
        else:
            spec = bb.plan_ragged_spec(dest, valid, self.n_nodes,
                                       align=align, floor=floor)
        budgets = np.asarray(spec.budgets, np.int64)
        if floor is None:
            grew, new_floor = True, budgets
        else:
            grew = bool((budgets > floor).any())
            new_floor = np.maximum(floor, budgets) if grew else floor
        if grew and self.obs is not None:
            # a grown floor means a new spec → a fresh jit specialization
            self.obs.metrics.inc("ragged_respecializations_total", role=role)
        self._spec_floor[key] = new_floor
        return spec

    #: plans between telemetry re-reads of the align hint (each re-read
    #: snapshots the counter array: a device sync worth amortizing)
    _ALIGN_REFRESH = 32

    def _suggest_align(self, q: int) -> int:
        """Cached quantization hint (see ``ScopeTelemetry.suggest_align``).

        The hint changes at most a handful of times over a run, while
        ``suggest_align`` itself costs a device→host counter snapshot —
        so the live value is re-read only every ``_ALIGN_REFRESH`` plans
        per batch width.
        """
        if self.telemetry is None:
            return 8
        align, left = self._align_state.get(q, (None, 0))
        if align is None or left <= 0:
            with obs.span("client.sync.align", cat="client"):
                align = self.telemetry.suggest_align(q)
            left = self._ALIGN_REFRESH
        self._align_state[q] = (align, left - 1)
        return align

    @staticmethod
    def _cache_put(cache: Dict, key, value, cap: int = 64) -> None:
        """Insert with FIFO eviction — mesh op caches hold compiled
        shard_map executables and must not grow with drifting traffic."""
        if len(cache) >= cap:
            cache.pop(next(iter(cache)))
        cache[key] = value

    def _call_config(self, op: str, mode, ph, cid, valid,
                     data_loc=None) -> bb.ExchangeConfig:
        """The exchange config for one call — including measured ragged
        specs when this call is eligible (no explicit budget override,
        destinations computable without table state).  ``data_loc`` is
        the two-phase hybrid read's probed data-location array: with it,
        read destinations ARE computable here and the data round gets a
        measured plan; without it a hybrid read keeps the uniform
        lossless plan for the whole call.  Runs in a ``client.plan``
        span, routing in ``client.route``."""
        with obs.span("client.plan", cat="client"):
            q = ph.shape[1]
            kind = self._select_kind(q)
            if kind == "dense":
                return bb.DENSE
            cfg = self.exchange_config
            if cfg.kind != "compacted":
                cfg = dataclasses.replace(cfg, kind="compacted")
            if not self.ragged or q == 0:
                return cfg
            N, client = self.n_nodes, self._client_ranks()
            if op in ("write", "read") and cfg.budget is None:
                if op == "read" and data_loc is None and \
                        LayoutMode.HYBRID in self.policy.modes_present():
                    # hybrid read destinations come from the metadata phase
                    # (table state), which is invisible here — the two-phase
                    # path probes first and calls back in with data_loc
                    return cfg
                with obs.span("client.route", cat="client"):
                    dest = route_data(mode, N, ph, cid, client,
                                      data_loc=data_loc, xp=jnp)
                cfg = dataclasses.replace(
                    cfg, data_spec=self._plan_spec(
                        "data", dest, valid, 4 * (self.words + 3)))
            if op in ("write", "meta") and cfg.meta_budget is None and \
                    cfg.budget is None:
                # an explicit ``budget`` historically also caps the metadata
                # exchange (see ``meta_budget``) — honour it rather than
                # silently upgrading metadata to ragged sizing
                with obs.span("client.route", cat="client"):
                    owner = route_meta(mode, N, self.policy.n_md_servers, ph,
                                       client, xp=jnp)
                cfg = dataclasses.replace(
                    cfg, meta_spec=self._plan_spec("meta", owner, valid,
                                                   4 * 8))
            if cfg.pipeline and cfg.lossless and cfg.budget is not None:
                # explicit uniform budgets skip ragged sizing, but the carry
                # round need not pay the worst-case q − B width: measure the
                # actual overflow histogram and cap the carry at the observed
                # residual (same eager measurement the specs do)
                hint = self._carry_hint(op, mode, ph, cid, valid, data_loc, q,
                                        cfg)
                if hint is not None:
                    cfg = dataclasses.replace(cfg, carry_budget_hint=hint)
            return cfg

    def _carry_hint(self, op: str, mode, ph, cid, valid, data_loc,
                    q: int, cfg: bb.ExchangeConfig) -> Optional[int]:
        """Measured worst per-(row, destination) round-1 residual.

        Every overflowable plane of this call (data at ``B_d``, metadata
        at ``B_m``) contributes ``max(count − B, 0)`` over its measured
        destination histogram; the maximum — quantized up to 8 and maxed
        into a running per-q floor so steady traffic converges to ONE
        jit specialization — upper-bounds the residual of either plane,
        so capping the carry at it preserves losslessness.  ``None``
        means no hint applies (destinations unknowable, or no plane can
        overflow).
        """
        policy, N = self.policy, self.n_nodes
        # budgets before routing: when no plane can overflow (B = q) the
        # carry is already statically elided, and the hot write path must
        # not pay eager destination routing just to discard it
        b_d = bb.data_budget(policy, q, cfg)
        b_m = bb.meta_budget(policy, q, cfg)
        data = op in ("write", "read") and b_d < q
        meta = op in ("write", "meta") and b_m < q
        if data and op == "read" and data_loc is None and \
                LayoutMode.HYBRID in policy.modes_present():
            return None            # destinations live in table state
        if not (data or meta):
            return None            # B = q everywhere: carry already elided
        # host-side measurement (numpy routing, like the spec planners):
        # this sits on the hot request path, so it reads the call's arrays
        # to the host once and dispatches no device work
        with obs.span("client.sync.carry_hint", cat="client"):
            mode_h, ph_h, cid_h, loc_h, v = jax.device_get(
                (mode, ph, cid if data else None,
                 data_loc if data else None, valid))
        ranks = np.arange(N, dtype=np.int32)[:, None]
        planes = []
        if data:
            planes.append((route_data(mode_h, N, ph_h, cid_h, ranks,
                                      data_loc=loc_h, xp=np), b_d))
        if meta:
            planes.append((route_meta(mode_h, N, policy.n_md_servers,
                                      ph_h, ranks, xp=np), b_m))
        v = np.asarray(v)
        worst = 0
        for dest, b in planes:
            d = np.asarray(dest)
            for i in range(d.shape[0]):
                counts = np.bincount(d[i][v[i]], minlength=N)
                worst = max(worst, int(counts.max(initial=0)) - b)
        hint = 0 if worst <= 0 else min(q, -(-worst // 8) * 8)
        floor = self._hint_floor.get(q)
        if floor is None or hint > floor:
            if floor is not None and self.obs is not None:
                self.obs.metrics.inc("carry_hint_respecializations_total")
            self._hint_floor[q] = floor = hint
        return floor

    def _ops(self, config: bb.ExchangeConfig) -> bb.EngineOps:
        """(write, read, meta, read_loc) jitted ops for one config; they
        keep the state they are given (``owned`` holds the donating
        twins)."""
        if not self._is_mesh:
            return _stacked_ops_for(self.policy.engine_key(), config)
        ops = self._mesh_ops.get(config)
        if ops is None:
            from repro.core.mesh_engine import build_mesh_ops
            ops = build_mesh_ops(self.backend, self.policy, config)
            self._cache_put(self._mesh_ops, config, ops)
        return ops

    def _ops_for(self, config: bb.ExchangeConfig, owned: bool) -> Tuple:
        """The ops a call runs: the donating ``owned`` twins when the call
        owns the state it passes (it rebinds ``self.state`` to the
        result), else ``_ops(config)``.  A stand-in for ``_ops`` that
        returns a plain tuple (an instrumenting wrapper) has no twins, and
        its ops run as given."""
        ops = self._ops(config)
        return getattr(ops, "owned", ops) if owned else ops

    # ---- engine entries -----------------------------------------------------
    # One code path per op: ``_<op>_in`` plans and dispatches inside the
    # open ``client.<op>`` span.  The public calls open that span around
    # request resolution too, and pass ``owned=True``: they hand
    # ``self.state`` over and rebind it.  ``_write`` / ``_read`` / ``_meta``
    # take the state and resolved arrays explicitly (the benchmarks drive
    # them) and leave the caller's state alive.
    def _write(self, state, mode, ph, cid, payload, valid):
        """Engine write entry (state explicit — the benchmarks drive it)."""
        with obs.activate(self.obs), \
                obs.span("client.write", cat="client",
                         q=int(ph.shape[1])) as h:
            return h.fence(self._write_in(state, mode, ph, cid, payload,
                                          valid))

    def _write_in(self, state, mode, ph, cid, payload, valid, owned=False):
        """Plan and dispatch one write (donating ``state`` if ``owned``)."""
        cfg = self._call_config("write", mode, ph, cid, valid)
        with obs.span("client.dispatch", cat="client"):
            out = self._ops_for(cfg, owned)[0](state, mode, ph, cid,
                                                payload, valid)
        if self.obs is not None:
            self._account("write", cfg, ph.shape[1], out, mode, ph, cid,
                          valid)
        return out

    def _read(self, state, mode, ph, cid, valid):
        """Engine read entry (state explicit — the benchmarks drive it)."""
        with obs.activate(self.obs), \
                obs.span("client.read", cat="client",
                         q=int(ph.shape[1])) as h:
            return h.fence(self._read_in(state, mode, ph, cid, valid))

    def _read_in(self, state, mode, ph, cid, valid):
        """Plan and dispatch one read.

        Hybrid-capable ragged reads go two-phase: the metadata probe runs
        as its own jitted call, the resolved data locations size a
        measured ragged plan, and the data round runs with the engine's
        internal meta phase skipped — identical answers (the probe IS the
        same ``meta_op`` STAT), measured instead of worst-case budgets.
        """
        q = ph.shape[1]
        if (self.two_phase and q > 0 and
                LayoutMode.HYBRID in self.policy.modes_present() and
                self.exchange_config.budget is None and
                self._select_kind(q) == "compacted"):
            return self._read_two_phase(state, mode, ph, cid, valid)
        cfg = self._call_config("read", mode, ph, cid, valid)
        with obs.span("client.dispatch", cat="client"):
            out = self._ops(cfg)[1](state, mode, ph, cid, valid)
        if self.obs is not None:
            self._account("read", cfg, q, None, mode, ph, cid, valid)
        return out

    def _read_two_phase(self, state, mode, ph, cid, valid):
        """Metadata probe → ragged data round (see ``_read_in``)."""
        shape = ph.shape
        with obs.span("client.read.probe", cat="client") as h:
            ranks = jnp.broadcast_to(self._client_ranks(), shape)
            probe_valid = self._as_bool(valid) & (mode == LayoutMode.HYBRID)
            with obs.span("client.sync.probe_mask", cat="client"):
                any_hybrid = bool(np.any(np.asarray(probe_valid)))
            if not any_hybrid:
                # no hybrid rows in THIS batch (e.g. an epoch-fallback
                # re-read under a hashed old mode): skip the probe round —
                # every data destination resolves without table state
                data_loc = ranks
            else:
                cfg_m = self._call_config("meta", mode, ph, None,
                                          probe_valid)
                with obs.span("client.dispatch", cat="client"):
                    fm, loc = h.fence(self._probe_op(cfg_m)(
                        state, mode, ph, probe_valid))
                if self.obs is not None:
                    self._account("meta", cfg_m, shape[1], None, mode, ph,
                                  None, probe_valid)
                data_loc = jnp.where(fm & (loc >= 0), loc, ranks)
        with obs.span("client.read.data", cat="client") as h:
            cfg = self._call_config("read", mode, ph, cid, valid,
                                    data_loc=data_loc)
            with obs.span("client.dispatch", cat="client"):
                out = h.fence(self._ops(cfg)[3](state, mode, ph, cid, valid,
                                                data_loc))
        if self.obs is not None:
            self._account("read", cfg, shape[1], None, mode, ph, cid, valid)
        return out

    def _probe_op(self, config: bb.ExchangeConfig):
        """The (found, loc)-only STAT op for one config (both backends)."""
        if not self._is_mesh:
            return _stacked_probe_for(self.policy.engine_key(), config)
        op = self._mesh_probe.get(config)
        if op is None:
            from repro.core.mesh_engine import build_mesh_probe
            op = build_mesh_probe(self.backend, self.policy, config)
            self._cache_put(self._mesh_probe, config, op)
        return op

    @staticmethod
    def _as_bool(valid) -> jax.Array:
        """Request mask as a bool array (callers may pass int masks)."""
        return jnp.asarray(valid, bool)

    def _meta(self, state, mode, op, ph, size, loc, valid):
        """Engine metadata entry (state explicit)."""
        with obs.activate(self.obs), \
                obs.span("client.meta", cat="client",
                         q=int(ph.shape[1])) as h:
            return h.fence(self._meta_in(state, mode, op, ph, size, loc,
                                         valid))

    def _meta_in(self, state, mode, op, ph, size, loc, valid, owned=False):
        """Plan and dispatch one metadata call (donating ``state`` if
        ``owned``)."""
        cfg = self._call_config("meta", mode, ph, None, valid)
        with obs.span("client.dispatch", cat="client"):
            out = self._ops_for(cfg, owned)[2](state, mode, op, ph, size,
                                                loc, valid)
        if self.obs is not None:
            self._account("meta", cfg, ph.shape[1], out[0], mode, ph, None,
                          valid)
        return out

    # ---- traced-call accounting (tracing on only) ---------------------------
    _FOOT_ELEMS = {"write": "write_elems", "read": "read_elems",
                   "meta": "meta_elems"}

    def _footprint(self, q: int, cfg: bb.ExchangeConfig) -> Dict[str, int]:
        """Memoized ``exchange_footprint`` of one (q, config) pair."""
        key = (q, cfg)
        foot = self._foot_cache.get(key)
        if foot is None:
            foot = bb.exchange_footprint(self.policy, q, self.words, cfg)
            self._cache_put(self._foot_cache, key, foot, cap=256)
        return foot

    def _account(self, op: str, cfg: bb.ExchangeConfig, q: int, state_out,
                 mode, ph, cid, valid) -> None:
        """Metrics for one engine call: op mix, modeled exchange bytes,
        executor-reported drop accounting and the carry-round rate.

        ``exchange_bytes_total{op}`` increments by exactly the modeled
        footprint of the config the call ran under (4 bytes per int32
        element — the same arithmetic the benchmarks report), and
        ``exchange_dropped_rows`` mirrors the engine's own cumulative
        ``state.dropped`` counter, so snapshot totals reconcile against
        executor-reported accounting by construction.  For uniform
        lossless under-budget plans the host mirrors the executor's
        per-(row, destination) overflow count to expose the carry-round
        rate jit's cond-gating hides.
        """
        m = self.obs.metrics
        foot = self._footprint(q, cfg)
        m.inc("client_ops_total", op=op, kind=foot["kind"],
              epoch=self.epoch)
        m.inc("exchange_bytes_total", 4 * foot[self._FOOT_ELEMS[op]], op=op)
        if state_out is not None:
            m.set_gauge("exchange_dropped_rows",
                        float(np.asarray(state_out.dropped).sum()))
        if foot["kind"] != "compacted" or not cfg.lossless:
            return
        # the carry mirror only applies to uniform under-budget plans —
        # with ragged per-call specs (the default) neither branch fires,
        # so the host routing replay is built strictly on demand
        if op in ("write", "read") and cfg.data_spec is None and \
                foot["data_budget"] < q:
            ranks = np.arange(self.n_nodes, dtype=np.int64)[:, None]
            dest = route_data(np.asarray(mode), self.n_nodes,
                              np.asarray(ph), np.asarray(cid), ranks,
                              xp=np)
            self._carry_metrics(dest, valid, foot["data_budget"], "data")
        elif op == "meta" and cfg.meta_spec is None and \
                foot["meta_budget"] < q:
            ranks = np.arange(self.n_nodes, dtype=np.int64)[:, None]
            owner = route_meta(np.asarray(mode), self.n_nodes,
                               self.policy.n_md_servers, np.asarray(ph),
                               ranks, xp=np)
            self._carry_metrics(owner, valid, foot["meta_budget"], "meta")

    def _carry_metrics(self, dest: np.ndarray, valid, budget: int,
                       plane: str) -> None:
        """Host mirror of the executor's budget-overflow accounting.

        Counts, per source row, the requests beyond the per-destination
        budget — the same quantity ``ExchangePlan.overflow`` sums and
        ``_carry_taken`` gates the carry round on — and feeds the
        carry-rate counters and the overflow-pressure histogram.
        """
        v = np.asarray(valid).astype(bool)
        over = 0
        for row in range(dest.shape[0]):
            c = np.bincount(np.asarray(dest[row])[v[row]],
                            minlength=self.n_nodes)
            over += int(np.clip(c - budget, 0, None).sum())
        m = self.obs.metrics
        m.inc("carry_eligible_total", plane=plane)
        m.observe("carry_overflow_rows", over, plane=plane)
        if over > 0:
            m.inc("carry_rounds_total", plane=plane)

    # ---- data plane ---------------------------------------------------------
    def write(self, req: BBRequest) -> "BBClient":
        """Write a batch of chunks; mutates the held state, returns self."""
        assert req.payload is not None, "write requires req.payload"
        ph = req.path_hash
        with obs.activate(self.obs), \
                obs.span("client.write", cat="client",
                         q=int(ph.shape[1])) as h:
            if self.telemetry is not None:
                self._observe(req, "write")
            with obs.span("client.resolve", cat="client"):
                mode, cid, valid = (self._modes(req), self._chunk_id(req),
                                    self._valid(req))
            self.state = h.fence(self._write_in(self.state, mode, ph, cid,
                                                req.payload, valid,
                                                owned=True))
        return self

    def read(self, req: BBRequest) -> Tuple[jax.Array, jax.Array]:
        """Read a batch of chunks → (payload (L, q, w), found (L, q)).

        During a live relayout (``fallback`` armed), misses of the
        migrating scope are re-issued under the old mode — a chunk the
        watermark hasn't reached yet is served from its old placement.
        """
        ph = req.path_hash
        with obs.activate(self.obs), \
                obs.span("client.read", cat="client",
                         q=int(ph.shape[1])) as h:
            if self.telemetry is not None:
                self._observe(req, "read")
            with obs.span("client.resolve", cat="client"):
                mode, cid, valid = (self._modes(req), self._chunk_id(req),
                                    self._valid(req))
            payload, found = self._read_in(self.state, mode, ph, cid, valid)
            fb = self.fallback
            if fb is not None and req.scope_hash is not None:
                with obs.span("client.sync.fallback", cat="client"):
                    miss = (np.asarray(valid) & ~np.asarray(found) &
                            (self._scope_hashes(req) == fb.scope_hash))
                if miss.any():
                    old = jnp.full(ph.shape, fb.old_mode, jnp.int32)
                    p2, f2 = self._read_in(self.state, old, ph, cid,
                                           jnp.asarray(miss))
                    payload = jnp.where(f2[..., None], p2, payload)
                    found = jnp.logical_or(found, f2)
            return h.fence((payload, found))

    # ---- metadata plane -----------------------------------------------------
    def _meta_call(self, opcode: int, req: BBRequest, mode=None, valid=None):
        """Shared create/stat/remove plumbing: fill defaults, run, unpack.

        ``mode``/``valid`` override the request's resolution — the
        dual-epoch retries pass the old-mode array with a miss mask."""
        ph = req.path_hash
        shape = ph.shape
        with obs.activate(self.obs), \
                obs.span("client.meta", cat="client", q=int(shape[1])) as h:
            if mode is None and self.telemetry is not None:
                self._observe(req, "meta")
            with obs.span("client.resolve", cat="client"):
                op = jnp.full(shape, opcode, jnp.int32)
                size = (jnp.zeros(shape, jnp.int32) if req.size is None
                        else jnp.asarray(req.size, jnp.int32))
                loc = (jnp.full(shape, -1, jnp.int32) if req.loc is None
                       else jnp.asarray(req.loc, jnp.int32))
                mode = self._modes(req) if mode is None else mode
                valid = self._valid(req) if valid is None else valid
            self.state, found, r_size, r_loc = h.fence(self._meta_in(
                self.state, mode, op, ph, size, loc, valid, owned=True))
        return found, r_size, r_loc

    def _epoch_rows(self, req: BBRequest) -> Optional[np.ndarray]:
        """Valid rows of the migrating scope (None if there is none)."""
        fb = self.fallback
        if fb is None or req.scope_hash is None:
            return None
        with obs.span("client.sync.fallback", cat="client"):
            rows = (np.asarray(self._valid(req)) &
                    (self._scope_hashes(req) == fb.scope_hash))
        return rows if rows.any() else None

    def create(self, req: BBRequest) -> jax.Array:
        """Create file entries (idempotent) → found mask."""
        found, _, _ = self._meta_call(bb.OP_CREATE, req)
        return found

    def stat(self, req: BBRequest) -> Tuple[jax.Array, jax.Array, jax.Array]:
        """Stat file entries → (found, size, data_location_rank).

        Dual-epoch during a relayout: entries of the migrating scope are
        also stat'ed under the old mode, so a file the watermark hasn't
        reached is still served by its old-mode owner.  A file written
        during the relayout has an entry in each epoch until its
        installment merges them; its size is the larger of the two (sizes
        only grow), its location the new epoch's."""
        found, size, loc = self._meta_call(bb.OP_STAT, req)
        rows = self._epoch_rows(req)
        if rows is not None:
            old = jnp.full(req.path_hash.shape, self.fallback.old_mode,
                           jnp.int32)
            f2, s2, l2 = self._meta_call(bb.OP_STAT, req, mode=old,
                                         valid=jnp.asarray(rows))
            loc = jnp.where(f2 & ~found, l2, loc)
            size = jnp.where(f2, jnp.maximum(size, s2), size)
            found = jnp.logical_or(found, f2)
        return found, size, loc

    def remove(self, req: BBRequest) -> jax.Array:
        """Remove file entries (record fully cleared) → found mask.

        During a relayout the remove is issued under BOTH epochs for the
        migrating scope, so a not-yet-migrated old-owner entry cannot
        resurface through the dual-epoch stat fallback."""
        found, _, _ = self._meta_call(bb.OP_REMOVE, req)
        if self.telemetry is not None:
            # prune the registry so later migration worklists skip the file
            v = np.asarray(self._valid(req))
            ph, sh = np.asarray(req.path_hash), self._scope_hashes(req)
            for i, j in zip(*np.nonzero(v)):
                self._files.get(int(sh[i, j]), {}).pop(int(ph[i, j]), None)
        rows = self._epoch_rows(req)
        if rows is not None:
            old = jnp.full(req.path_hash.shape, self.fallback.old_mode,
                           jnp.int32)
            f2, _, _ = self._meta_call(bb.OP_REMOVE, req, mode=old,
                                       valid=jnp.asarray(rows))
            found = jnp.logical_or(found, f2)
        return found
