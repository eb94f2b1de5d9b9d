"""shard_map deployment of the burst-buffer engine on a device mesh.

The stacked engine (burst_buffer.py) runs unchanged per-chip under
``shard_map``: the node axis is sharded over the devices, each holding
``N / n_devices`` node rows, global ranks come from ``axis_index`` and the
exchange becomes ``jax.lax.all_to_all`` over the ``node`` axis.  This is
the production data plane behind the mesh backend of ``BBClient``
(client.py) — construct ``BBClient(policy, mesh)`` rather than calling
``build_mesh_ops`` directly.

Ragged plans on the mesh: a packed :class:`~repro.core.exchange_plan.
RaggedSpec` cannot cross ``all_to_all`` (uniform splits) and is rejected
here, but a measured :class:`~repro.core.exchange_plan.MeshRaggedSpec`
can: it splits every exchange in two, rows for a node on the same chip
move on the chip, and only rows for other chips cross the fabric,
compacted per chip pair — in one ``all_to_all`` over the chips
(``mesh_exchange`` on a (1, chips, width) buffer) in its "padded" form,
or in one :func:`build_mesh_shift` ``lax.ppermute`` per chip offset with
traffic in its "ppermute" form.  Any number of node rows per chip.

Migration note: the pre-policy ``make_mesh_ops(mesh, params)`` entry point is
gone.  ``build_mesh_ops(mesh, policy)`` returns ops that additionally take
the per-request ``mode`` array as their second argument, which is how a
heterogeneous ``LayoutPolicy`` reaches the routing triplet under shard_map.
"""
from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as PS

from repro.core import burst_buffer as bb
from repro.core import obs
from repro.core.exchange_plan import MeshRaggedSpec, RaggedSpec
from repro.core.policy import LayoutPolicy, as_policy

NODE_AXIS = "node"


def init_mesh_state(mesh: Mesh, n_nodes: int, cap: int, words: int,
                    mcap: int) -> bb.BBState:
    """Fresh node tables created in place, sharded over the node axis.

    ``bb.init_state`` followed by a reshard would first build every table
    on one device; jitting it with node-sharded outputs makes each device
    allocate only its own rows."""
    return jax.jit(functools.partial(bb.init_state, n_nodes, cap, words,
                                     mcap),
                   out_shardings=NamedSharding(mesh, PS(NODE_AXIS)))()


def mesh_exchange(x: jax.Array) -> jax.Array:
    """Per-node (L, N, s, ...) -> (L, N, s, ...) with src/dst swapped globally.

    Slot-count agnostic: ``s`` is q dense slots or the compacted plan's
    per-destination budget B — ``all_to_all`` only touches the (src, dst)
    axes, which is what makes the ragged/compacted buffers exchange through
    the identical wiring as the dense ones.
    """
    y = jax.lax.all_to_all(x, NODE_AXIS, split_axis=1, concat_axis=0,
                           tiled=True)
    # y: (N_src, L_dst, s, ...): source-major over every device's rows.
    # The swap is always due — also on a one-device mesh, where the
    # collective is the identity but the rows still change sides
    return jnp.swapaxes(y, 0, 1)


def build_mesh_shift(n_dev: int) -> Callable:
    """The mesh twin of ``exchange_plan.stacked_shift``: a k-chip rotation.

    Returns ``shift(x, k)`` running ``lax.ppermute`` with the
    ``[(i, (i + k) % n_dev) for i]`` ring permutation over the devices —
    device ``i``'s buffer lands on device ``(i + k) mod n_dev``, what
    ``jnp.roll(x, k, axis=0)`` does to a stacked array of one group per
    chip.  The buffer is a chip's fabric segment for offset ``k``
    (``exchange_plan.PermuteExecutor``), so it holds rows of whichever of
    the chip's node rows send that way: any number of node rows per chip.
    """

    def shift(x: jax.Array, k: int) -> jax.Array:
        perm = [(i, (i + k) % n_dev) for i in range(n_dev)]
        return jax.lax.ppermute(x, NODE_AXIS, perm)

    return shift


def _node_ids(local_n: int) -> jax.Array:
    base = jax.lax.axis_index(NODE_AXIS) * local_n
    return base + jnp.arange(local_n, dtype=jnp.int32)


def mesh_global_sum(x: jax.Array) -> jax.Array:
    """All-node scalar reduction: local sum, then psum over the node axis.

    This is the carry-round predicate reduction (``burst_buffer``'s
    ``global_sum`` hook): every device sees the same total, so the
    ``lax.cond`` around the overflow-carry exchange takes the same branch
    everywhere and the ``all_to_all`` inside it stays aligned.
    """
    return jax.lax.psum(jnp.sum(x), NODE_AXIS)


def _check_specs(config: bb.ExchangeConfig, n_dev: int) -> None:
    """Reject exchange specs the mesh collectives cannot carry."""
    for spec in (config.data_spec, config.meta_spec):
        if isinstance(spec, RaggedSpec):
            raise ValueError(
                "packed ragged exchange specs need a single-device packed "
                "layout; the mesh all_to_all requires uniform splits — "
                "use a MeshRaggedSpec (padded or ppermute plan) or "
                "uniform budgets (the lossless carry round covers "
                "overflow)")
        if isinstance(spec, MeshRaggedSpec) and spec.n_chips != n_dev:
            raise ValueError(
                f"a MeshRaggedSpec planned for {spec.n_chips} chips cannot "
                f"run on a {n_dev}-device mesh")


@obs.trace_span("mesh.build_ops", cat="build")
def build_mesh_ops(mesh: Mesh, policy,
                   config: bb.ExchangeConfig = bb.DENSE) -> bb.EngineOps:
    """Returns jitted (write, read, meta, read_loc) ops bound to a mesh.

    Each op takes the per-request ``mode`` array right after the state
    (matching the stacked ops in client.py); ``read_loc`` additionally
    takes the precomputed ``data_loc`` ranks of the client's two-phase
    hybrid read as its trailing argument.  State and request arrays are
    sharded over the ``node`` axis on their leading dim.  ``config``
    selects the exchange data plane; the planner (exchange_plan.py)
    resolves it per phase, and all transports — dense bucketize, uniform
    all_to_all, the two-level mesh split with its padded chip
    ``all_to_all`` or its chip-ring shifts (software-pipelined when
    ``config.pipeline``) — run through the same
    ``mesh_exchange``/``build_mesh_shift`` collectives.

    The four keep the state they are given; their ``owned`` twins
    (``bb.EngineOps``) donate it to write and meta, so each device's
    table shard is updated in place.  ``BBClient``'s mutating calls run
    the twins and rebind their state; replay loops that reuse a saved
    state run the four.
    """
    policy = as_policy(policy)
    n_dev = mesh.shape[NODE_AXIS]
    assert policy.n_nodes % n_dev == 0
    local_n = policy.n_nodes // n_dev
    req_spec = PS(NODE_AXIS)
    _check_specs(config, n_dev)
    shift = build_mesh_shift(n_dev)

    def _write(state, mode, ph, cid, payload, valid):
        return bb.forward_write(state, policy, ph, cid, payload, valid,
                                mode=mode, exchange=mesh_exchange,
                                node_ids=_node_ids(local_n), config=config,
                                global_sum=mesh_global_sum, shift=shift)

    def _read(state, mode, ph, cid, valid):
        return bb.forward_read(state, policy, ph, cid, valid,
                               mode=mode, exchange=mesh_exchange,
                               node_ids=_node_ids(local_n), config=config,
                               global_sum=mesh_global_sum, shift=shift)

    def _meta(state, mode, op, ph, size, loc, valid):
        return bb.meta_op(state, policy, op, ph, size, loc, valid,
                          mode=mode, exchange=mesh_exchange,
                          node_ids=_node_ids(local_n), config=config,
                          global_sum=mesh_global_sum, shift=shift)

    def _read_loc(state, mode, ph, cid, valid, data_loc):
        return bb.forward_read(state, policy, ph, cid, valid,
                               mode=mode, exchange=mesh_exchange,
                               node_ids=_node_ids(local_n), config=config,
                               global_sum=mesh_global_sum,
                               data_loc=data_loc, shift=shift)

    state_specs = jax.tree_util.tree_map(
        lambda _: PS(NODE_AXIS), bb.init_state(1, 1, 1, 1))

    write = jax.shard_map(
        _write, mesh=mesh,
        in_specs=(state_specs, req_spec, req_spec, req_spec, req_spec,
                  req_spec),
        out_specs=state_specs, check_vma=False)
    read = jax.shard_map(
        _read, mesh=mesh,
        in_specs=(state_specs, req_spec, req_spec, req_spec, req_spec),
        out_specs=(req_spec, req_spec), check_vma=False)
    meta = jax.shard_map(
        _meta, mesh=mesh,
        in_specs=(state_specs, req_spec, req_spec, req_spec, req_spec,
                  req_spec, req_spec),
        out_specs=(state_specs, req_spec, req_spec, req_spec),
        check_vma=False)
    read_loc = jax.shard_map(
        _read_loc, mesh=mesh,
        in_specs=(state_specs, req_spec, req_spec, req_spec, req_spec,
                  req_spec),
        out_specs=(req_spec, req_spec), check_vma=False)
    return bb.jit_engine_ops(write, read, meta, read_loc)


@obs.trace_span("mesh.build_migrate", cat="build")
def build_mesh_migrate(mesh: Mesh, policy,
                       config: bb.ExchangeConfig = bb.COMPACTED):
    """Jitted ``migrate_rows`` bound to a mesh + policy (live relayout).

    Kept separate from ``build_mesh_ops`` so existing tuple callers are
    untouched; the returned op takes
    ``(state, ph, cid, valid, old_mode, new_mode)`` with every request
    array sharded over the node axis, and runs the same old-fetch →
    probe → copy → meta-move → tombstone sequence as the stacked
    backend, with the carry-round predicate psum-reduced so every device
    takes the same cond branch.  The state is donated: the one caller,
    ``BBClient.migrate_rows``, rebinds its state to the result.
    """
    policy = as_policy(policy)
    n_dev = mesh.shape[NODE_AXIS]
    assert policy.n_nodes % n_dev == 0
    local_n = policy.n_nodes // n_dev
    req_spec = PS(NODE_AXIS)
    shift = build_mesh_shift(n_dev)

    def _migrate(state, ph, cid, valid, old_mode, new_mode):
        state, moved, found_old = bb.migrate_rows(
            state, policy, ph, cid, valid, old_mode, new_mode,
            exchange=mesh_exchange, node_ids=_node_ids(local_n),
            config=config, global_sum=mesh_global_sum, shift=shift)
        return state, moved, found_old

    state_specs = jax.tree_util.tree_map(
        lambda _: PS(NODE_AXIS), bb.init_state(1, 1, 1, 1))
    return jax.jit(jax.shard_map(
        _migrate, mesh=mesh,
        in_specs=(state_specs, req_spec, req_spec, req_spec, req_spec,
                  req_spec),
        out_specs=(state_specs, req_spec, req_spec), check_vma=False),
        donate_argnums=0)


@obs.trace_span("mesh.build_probe", cat="build")
def build_mesh_probe(mesh: Mesh, policy,
                     config: bb.ExchangeConfig = bb.DENSE):
    """Jitted hybrid-read probe op: STAT → (found, loc) ONLY.

    The mesh twin of the client's stacked probe — returning just the two
    reply arrays lets XLA drop the post-STAT state outputs instead of
    materializing a copy of every sharded table per read (the two-phase
    read issues one of these per call).
    """
    policy = as_policy(policy)
    n_dev = mesh.shape[NODE_AXIS]
    assert policy.n_nodes % n_dev == 0
    local_n = policy.n_nodes // n_dev
    req_spec = PS(NODE_AXIS)
    _check_specs(config, n_dev)
    shift = build_mesh_shift(n_dev)

    def _probe(state, mode, ph, valid):
        shape = ph.shape
        op = jnp.full(shape, bb.OP_STAT, jnp.int32)
        _, found, _, loc = bb.meta_op(
            state, policy, op, ph, jnp.zeros(shape, jnp.int32),
            jnp.full(shape, -1, jnp.int32), valid, mode=mode,
            exchange=mesh_exchange, node_ids=_node_ids(local_n),
            config=config, global_sum=mesh_global_sum, shift=shift)
        return found, loc

    state_specs = jax.tree_util.tree_map(
        lambda _: PS(NODE_AXIS), bb.init_state(1, 1, 1, 1))
    return jax.jit(jax.shard_map(
        _probe, mesh=mesh,
        in_specs=(state_specs, req_spec, req_spec, req_spec),
        out_specs=(req_spec, req_spec), check_vma=False))


def build_telemetry_reduce(mesh: Mesh):
    """Jitted mesh-wide reduction of per-node telemetry counters.

    Takes a ``(n_nodes, n_scopes, n_features)`` counter array sharded
    over the node axis (``ScopeTelemetry(per_node=...)``) and returns the
    ``(n_scopes, n_features)`` global sum *replicated on every device* —
    each host computes the fleet-wide scope signatures from its own shard
    plus one ``psum``, so drift detection can fire from any host instead
    of only the driving client (see ``adapt.telemetry``).
    """

    def _reduce(counts):
        return jax.lax.psum(jnp.sum(counts, axis=0), NODE_AXIS)

    return jax.jit(jax.shard_map(
        _reduce, mesh=mesh, in_specs=PS(NODE_AXIS), out_specs=PS(),
        check_vma=False))


def make_node_mesh(n_devices: int = None) -> Mesh:
    """1-D device mesh over the node axis (default: all devices)."""
    devs = jax.devices()
    n = n_devices or len(devs)
    return jax.make_mesh((n,), (NODE_AXIS,))
