"""Pipelined-exchange benchmark: sync vs software-pipelined rounds, and
serial vs fused write round-trips, next to the fabric model's floor.

Two sections land in ``BENCH_pr10.json`` (``make bench-pipeline``):

* ``overlap`` — the multi-round transports in isolation.  Each cell
  spawns a subprocess that forces ``n`` host devices, measures the
  fabric (``all_to_all`` timings → same-run affine fit, the only honest
  model to bound a run on the same box), then times the data-plane write
  (``forward_write(update_meta=False)``) with ``config.pipeline`` off
  and on over the SAME traffic:

  - ``ppermute`` path: hashed traffic through a forced-``ppermute``
    :class:`~repro.core.exchange_plan.MeshRaggedSpec` — the N−1 shift
    rounds the software pipeline double-buffers;
  - ``carry`` path: incast traffic at a uniform ``B = q/2`` budget — the
    cond-gated lossless carry round whose plan the pipeline hoists out
    of the cond; timed through ``run_exchange`` with a trivial reducing
    apply so the cell prices the same thing the bound does (the two
    collectives), not the receiver's incast table scatter.

  ``lower_bound_us`` is the fitted fabric model's cost of the cell's
  collective sequence ALONE (Σ per-round ``collective_us`` over the
  bytes each round ships, zero gather/apply) — the fabric-busy floor no
  amount of overlap can beat.  ``overlap_efficiency`` is
  :func:`overlap_efficiency` over the three numbers.

* ``write_heavy`` — the full client write path (mesh backend) at
  uniform lossless ``B = q`` budgets, where ``pipeline=True`` fuses the
  serial data + metadata round-trips (three collectives) into ONE and
  applies the metadata plane through the write-specialized
  ``_meta_write_apply`` (the fused plan certifies the CREATE/UPDATE-only
  op mix statically); ``speedup`` is the synchronous round time over
  the fused one.

``tests/test_bench_regression.py`` pins the 32-node cells of both
sections; ``tools/bench_check.py`` gates the ``overlap`` schema.

Usage:
    PYTHONPATH=src python benchmarks/pipeline_bench.py --quick
    PYTHONPATH=src python benchmarks/pipeline_bench.py --nodes 8,32
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time
from typing import Dict, List


def _block(x):
    import jax
    jax.block_until_ready(jax.tree_util.tree_leaves(x))


def _time_us(fn, *args, iters=5):
    _block(fn(*args))
    _block(fn(*args))
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    _block(out)
    return (time.perf_counter() - t0) / iters * 1e6


def overlap_efficiency(sync_us: float, pipelined_us: float,
                       lower_bound_us: float) -> float:
    """How much of the pipelining headroom a measured round captured.

    ``1.0`` means the pipelined round reached the fabric model's
    pure-bytes lower bound (every µs of gather latency hidden behind the
    collective); ``0.0`` means it did no better than the synchronous
    round.  Clamped to [0, 1] so regressions (pipelined slower than
    sync) and fits whose lower bound exceeds the sync time (degenerate
    headroom) stay plottable rather than exploding the scale — in the
    degenerate case the round scores 1.0 when pipelining did not hurt
    and 0.0 when it did.
    """
    headroom = sync_us - lower_bound_us
    if headroom <= 0:
        return 1.0 if pipelined_us <= sync_us else 0.0
    return min(1.0, max(0.0, (sync_us - pipelined_us) / headroom))


def bench_node(n: int, q: int, w: int, iters: int) -> Dict:
    """All cells for one node count (requires ``n`` forced devices)."""
    import dataclasses

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import PartitionSpec as PS

    from benchmarks.exchange_bench import _FABRIC_SHAPES, fabric_rows
    from repro.core import burst_buffer as bb
    from repro.core import exchange_select
    from repro.core import mesh_engine as me
    from repro.core.client import BBClient
    from repro.core.exchange_plan import plan_mesh_ragged_spec
    from repro.core.layouts import LayoutMode, route_data
    from repro.core.policy import LayoutPolicy

    # -- same-run fabric fit: the model the lower bounds are honest in --
    frows = fabric_rows(list(_FABRIC_SHAPES), iters=iters)
    fit = exchange_select._fit_fabric(frows)
    model = (fit[0], fit[1], True) if fit is not None else \
        (*exchange_select.FALLBACK_FABRIC, False)

    policy = LayoutPolicy.from_scopes({}, n_nodes=n,
                                      default=LayoutMode.DIST_HASH)
    mesh = me.make_node_mesh(n)
    shift = me.build_mesh_shift(n)
    req = PS(me.NODE_AXIS)
    state_specs = jax.tree_util.tree_map(
        lambda _: PS(me.NODE_AXIS), bb.init_state(1, 1, 1, 1))
    rng = np.random.RandomState(0)

    def data_write_op(cfg):
        """forward_write(update_meta=False): the data plane in isolation."""

        def _w(state, mode, ph, cid, payload, valid):
            return bb.forward_write(
                state, policy, ph, cid, payload, valid, mode=mode,
                exchange=me.mesh_exchange, node_ids=me._node_ids(1),
                config=cfg, global_sum=me.mesh_global_sum, shift=shift,
                update_meta=False)

        return jax.jit(jax.shard_map(
            _w, mesh=mesh,
            in_specs=(state_specs, req, req, req, req, req),
            out_specs=state_specs, check_vma=False))

    def overlap_cell(path, cfg_of, ph, rounds_of):
        mode = jnp.full((n, q), int(LayoutMode.DIST_HASH), jnp.int32)
        cid = jnp.asarray(rng.randint(0, 8, (n, q)), jnp.int32)
        payload = jnp.asarray(rng.randint(0, 9999, (n, q, w)), jnp.int32)
        valid = jnp.ones((n, q), bool)
        client = BBClient(policy, mesh, cap=4 * q, words=w, mcap=4 * q)
        times = {}
        for pipe in (False, True):
            op = data_write_op(cfg_of(pipe))
            times[pipe] = _time_us(op, client.state, mode, ph, cid,
                                   payload, valid, iters=iters)
        lb = sum(exchange_select.collective_us(b, model)
                 for b in rounds_of())
        return {
            "path": path, "n_nodes": n, "batch": q, "words": w,
            "sync_us": round(times[False], 1),
            "pipelined_us": round(times[True], 1),
            "lower_bound_us": round(lb, 1),
            "overlap_efficiency": round(overlap_efficiency(
                times[False], times[True], lb), 3),
        }

    row_bytes = 4 * (w + 3)              # keys + payload + occupancy cols

    # ppermute path: hashed traffic, executor forced to the segmented
    # multi-round plan (the fabric-model pick would take padded on a
    # dispatch-heavy host — the point here is to time the N−1 rounds)
    ph_hash = jnp.asarray(rng.randint(1, 1 << 20, (n, q)), jnp.int32)
    mode_np = np.full((n, q), int(LayoutMode.DIST_HASH), np.int32)
    ranks = np.broadcast_to(np.arange(n, dtype=np.int32)[:, None], (n, q))
    dest = route_data(mode_np, n, np.asarray(ph_hash),
                      np.zeros((n, q), np.int32), ranks, xp=np)
    spec = plan_mesh_ragged_spec(dest, np.ones((n, q), bool), n,
                                 row_bytes=row_bytes,
                                 node_ids=np.arange(n))
    spec = dataclasses.replace(spec, executor="ppermute")

    def ppermute_rounds():
        return [n * wk * row_bytes for wk in spec.round_widths[1:]
                if wk > 0]

    cells = [overlap_cell(
        "ppermute",
        lambda pipe: dataclasses.replace(bb.COMPACTED, data_spec=spec,
                                         pipeline=pipe),
        ph_hash, ppermute_rounds)]

    # carry path: incast (every slot → one owner) at B = q/2 — the main
    # all_to_all plus the cond-gated carry round, which fires every call.
    # Transport in isolation: run_exchange over a trivial reducing apply,
    # because the bound prices ONLY the two collectives and the receiver
    # incast table apply would swamp them on a timeshared host.
    from repro.core import exchange_plan
    B = max(1, q // 2)
    dest_in = jnp.zeros((n, q), jnp.int32)
    valid_in = jnp.ones((n, q), bool)
    fields_in = jnp.concatenate(
        [jnp.asarray(rng.randint(0, 999, (n, q, w + 2)), jnp.int32),
         jnp.ones((n, q, 1), jnp.int32)], axis=-1)
    clientv = jnp.arange(n, dtype=jnp.int32)[:, None]
    carry_state0 = jnp.zeros((n, 1), jnp.int32)

    def carry_transport_op(pipe):
        cfg = dataclasses.replace(bb.COMPACTED, budget=B, lossless=True,
                                  pipeline=pipe)

        def _x(st, d, v, f, cl):
            out_st, _, _, _ = exchange_plan.run_exchange(
                "data", policy, cfg, d, v, f,
                lambda s, recv, rv: (
                    s + recv.astype(jnp.int32).sum() + rv.sum(), None),
                exchange=me.mesh_exchange, shift=shift,
                global_sum=me.mesh_global_sum, state=st, client=cl)
            return out_st

        return jax.jit(jax.shard_map(_x, mesh=mesh, in_specs=(req,) * 5,
                                     out_specs=req, check_vma=False))

    carry_times = {}
    for pipe in (False, True):
        carry_times[pipe] = _time_us(
            carry_transport_op(pipe), carry_state0, dest_in, valid_in,
            fields_in, clientv, iters=iters)
    carry_lb = sum(exchange_select.collective_us(b, model) for b in
                   [n * n * B * row_bytes,
                    n * n * exchange_plan._carry_budget(q, B) * row_bytes])
    cells.append({
        "path": "carry", "n_nodes": n, "batch": q, "words": w,
        "sync_us": round(carry_times[False], 1),
        "pipelined_us": round(carry_times[True], 1),
        "lower_bound_us": round(carry_lb, 1),
        "overlap_efficiency": round(overlap_efficiency(
            carry_times[False], carry_times[True], carry_lb), 3),
    })

    # -- write-heavy: serial (3 collectives) vs fused (1) full writes --
    # One fused round-trip plus its write-specialized receiver apply
    # (``_meta_write_apply``) vs three collectives through the generic
    # metadata apply, on the real shard_map backend.
    mode = jnp.full((n, q), int(LayoutMode.DIST_HASH), jnp.int32)
    cid = jnp.asarray(rng.randint(0, 8, (n, q)), jnp.int32)
    payload = jnp.asarray(rng.randint(0, 9999, (n, q, w)), jnp.int32)
    valid = jnp.ones((n, q), bool)
    wh = {}
    for label, pipe in (("sync", False), ("fused", True)):
        client = BBClient(policy, mesh, cap=4 * q, words=w, mcap=4 * q,
                          exchange="compacted", budget=q, meta_budget=q,
                          pipeline=pipe)
        wh[label] = _time_us(
            lambda: client._write(client.state, mode, ph_hash, cid,
                                  payload, valid), iters=iters)
    write_heavy = {
        "n_nodes": n, "batch": q, "words": w,
        "sync_us": round(wh["sync"], 1),
        "fused_us": round(wh["fused"], 1),
        "speedup": round(wh["sync"] / wh["fused"], 2),
    }
    return {"fabric_rows": frows,
            "fabric_fit": {"a_us": round(model[0], 1),
                           "bytes_per_us": round(model[1], 1),
                           "measured": model[2]},
            "cells": cells, "write_heavy": write_heavy}


def main(argv=None) -> Dict:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--quick", action="store_true",
                    help="N=8,32 at q=64 w=16, 5 iters")
    ap.add_argument("--nodes", default="8,32")
    ap.add_argument("--batch", type=int, default=64)
    ap.add_argument("--words", type=int, default=16)
    ap.add_argument("--iters", type=int, default=5)
    ap.add_argument("--out", default="BENCH_pr10.json")
    args = ap.parse_args(argv)
    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))
    from benchmarks.child import run_child
    nodes = ([8, 32] if args.quick
             else [int(x) for x in args.nodes.split(",")])
    cells: List[Dict] = []
    write_heavy: List[Dict] = []
    fabric = None
    for n in nodes:
        got = run_child("benchmarks.pipeline_bench", "bench_node",
                        (n, args.batch, args.words, args.iters), n)
        for c in got["cells"]:
            print(f"{c['path']:9s} N={c['n_nodes']:3d} "
                  f"sync={c['sync_us']:9.1f}us "
                  f"pipelined={c['pipelined_us']:9.1f}us "
                  f"bound={c['lower_bound_us']:9.1f}us "
                  f"eff={c['overlap_efficiency']}")
        wh = got["write_heavy"]
        print(f"write_hvy N={wh['n_nodes']:3d} sync={wh['sync_us']:9.1f}us "
              f"fused={wh['fused_us']:9.1f}us speedup={wh['speedup']}")
        cells += got["cells"]
        write_heavy.append(wh)
        # keep the largest run's fabric section (the 32-node fit the
        # regression bounds key on)
        fabric = {"collective": "mesh_all_to_all", "n_devices": n,
                  "fit": got["fabric_fit"], "rows": got["fabric_rows"]}
    from repro.core import exchange_select, obs
    result = {
        "meta": {
            "bench": "pipeline_bench", "pr": 10,
            "workload": "mesh data-plane rounds sync vs software-"
                        "pipelined (ppermute/carry) + serial vs fused "
                        "write round-trips, vs the same-run fabric fit",
            "iters": args.iters,
            "timestamp": time.strftime("%Y-%m-%dT%H:%M:%S"),
            **obs.provenance_meta(warm_passes=2),
        },
        "overlap": {"cells": cells},
        "write_heavy": {"cells": write_heavy},
        "fabric": fabric,
    }
    with open(args.out, "w") as f:
        json.dump(result, f, indent=1)
    exchange_select.refresh()
    print(f"wrote {args.out}")
    return result


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
