"""Exchange data-plane benchmark: dense O(N²·q) bucketize vs the compacted
plan (ragged histogram-sized budgets by default), swept over nodes × batch
× words.

Each cell runs the REAL stacked engine (both backends share one request
trace: a mixed-mode batch, half Mode-2 central-metadata and half Mode-3
hashed, exercising write + read + stat) and reports measured wall time per
call next to the modeled exchange footprint from
``burst_buffer.exchange_footprint``.  Results go to a machine-readable JSON
(``BENCH_pr3.json``) so later PRs can diff the perf trajectory, the
per-call backend auto-selection (``exchange_select``) can learn the
measured dense/compacted crossover, and ``docs/exchange.md`` can cite the
"which backend wins where" table (``--markdown`` prints it).

Also includes the carry-round microbench (uniform tight budget: lossless
carry vs legacy drop vs single lossless round) and the client-boundary
microbenches: memoized vs uncached path hashing in ``BBClient.encode`` and
interpret-mode latencies of the routing / histogram / pack kernels.

Usage:
    PYTHONPATH=src python benchmarks/exchange_bench.py --quick
    PYTHONPATH=src python benchmarks/exchange_bench.py \
        --nodes 8,16,32,64 --batch 32,64,128 --words 8,16
"""
from __future__ import annotations

import argparse
import json
import time
from typing import Dict, List

import numpy as np


def _block(x):
    import jax
    jax.block_until_ready(jax.tree_util.tree_leaves(x))


def _time_us(fn, *args, iters: int) -> float:
    _block(fn(*args))                                  # compile
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    _block(out)
    return (time.perf_counter() - t0) / iters * 1e6


def _mixed_policy(n_nodes: int):
    from repro.core.layouts import LayoutMode
    from repro.core.policy import LayoutPolicy
    return LayoutPolicy.from_scopes(
        {"/bb/meta2": LayoutMode.CENTRAL_META}, n_nodes=n_nodes,
        default=LayoutMode.DIST_HASH)


def bench_cell(n: int, q: int, w: int, kind: str, iters: int,
               capacity: float, trace=None) -> Dict:
    import jax.numpy as jnp
    from repro.core import burst_buffer as bb
    from repro.core.client import BBClient
    from repro.core.layouts import LayoutMode

    policy = _mixed_policy(n)
    # ragged (default): budgets — data AND metadata — are sized per call
    # from the measured per-destination histograms, so the old explicit
    # hash-spread ``meta_budget`` workaround is gone and the plan is
    # lossless with no carry round
    client = BBClient(policy, cap=max(256, 4 * q), words=w,
                      mcap=max(256, 4 * q), exchange=kind,
                      capacity=capacity, trace=trace)
    rng = np.random.RandomState(0)
    ph = jnp.asarray(rng.randint(1, 1 << 20, (n, q)), jnp.int32)
    cid = jnp.asarray(rng.randint(0, 8, (n, q)), jnp.int32)
    payload = jnp.asarray(rng.randint(0, 9999, (n, q, w)), jnp.int32)
    valid = jnp.ones((n, q), bool)
    mode = jnp.asarray(rng.choice([int(LayoutMode.CENTRAL_META),
                                   int(LayoutMode.DIST_HASH)], (n, q)),
                       jnp.int32)
    op = jnp.full((n, q), bb.OP_STAT, jnp.int32)
    zeros = jnp.zeros((n, q), jnp.int32)
    neg = jnp.full((n, q), -1, jnp.int32)

    write_us = _time_us(client._write, client.state, mode, ph, cid, payload,
                        valid, iters=iters)
    client.state = client._write(client.state, mode, ph, cid, payload, valid)
    read_us = _time_us(client._read, client.state, mode, ph, cid, valid,
                       iters=iters)
    stat_us = _time_us(client._meta, client.state, mode, op, ph, zeros, neg,
                       valid, iters=iters)
    # footprint of the config this cell actually ran — including the
    # measured ragged specs the client attached per call
    cfg = (bb.DENSE if kind == "dense"
           else client._call_config("write", mode, ph, cid, valid))
    foot = bb.exchange_footprint(policy, q, w, cfg)
    return {
        "backend": kind, "n_nodes": n, "batch": q, "words": w,
        "data_budget": foot["data_budget"],
        "meta_budget": foot["meta_budget"],
        "ragged_cols": cfg.data_spec.total if cfg.data_spec else None,
        "ragged_meta_cols": cfg.meta_spec.total if cfg.meta_spec else None,
        "write_us": round(write_us, 1), "read_us": round(read_us, 1),
        "stat_us": round(stat_us, 1),
        "write_exchange_bytes": 4 * foot["write_elems"],
        "read_exchange_bytes": 4 * foot["read_elems"],
        "write_carry_bytes_worst": 4 * foot["write_carry_elems"],
        "chunks_per_s_write": round(n * q / (write_us / 1e6)),
    }


def encode_bench(n_rows: int = 64, row_len: int = 32,
                 repeats: int = 5) -> Dict:
    """Memoized encode vs the raw per-path hashing loop it replaced."""
    from repro.core.layouts import str_hash

    policy = _mixed_policy(8)
    paths = [[f"/bb/meta2/dir{i}/file{j}" for j in range(row_len)]
             for i in range(n_rows)]
    n_paths = n_rows * row_len

    from repro.core.client import BBClient
    client = BBClient(policy, cap=16, words=4, mcap=16)
    t0 = time.perf_counter()
    _block(client.encode(paths))
    cold_us = (time.perf_counter() - t0) * 1e6
    warm = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        _block(client.encode(paths))
        warm.append((time.perf_counter() - t0) * 1e6)
    t0 = time.perf_counter()
    for row in paths:                                   # the old hot loop
        for p in row:
            str_hash(p)
            policy.scope_hash_of(p)
    uncached_us = (time.perf_counter() - t0) * 1e6
    warm_us = min(warm)
    return {"n_paths": n_paths, "cold_us": round(cold_us, 1),
            "warm_us": round(warm_us, 1),
            "uncached_loop_us": round(uncached_us, 1),
            "steady_state_speedup": round(uncached_us / warm_us, 2)}


def carry_bench(n: int = 8, q: int = 64, w: int = 16,
                iters: int = 5) -> Dict:
    """Cost of the lossless carry round at a uniform tight budget.

    A per-file concentrated batch (every chunk of one file per node — the
    canonical checkpoint write) overflows a ``q//4`` uniform budget every
    call, so the cond-gated carry round is TAKEN; comparing against the
    legacy drop plane (same budget, ``lossless=False``) isolates what
    losslessness costs when it actually fires, and against the single
    lossless round (``budget=q``) what the tight budget saves/loses.
    Ragged sizing is disabled so the uniform path is what's measured.
    """
    import jax.numpy as jnp
    from repro.core.client import BBClient
    from repro.core.policy import LayoutPolicy
    from repro.core.layouts import LayoutMode

    policy = LayoutPolicy.uniform(LayoutMode.DIST_HASH, n)
    rng = np.random.RandomState(0)
    ph = jnp.asarray(np.repeat(rng.randint(1, 1 << 20, (n, 1)), q, axis=1),
                     jnp.int32)
    cid = jnp.asarray(np.tile(np.arange(q, dtype=np.int32), (n, 1)))
    payload = jnp.asarray(rng.randint(0, 9999, (n, q, w)), jnp.int32)
    valid = jnp.ones((n, q), bool)
    mode = jnp.full((n, q), int(LayoutMode.DIST_HASH), jnp.int32)
    out = {"n_nodes": n, "batch": q, "words": w, "budget": q // 4}
    for name, kw in [
        ("carry_taken_us", dict(budget=q // 4, lossless=True)),
        ("drop_us", dict(budget=q // 4, lossless=False)),
        ("single_round_us", dict(budget=q, lossless=True)),
    ]:
        client = BBClient(policy, cap=4 * q, words=w, mcap=4 * q,
                          exchange="compacted", ragged=False,
                          meta_budget=q, **kw)
        out[name] = round(_time_us(client._write, client.state, mode, ph,
                                   cid, payload, valid, iters=iters), 1)
    out["carry_overhead_vs_drop"] = round(
        out["carry_taken_us"] / out["drop_us"], 2)
    return out


def fabric_rows(shapes: List, iters: int = 10) -> List[Dict]:
    """Time the mesh backend's ``all_to_all`` on the available devices.

    Measures ``mesh_engine.mesh_exchange`` — the exact collective every
    mesh engine call funnels through — under ``shard_map`` over whatever
    devices this process sees, reporting bytes/µs per shape.  These are
    FABRIC timings (the real collective), not the CPU transposes the
    stacked sweep measures; the auto-selection model does not consume
    them yet (ROADMAP: per-deployment learned tables) — this is the
    measurement wiring and the JSON schema they will key on.
    """
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as PS

    from repro.core.mesh_engine import (NODE_AXIS, make_node_mesh,
                                        mesh_exchange)
    n_dev = len(jax.devices())
    mesh = make_node_mesh(n_dev)
    fn = jax.jit(jax.shard_map(mesh_exchange, mesh=mesh,
                               in_specs=PS(NODE_AXIS),
                               out_specs=PS(NODE_AXIS), check_vma=False))
    rows = []
    for slots, words in shapes:
        x = jnp.ones((n_dev, n_dev, slots, words), jnp.int32)
        us = _time_us(fn, x, iters=iters)
        nbytes = int(x.size) * 4
        rows.append({"n_devices": n_dev, "slots": int(slots),
                     "words": int(words), "us_per_call": round(us, 1),
                     "exchanged_bytes": nbytes,
                     "bytes_per_us": round(nbytes / us, 1)})
    return rows


_FABRIC_SHAPES = ((8, 16), (64, 16), (256, 16))


def fabric_bench(n_devices: int = 8, iters: int = 10) -> Dict:
    """``all_to_all`` fabric timings on ``n_devices`` real host devices.

    The device count must be forced before jax initializes, so the
    measurement runs in a child process (``benchmarks.child``); a failed
    child ends the run non-zero.
    """
    import pathlib
    import sys
    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))
    from benchmarks.child import run_child
    rows = run_child("benchmarks.exchange_bench", "fabric_rows",
                     ([list(s) for s in _FABRIC_SHAPES], iters), n_devices,
                     timeout=600)
    return {"collective": "mesh_all_to_all",
            "n_devices": rows[0]["n_devices"] if rows else 0,
            "rows": rows}


def kernel_bench(iters: int = 5) -> List[Dict]:
    """Interpret-mode kernel latencies (correctness-path cost, off-TPU)."""
    import jax.numpy as jnp
    from repro.kernels.chunk_pack.ops import gather_rows, pack_chunks
    from repro.kernels.chunk_router.ops import dest_histogram, route_chunks

    rng = np.random.RandomState(0)
    n = 4096
    ph = jnp.asarray(rng.randint(1, 1 << 30, n), jnp.int32)
    cid = jnp.asarray(rng.randint(0, 64, n), jnp.int32)
    cl = jnp.zeros(n, jnp.int32)
    payload = jnp.asarray(rng.randint(0, 9999, (n, 16)), jnp.int32)
    idx = jnp.asarray(rng.randint(-1, n, n), jnp.int32)
    dest = jnp.asarray(rng.randint(-1, 64, n), jnp.int32)
    rows = []
    for name, fn, args in [
        ("chunk_router.4096", route_chunks, (ph, cid, cl)),
        ("dest_histogram.4096x64", dest_histogram, (dest,)),
        ("chunk_pack.4096x16", pack_chunks, (payload, idx)),
        ("gather_rows.4096x16", gather_rows, (payload, idx)),
    ]:
        kw = ({"mode": 3, "n_nodes": 64} if "router" in name
              else {"n_bins": 64} if "histogram" in name else {})
        us = _time_us(lambda: fn(*args, **kw), iters=iters)
        rows.append({"kernel": name, "us_per_call": round(us, 1)})
    return rows


def run(nodes: List[int], batches: List[int], words: List[int],
        iters: int, capacity: float, out: str, skip_micro: bool = False,
        trace_out: str = "") -> Dict:
    from repro.core import obs
    rec = obs.TraceRecorder() if trace_out else None
    rows = []
    with obs.activate(rec):
        for n in nodes:
            for q in batches:
                for w in words:
                    for kind in ("dense", "compacted"):
                        row = bench_cell(n, q, w, kind, iters, capacity,
                                         trace=rec)
                        rows.append(row)
                        print(f"{kind:9s} N={n:3d} q={q:4d} w={w:3d} "
                              f"write={row['write_us']:9.1f}us "
                              f"read={row['read_us']:9.1f}us "
                              f"xbytes={row['write_exchange_bytes']}")
        # summary at the largest swept node count
        n_max = max(nodes)
        summary = {}
        for q in batches:
            for w in words:
                d = next(r for r in rows if r["backend"] == "dense" and
                         r["n_nodes"] == n_max and r["batch"] == q and
                         r["words"] == w)
                c = next(r for r in rows if r["backend"] == "compacted" and
                         r["n_nodes"] == n_max and r["batch"] == q and
                         r["words"] == w)
                d_round = d["write_us"] + d["read_us"] + d["stat_us"]
                c_round = c["write_us"] + c["read_us"] + c["stat_us"]
                summary[f"N{n_max}_q{q}_w{w}"] = {
                    "write_speedup": round(d["write_us"] / c["write_us"], 2),
                    "read_speedup": round(d["read_us"] / c["read_us"], 2),
                    "stat_speedup": round(d["stat_us"] / c["stat_us"], 2),
                    "round_speedup": round(d_round / c_round, 2),
                    "exchange_bytes_ratio": round(
                        d["write_exchange_bytes"] /
                        c["write_exchange_bytes"], 2),
                }
        # measured dense/compacted crossover + leave-one-out accuracy of
        # the auto selector (each cell predicted from the table WITHOUT
        # itself — a self-lookup would score 1.0 on any data); runs under
        # the recorder activation so its pick_backend calls audit too
        from repro.core import exchange_select
        crossover = exchange_select.crossover_table(rows)
        acc = exchange_select.auto_accuracy(crossover)
    auto_accuracy = None if acc is None else round(acc, 3)
    result = {
        "meta": {
            "bench": "exchange_bench", "pr": 3,
            "workload": "mixed-mode (Mode-2 central-meta + Mode-3 hashed) "
                        "write/read/stat, stacked backend, ragged budgets",
            "capacity": capacity, "iters": iters,
            "timestamp": time.strftime("%Y-%m-%dT%H:%M:%S"),
            **obs.provenance_meta(warm_passes=iters),
        },
        "rows": rows,
        "summary": summary,
        "crossover": [list(c) for c in crossover],
        "auto_accuracy": auto_accuracy,
    }
    if not skip_micro:
        result["encode"] = encode_bench()
        result["kernels"] = kernel_bench()
        result["carry"] = carry_bench()
        # mesh-fabric all_to_all timings (schema for future auto-selection
        # features; see fabric_rows)
        result["fabric"] = fabric_bench()
    with open(out, "w") as f:
        json.dump(result, f, indent=1)
    # invalidate the per-process crossover cache so in-process clients
    # constructed after this run pick from the fresh artifact
    exchange_select.refresh()
    print(f"wrote {out}")
    if rec is not None:
        obs.write_recording(rec, trace_out, meta=result["meta"])
        print(f"wrote {trace_out} ({len(rec.spans)} spans, "
              f"{sum(rec.audit.counts().values())} decisions)")
    for k, v in summary.items():
        print(f"summary {k}: {v}")
    print(f"auto_accuracy (leave-one-out): {auto_accuracy} "
          f"over {len(crossover)} cells")
    return result


def markdown_table(result: Dict) -> str:
    """The docs/exchange.md "which backend wins where" table from a bench
    result dict (``--markdown`` prints it for paste-through).  Winners and
    round times come from ``exchange_select`` so the table can never
    diverge from what ``pick_backend`` actually selects."""
    from repro.core import exchange_select as xs
    lines = ["| N | q | words | dense round µs | compacted round µs | "
             "winner | bytes ratio (d/c) |",
             "|---|---|-------|---------------|--------------------|"
             "--------|-------------------|"]
    by = {}
    for r in result["rows"]:
        by.setdefault((r["n_nodes"], r["batch"], r["words"]),
                      {})[r["backend"]] = r
    for n, q, w, winner in xs.crossover_table(result["rows"]):
        d, c = by[(n, q, w)]["dense"], by[(n, q, w)]["compacted"]
        ratio = d["write_exchange_bytes"] / c["write_exchange_bytes"]
        lines.append(f"| {n} | {q} | {w} | {xs.round_us(d):.0f} | "
                     f"{xs.round_us(c):.0f} | {winner} | {ratio:.1f}× |")
    return "\n".join(lines)


def main(argv=None) -> Dict:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--quick", action="store_true",
                    help="small sweep (4/8/32 nodes, q=8/64, w=16) — "
                         "includes the tiny cells where dense wins, so the "
                         "auto selector has a real crossover to learn")
    ap.add_argument("--nodes", default="8,16,32,64")
    ap.add_argument("--batch", default="32,64,128")
    ap.add_argument("--words", default="8,16")
    ap.add_argument("--iters", type=int, default=10)
    ap.add_argument("--capacity", type=float, default=2.0)
    ap.add_argument("--out", default="BENCH_pr3.json")
    ap.add_argument("--skip-micro", action="store_true")
    ap.add_argument("--trace-out", default="",
                    help="also write a flight-recorder capture of the "
                         "sweep (Perfetto trace events + metrics snapshot "
                         "+ decision audit) to this JSON path")
    ap.add_argument("--markdown", action="store_true",
                    help="also print the docs/exchange.md winner table")
    args = ap.parse_args(argv)
    if args.quick:
        nodes, batches, words, iters = [4, 8, 32], [8, 64], [16], 10
    else:
        nodes = [int(x) for x in args.nodes.split(",")]
        batches = [int(x) for x in args.batch.split(",")]
        words = [int(x) for x in args.words.split(",")]
        iters = args.iters
    result = run(nodes, batches, words, iters, args.capacity, args.out,
                 skip_micro=args.skip_micro, trace_out=args.trace_out)
    if args.markdown:
        print(markdown_table(result))
    return result


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
