"""Mesh exchange data-plane benchmark: measured ragged plans vs uniform
``q`` budgets on the real shard_map backend.

Each cell spawns a subprocess that forces ``n`` host devices, builds two
mesh-backed ``BBClient``s over the SAME policy and request trace —

* **uniform**: ``ragged=False`` — the pre-PR-5 mesh plane: jit-static
  uniform budgets (B = q here, because the hybrid scope makes
  concentration structural) with the lossless carry round;
* **ragged**: the measured ``MeshRaggedSpec`` plane (global-max padded
  ``all_to_all`` or ppermute segmented rounds, picked per call from the
  fabric model) —

and times write / read / stat per call next to the modeled exchange bytes
of the config each call actually ran.  Two workloads per node count:

* ``skewed`` — half the batch is hybrid self-placed traffic (one hot
  diagonal per node: the regime where global-max padding degenerates
  toward uniform q and only a segmented plan saves bytes);
* ``spread`` — hashed traffic (the even-histogram regime where padding
  to the measured bmax is already a large win over B = q).

Results land in ``BENCH_pr5.json`` — including a re-measured ``fabric``
section (the all_to_all timings ``exchange_select.fabric_model`` fits, so
committing the artifact makes the executor pick and the migration-cost
gate *measured* on this deployment).  ``tests/test_bench_regression.py``
pins the byte-reduction floor against this artifact.

Usage:
    PYTHONPATH=src python benchmarks/mesh_bench.py --quick
    PYTHONPATH=src python benchmarks/mesh_bench.py --nodes 8,32 --batch 64
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time
from typing import Dict, List


def bench_cells(n: int, q: int, w: int, iters: int) -> List[Dict]:
    """Run the uniform-vs-ragged cells for one node count (in-process).

    Must run under a process that already sees ``n`` devices — use
    ``benchmarks.child.run_child`` from the harness entry point.
    """
    import jax.numpy as jnp
    import numpy as np
    from repro.core import burst_buffer as bb
    from repro.core.client import BBClient
    from repro.core.layouts import LayoutMode
    from repro.core.mesh_engine import make_node_mesh
    from repro.core.policy import LayoutPolicy

    def _block(x):
        import jax
        jax.block_until_ready(jax.tree_util.tree_leaves(x))

    def _time_us(fn, *args):
        # two warmup calls: the first plants the presizing floor, which
        # widens the planned spec ONCE (one extra jit specialization);
        # the second compiles the stabilized spec — steady state is what
        # gets timed
        _block(fn(*args))
        _block(fn(*args))
        t0 = time.perf_counter()
        for _ in range(iters):
            out = fn(*args)
        _block(out)
        return (time.perf_counter() - t0) / iters * 1e6

    policy = LayoutPolicy.from_scopes(
        {"/bb/hot": LayoutMode.HYBRID}, n_nodes=n,
        default=LayoutMode.DIST_HASH)
    rng = np.random.RandomState(0)
    rows = []
    for workload in ("skewed", "spread"):
        if workload == "skewed":
            # half hybrid (self-placed: the hot diagonal), half hashed
            mode = np.where(np.arange(q)[None, :] % 2 == 0,
                            int(LayoutMode.HYBRID),
                            int(LayoutMode.DIST_HASH))
            mode = np.broadcast_to(mode, (n, q)).astype(np.int32)
        else:
            mode = np.full((n, q), int(LayoutMode.DIST_HASH), np.int32)
        ph = rng.randint(1, 1 << 20, (n, q)).astype(np.int32)
        cid = rng.randint(0, 8, (n, q)).astype(np.int32)
        payload = rng.randint(0, 9999, (n, q, w)).astype(np.int32)
        valid = np.ones((n, q), bool)
        args = (jnp.asarray(mode), jnp.asarray(ph), jnp.asarray(cid),
                jnp.asarray(payload), jnp.asarray(valid))
        for backend, ragged in (("uniform", False), ("ragged", True)):
            client = BBClient(policy, make_node_mesh(n),
                              cap=max(256, 4 * q), words=w,
                              mcap=max(256, 4 * q), exchange="compacted",
                              ragged=ragged)
            mode_a, ph_a, cid_a, pay_a, valid_a = args
            write_us = _time_us(
                lambda: client._write(client.state, mode_a, ph_a, cid_a,
                                      pay_a, valid_a))
            client.state = client._write(client.state, mode_a, ph_a,
                                         cid_a, pay_a, valid_a)
            read_us = _time_us(
                lambda: client._read(client.state, mode_a, ph_a, cid_a,
                                     valid_a))
            op = jnp.full((n, q), bb.OP_STAT, jnp.int32)
            zeros = jnp.zeros((n, q), jnp.int32)
            neg = jnp.full((n, q), -1, jnp.int32)
            stat_us = _time_us(
                lambda: client._meta(client.state, mode_a, op, ph_a,
                                     zeros, neg, valid_a))
            cfg = client._call_config("write", mode_a, ph_a, cid_a,
                                      valid_a)
            foot = bb.exchange_footprint(policy, q, w, cfg)
            spec = cfg.data_spec
            rows.append({
                "backend": backend, "workload": workload, "n_nodes": n,
                "batch": q, "words": w,
                "executor": (getattr(spec, "executor", "packed")
                             if spec is not None else "uniform"),
                "data_budget": foot["data_budget"],
                "write_us": round(write_us, 1),
                "read_us": round(read_us, 1),
                "stat_us": round(stat_us, 1),
                "write_exchange_bytes": 4 * foot["write_elems"],
                "read_exchange_bytes": 4 * foot["read_elems"],
            })
    return rows


def summarize(rows: List[Dict]) -> Dict:
    """Per (N, workload): ragged-vs-uniform byte and wall-time ratios."""
    by = {}
    for r in rows:
        by.setdefault((r["n_nodes"], r["workload"]),
                      {})[r["backend"]] = r
    out = {}
    for (n, wl), pair in sorted(by.items()):
        if "uniform" not in pair or "ragged" not in pair:
            continue
        u, g = pair["uniform"], pair["ragged"]

        def _round(r):
            return r["write_us"] + r["read_us"] + r["stat_us"]

        out[f"N{n}_{wl}"] = {
            "executor": g["executor"],
            "exchange_bytes_reduction": round(
                u["write_exchange_bytes"] / g["write_exchange_bytes"], 2),
            "read_bytes_reduction": round(
                u["read_exchange_bytes"] / g["read_exchange_bytes"], 2),
            "round_time_ratio": round(_round(u) / _round(g), 2),
        }
    return out


def main(argv=None) -> Dict:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--quick", action="store_true",
                    help="N=8,32 at q=64 w=16, 5 iters")
    ap.add_argument("--nodes", default="8,32")
    ap.add_argument("--batch", type=int, default=64)
    ap.add_argument("--words", type=int, default=16)
    ap.add_argument("--iters", type=int, default=5)
    ap.add_argument("--out", default="BENCH_pr5.json")
    args = ap.parse_args(argv)
    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))
    from benchmarks.child import run_child
    nodes = ([8, 32] if args.quick
             else [int(x) for x in args.nodes.split(",")])
    rows: List[Dict] = []
    for n in nodes:
        got = run_child("benchmarks.mesh_bench", "bench_cells",
                        (n, args.batch, args.words, args.iters), n)
        for r in got:
            print(f"{r['backend']:8s} {r['workload']:7s} N={r['n_nodes']:3d} "
                  f"exec={r['executor']:8s} "
                  f"write={r['write_us']:9.1f}us "
                  f"xbytes={r['write_exchange_bytes']}")
        rows += got
    # re-measure the fabric so the committed artifact makes
    # exchange_select.fabric_model (executor pick, migration gate) measured
    from benchmarks.exchange_bench import fabric_bench
    from repro.core import obs
    result = {
        "meta": {
            "bench": "mesh_bench", "pr": 5,
            "workload": "mesh shard_map write/read/stat, hybrid+hashed "
                        "mix; ragged (MeshRaggedSpec) vs uniform budgets",
            "iters": args.iters,
            "timestamp": time.strftime("%Y-%m-%dT%H:%M:%S"),
            **obs.provenance_meta(warm_passes=1),
        },
        "rows": rows,
        "summary": summarize(rows),
        "fabric": fabric_bench(n_devices=max(nodes)),
    }
    with open(args.out, "w") as f:
        json.dump(result, f, indent=1)
    from repro.core import exchange_select
    exchange_select.refresh()
    print(f"wrote {args.out}")
    for k, v in result["summary"].items():
        print(f"summary {k}: {v}")
    return result


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
