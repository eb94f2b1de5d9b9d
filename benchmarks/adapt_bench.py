"""Online-adaptation benchmark: drifting workload, static vs adapted layout.

The scenario the adapt subsystem exists for: a scope whose production
traffic changes phase mid-run.

* **Phase A** (write-heavy, self-local): every node streams chunks into
  its own files under ``/bb/stream``.  The static per-scope decision —
  NODE_LOCAL — is right for this phase.
* **Phase B** (read-heavy, cross-rank): nodes read each *other's* files.
  Under NODE_LOCAL every such read misses its self-routed lookup and
  falls back to the stranded-data broadcast — the paper's structural
  Mode-1 penalty, measured here on the real engine.

Two clients run the identical op sequence:

* ``static`` — the phase-A policy forever (no telemetry);
* ``adapted`` — ``telemetry=True`` + an ``AdaptationController`` ticked
  once per round: phase B's signature (read share up, locality collapsed)
  drifts past the EWMA threshold, the re-decision proposes a hashed
  layout, the cost/benefit gate clears it, and a ``LiveMigrator``
  relocates the scope's chunks in bounded installments while dual-epoch
  reads keep serving.

The JSON artifact (``BENCH_pr4.json``, ``make bench-adapt``) records the
per-round wall times of both clients, the adaptation timeline
(detection tick, migration ticks, epochs) and the summary the acceptance
criterion reads: steady-state speedup of the adapted client over the
static mismatched layout in phase B, and the number of saved-time rounds
needed to amortize the migration overhead.

Usage:
    PYTHONPATH=src python benchmarks/adapt_bench.py --out BENCH_pr4.json
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


def _policy(n: int):
    from repro.core.layouts import LayoutMode
    from repro.core.policy import LayoutPolicy
    return LayoutPolicy.from_scopes({"/bb/stream": LayoutMode.NODE_LOCAL},
                                    n_nodes=n,
                                    default=LayoutMode.DIST_HASH)


def _paths(n: int, q: int, rng) -> List[List[str]]:
    files = rng.randint(0, 4, (n, q))
    return [[f"/bb/stream/rank{i}/f{files[i, j]}" for j in range(q)]
            for i in range(n)]


def _one_pass(n: int, q: int, w: int, rounds_a: int, rounds_b: int,
              seed: int) -> Dict:
    """One full drifting-workload pass over fresh clients/controller."""
    from repro.core.adapt import AdaptConfig, AdaptationController
    from repro.core.adapt.drift import DriftConfig
    from repro.core.client import BBClient

    cap = 4 * q * max(rounds_a, 2)
    clients = {
        "static": BBClient(_policy(n), cap=cap, words=w, mcap=cap),
        "adapted": BBClient(_policy(n), cap=cap, words=w, mcap=cap,
                            telemetry=True),
    }
    ctl = AdaptationController(
        clients["adapted"],
        cfg=AdaptConfig(drift=DriftConfig(patience=2, cooldown=3,
                                          min_weight=4.0),
                        horizon_rounds=float(rounds_b) * 4,
                        step_chunks=max(64, n * q // 2),
                        installments_per_tick=2))

    rng = np.random.RandomState(seed)
    rounds: List[Dict] = []
    written: List = []          # encoded write requests, replayed as reads

    def one_round(r: int, phase: str) -> Dict:
        row: Dict = {"round": r, "phase": phase}
        if phase == "A":
            paths = _paths(n, q, rng)
            cid = rng.randint(0, rounds_a * 4, (n, q)).astype(np.int32)
            payload = rng.randint(0, 9999, (n, q, w)).astype(np.int32)
            reqs = {name: c.encode(paths, chunk_id=cid, payload=payload)
                    for name, c in clients.items()}
            written.append((paths, cid))
        else:
            # cross-rank replay: each node reads a previous round's
            # chunks written by a DIFFERENT rank
            paths, cid = written[rng.randint(len(written))]
            perm = np.roll(np.arange(n), 1 + r % (n - 1))
            paths = [paths[p] for p in perm]
            cid = cid[perm]
            reqs = {name: c.encode(paths, chunk_id=cid)
                    for name, c in clients.items()}
        for name, c in clients.items():
            req = reqs[name]
            t0 = time.perf_counter()
            if phase == "A":
                c.write(req)
                _block(c.state)
            else:
                outp, found = c.read(req)
                _block((outp, found))
                assert bool(np.asarray(found).all()), \
                    (name, r, "read miss")
            if name == "adapted":
                rep = ctl.tick()
                row["adapt_phase"] = rep.phase
                row["watermark"] = rep.watermark
            row[f"{name}_us"] = round(
                (time.perf_counter() - t0) * 1e6, 1)
        return row

    r = 0
    for _ in range(rounds_a):
        rounds.append(one_round(r, "A"))
        r += 1
    for _ in range(rounds_b):
        rounds.append(one_round(r, "B"))
        r += 1

    # ---- summary -----------------------------------------------------------
    b_rows = [x for x in rounds if x["phase"] == "B"]
    steady = [x for x in b_rows if x["adapt_phase"] == "idle"]
    tail = steady[-max(3, len(steady) // 2):] if steady else b_rows[-3:]
    static_us = float(np.median([x["static_us"] for x in tail]))
    adapted_us = float(np.median([x["adapted_us"] for x in tail]))
    migr = [x for x in b_rows if x["adapt_phase"] in
            ("adopted", "migrating", "completed")]
    overhead_us = float(sum(max(0.0, x["adapted_us"] - adapted_us)
                            for x in migr))
    saving_us = max(1e-9, static_us - adapted_us)
    detect = next((x["round"] for x in b_rows
                   if x["adapt_phase"] in ("adopted", "rejected")), None)
    summary = {
        "static_round_us": round(static_us, 1),
        "adapted_steady_us": round(adapted_us, 1),
        "steady_state_speedup": round(static_us / adapted_us, 2),
        "migration_overhead_us": round(overhead_us, 1),
        "amortized_after_rounds": round(overhead_us / saving_us, 1),
        "steady_rounds_measured": len(steady),
        "detection_round": detect,
        "migration_rounds": len(migr),
    }
    return {"rounds": rounds, "summary": summary,
            "adaptation": ctl.summary()}


def run(out: str, n: int = 8, q: int = 96, w: int = 16,
        rounds_a: int = 5, rounds_b: int = 30, seed: int = 0,
        passes: int = 2) -> Dict:
    """Drive the drifting workload through both clients; write the JSON.

    Two identical passes by default: the first pays every jit compile
    (new policy epochs and the migration op only exist mid-run, so they
    cannot be warmed up front); the second re-runs the identical
    workload against the process-level compile caches and is the pass
    the summary reports — the same compile-excluded convention as
    ``exchange_bench._time_us``.  The cold pass is kept in the artifact
    (``cold``) so one-time compile cost stays visible.
    """
    from repro.core import obs
    cold = None
    for _ in range(max(1, passes) - 1):
        cold = _one_pass(n, q, w, rounds_a, rounds_b, seed)
    warm = _one_pass(n, q, w, rounds_a, rounds_b, seed)
    result = {
        "meta": {"bench": "adapt_bench", "pr": 4,
                 "workload": "drifting /bb/stream: N-N local write burst "
                             "-> cross-rank read/analysis phase",
                 "n_nodes": n, "batch": q, "words": w,
                 "rounds_a": rounds_a, "rounds_b": rounds_b,
                 "passes": passes,
                 "timestamp": time.strftime("%Y-%m-%dT%H:%M:%S"),
                 **obs.provenance_meta(warm_passes=passes - 1)},
        "rounds": warm["rounds"],
        "summary": warm["summary"],
        "adaptation": warm["adaptation"],
    }
    if cold is not None:
        result["cold"] = {"summary": cold["summary"]}
    with open(out, "w") as f:
        json.dump(result, f, indent=1)
    print(f"wrote {out}")
    for k, v in result["summary"].items():
        print(f"summary {k}: {v}")
    return result


def main(argv=None) -> Dict:
    """CLI entry (``make bench-adapt``)."""
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--out", default="BENCH_pr4.json")
    ap.add_argument("--nodes", type=int, default=8)
    ap.add_argument("--batch", type=int, default=96)
    ap.add_argument("--words", type=int, default=16)
    ap.add_argument("--rounds-a", type=int, default=5)
    ap.add_argument("--rounds-b", type=int, default=30)
    ap.add_argument("--passes", type=int, default=2)
    args = ap.parse_args(argv)
    return run(args.out, n=args.nodes, q=args.batch, w=args.words,
               rounds_a=args.rounds_a, rounds_b=args.rounds_b,
               passes=args.passes)


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
