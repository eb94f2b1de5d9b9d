"""Layout-heterogeneity demo: the same 23-workload matrix under all four
layouts, the oracle, Proteus's decision, and the realized speedups —
the paper's Figure 12 on your terminal — followed by the part a single
mode cannot do: a heterogeneous job whose per-scope ``LayoutPolicy`` beats
every uniform layout, executed as one interleaved mixed-mode batch on the
real BB engine.

Run:  PYTHONPATH=src python examples/proteus_layout_demo.py
"""
import dataclasses

import numpy as np

from repro.core.client import BBClient
from repro.core.intent.oracle import oracle_mode, oracle_policy
from repro.core.intent.selector import select_layout
from repro.core.layouts import DEFAULT_MODE, LayoutMode
from repro.core.simulator import simulate
from repro.core.workloads import build_workloads, heterogeneous_workload


def single_mode_matrix() -> None:
    ws = build_workloads(32)
    hits = 0
    print(f"{'workload':10s} {'oracle':9s} {'proteus':9s} {'conf':>5s} "
          f"{'speedup':>8s}  verdict")
    for w in ws:
        orc = oracle_mode(w)
        d = select_layout(w)
        t_def = simulate(w, DEFAULT_MODE, w.n_nodes).total_s
        t_sel = simulate(w, d.mode, w.n_nodes).total_s
        ok = d.mode == orc
        hits += ok
        print(f"{w.name:10s} M{int(orc)}        M{int(d.mode)}       "
              f"{d.confidence:5.2f} {t_def / t_sel:7.2f}x  "
              f"{'✓' if ok else '✗ ' + d.decision.steps[-1][:48]}")
    print(f"\naccuracy: {hits}/{len(ws)} = {hits / len(ws) * 100:.2f}%  "
          f"(paper: 91.30%)")


def heterogeneous_plan() -> None:
    """One job, two scopes, no single-mode answer: the LayoutPolicy story."""
    w = heterogeneous_workload(32)
    print(f"\n=== heterogeneous job: {w.description} ===")
    d = select_layout(w)
    print(f"Proteus plan: default M{int(d.mode)}, scopes "
          + ", ".join(f"{s} → M{int(m)}" for s, m in d.scope_modes.items()))
    policy = d.layout_policy(w.n_nodes)

    times = {f"uniform M{int(m)}": simulate(w, m, w.n_nodes).total_s
             for m in LayoutMode}
    times["per-scope policy"] = simulate(w, policy, w.n_nodes).total_s
    orc = simulate(w, oracle_policy(w), w.n_nodes).total_s
    best_uniform = min(v for k, v in times.items() if k.startswith("uniform"))
    for k, v in sorted(times.items(), key=lambda kv: kv[1]):
        print(f"  {k:18s} {v:8.1f}s")
    print(f"  per-scope oracle   {orc:8.1f}s")
    print(f"→ heterogeneity buys {best_uniform / times['per-scope policy']:.2f}×"
          " over the best single mode")

    # and it runs for real: one interleaved mixed-mode batch, one exchange
    n = 8
    client = BBClient(dataclasses.replace(policy, n_nodes=n),
                      cap=128, words=8, mcap=128)
    rng = np.random.RandomState(0)
    paths = [[(f"/bb/ckpt/rank{r}/f{j}" if j % 2 == 0 else
               f"/bb/shared/obj{r}_{j}") for j in range(6)]
             for r in range(n)]
    req = client.encode(paths, chunk_id=np.zeros((n, 6), np.int32),
                        payload=rng.randint(0, 999, (n, 6, 8)))
    client.write(req)
    out, found = client.read(req)
    assert bool(found.all()) and np.array_equal(np.asarray(out),
                                                np.asarray(req.payload))
    modes = sorted(set(np.asarray(client.policy.resolve(
        np.asarray(req.scope_hash))).ravel().tolist()))
    print(f"BB engine: mixed-mode batch (modes {modes}) written + read "
          "back intact through one BBClient ✓")


def main() -> None:
    single_mode_matrix()
    heterogeneous_plan()


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
