"""Benchmarks mirroring the paper's tables (II: accuracy, III: ablations,
IV: cost) plus real wall-clock microbenchmarks of the decision pipeline and
the BB engine."""
from __future__ import annotations

import time
from typing import List, Tuple

import jax.numpy as jnp
import numpy as np

from repro.core.intent.oracle import oracle_mode
from repro.core.intent.selector import select_layout
from repro.core.workloads import build_workloads

Row = Tuple[str, float, str]


def _accuracy(**kw):
    ws = build_workloads(32)
    hits = sum(int(select_layout(w, **kw).mode == oracle_mode(w))
               for w in ws)
    return hits, len(ws)


def table2_accuracy() -> List[Row]:
    rows = []
    t0 = time.time()
    h, n = _accuracy()
    dt = (time.time() - t0) / n * 1e6
    rows.append(("table2.proteus", dt, f"accuracy={h}/{n}={h / n * 100:.2f}%"
                 ";paper=91.30%"))
    try:
        from repro.core.intent.ml_baseline import loo_accuracy
        acc, _ = loo_accuracy()
        rows.append(("table2.gbdt_baseline", 0.0,
                     f"accuracy={acc * 100:.2f}%;paper_xgboost=73.91%"))
    except Exception as e:  # pragma: no cover
        rows.append(("table2.gbdt_baseline", 0.0, f"error={e}"))
    return rows


def table3_ablations() -> List[Row]:
    rows = []
    for label, kw, paper in (
            ("full", {}, "91.30%"),
            ("wo_runtime", {"use_runtime": False}, "86.96%"),
            ("wo_app_ref", {"use_app_ref": False}, "82.60%"),
            ("wo_mode_know", {"use_mode_know": False}, "65.20%")):
        h, n = _accuracy(**kw)
        rows.append((f"table3.{label}", 0.0,
                     f"accuracy={h / n * 100:.2f}%;paper={paper}"))
    return rows


def table4_cost() -> List[Row]:
    """Decision-pipeline cost: measured wall time per stage + prompt size."""
    from repro.core.intent.probe import run_probe
    from repro.core.intent.prompt import build_prompt
    from repro.core.intent.context import HybridContext
    from repro.core.intent.static_extractor import extract_static
    from repro.core.intent.reasoner import KnowledgeReasoner
    ws = build_workloads(32)
    t_static = t_probe = t_reason = 0.0
    prompt_tokens = 0
    for w in ws:
        t0 = time.time()
        st = extract_static(w.source_code, w.job_script)
        t_static += time.time() - t0
        t0 = time.time()
        rt = run_probe(w)
        t_probe += time.time() - t0
        ctx = HybridContext(w.app, st, rt, w.n_nodes)
        prompt = build_prompt(ctx)
        prompt_tokens += len(prompt.split())
        t0 = time.time()
        KnowledgeReasoner().reason(ctx)
        t_reason += time.time() - t0
    n = len(ws)
    return [
        ("table4.static_extract", t_static / n * 1e6,
         "offline_training_runs=0"),
        ("table4.probe", t_probe / n * 1e6, "pre_exec_profiling=1-2 probes"),
        ("table4.reasoning", t_reason / n * 1e6,
         f"prompt_words~{prompt_tokens // n};paper_llm_latency=33.0s"),
    ]


def engine_microbench() -> List[Row]:
    """REAL wall-clock of the BB data plane (BBClient stacked backend)."""
    import jax
    from repro.core.client import BBClient, BBRequest
    from repro.core.layouts import LayoutMode
    from repro.core.policy import LayoutPolicy
    rows = []
    N, q, w = 8, 16, 64
    rng = np.random.RandomState(0)
    req = BBRequest(
        path_hash=jnp.asarray(rng.randint(1, 1 << 20, (N, q)), jnp.int32),
        chunk_id=jnp.asarray(rng.randint(0, 8, (N, q)), jnp.int32),
        payload=jnp.asarray(rng.randint(0, 999, (N, q, w)), jnp.int32))
    valid = jnp.ones((N, q), bool)

    def time_write(client, mode, r):
        # time the jitted data-plane op with pre-built arrays — facade-side
        # request prep (mode resolution, default masks) stays outside the
        # timed loop so rows measure the engine, comparably across policies
        state = client._write(client.state, mode, r.path_hash,
                              r.chunk_id, r.payload, valid)   # compile
        jax.block_until_ready(state.data)
        t0 = time.time()
        iters = 20
        for _ in range(iters):
            state = client._write(state, mode, r.path_hash, r.chunk_id,
                                  r.payload, valid)
        jax.block_until_ready(state.data)
        return (time.time() - t0) / iters * 1e6

    for mode in LayoutMode:
        client = BBClient(LayoutPolicy.uniform(mode, N),
                          cap=1024, words=w, mcap=1024)
        us = time_write(client, client.policy.mode_array((N, q), jnp), req)
        rows.append((f"engine.write.M{int(mode)}", us,
                     f"chunks_per_s={N * q / (us / 1e6):.0f}"))
    # one mixed-mode policy row: two scopes in one interleaved batch
    policy = LayoutPolicy.from_scopes(
        {"ckpt": LayoutMode.HYBRID, "shared": LayoutMode.DIST_HASH},
        n_nodes=N, default=LayoutMode.DIST_HASH)
    client = BBClient(policy, cap=1024, words=w, mcap=1024)
    paths = [[(f"ckpt/r{r}/s{j}" if j % 2 == 0 else f"shared/o{r}_{j}")
              for j in range(q)] for r in range(N)]
    mreq = client.encode(paths, chunk_id=np.asarray(req.chunk_id),
                         payload=np.asarray(req.payload))
    us = time_write(client, policy.resolve(mreq.scope_hash, xp=jnp), mreq)
    rows.append(("engine.write.hetero", us,
                 f"chunks_per_s={N * q / (us / 1e6):.0f}"))
    return rows


def kernel_microbench() -> List[Row]:
    """Kernel wall times; each row says whether the Pallas interpreter
    (off-TPU) or the compiled kernel ran."""
    import jax
    from repro.kernels import default_interpret
    from repro.kernels.chunk_router.ops import route_chunks
    from repro.kernels.fletcher.ops import fletcher_checksum
    mode = f"interpret_mode={default_interpret()}"
    rows = []
    rng = np.random.RandomState(0)
    ph = jnp.asarray(rng.randint(1, 1 << 30, 4096), jnp.int32)
    cid = jnp.asarray(rng.randint(0, 64, 4096), jnp.int32)
    cl = jnp.zeros(4096, jnp.int32)
    d, c = route_chunks(ph, cid, cl, mode=3, n_nodes=64)
    jax.block_until_ready(d)
    t0 = time.time()
    for _ in range(5):
        d, c = route_chunks(ph, cid, cl, mode=3, n_nodes=64)
    jax.block_until_ready(d)
    rows.append(("kernel.chunk_router.4096", (time.time() - t0) / 5 * 1e6,
                 mode))
    x = jnp.asarray(rng.randint(0, 1 << 30, 1 << 16), jnp.int32)
    cs = fletcher_checksum(x)
    jax.block_until_ready(cs)
    t0 = time.time()
    for _ in range(5):
        cs = fletcher_checksum(x)
    jax.block_until_ready(cs)
    rows.append(("kernel.fletcher.64Kwords", (time.time() - t0) / 5 * 1e6,
                 mode))
    return rows
