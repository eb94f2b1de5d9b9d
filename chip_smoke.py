"""On-chip smoke run of the burst-buffer data plane.

One TPU chip (no arguments):

1. decision: the intent pipeline decides the layout of the Table-I IOR-A
   job (N-N checkpoint, ``ior -F -t 4m``), a ``LayoutPolicy`` over 8
   burst-buffer nodes.
2. data: ``BBClient(policy)`` (``exchange="auto"``) and then
   ``BBClient(policy, exchange="compacted")`` on 512 KiB chunks, so one
   4 MiB IOR transfer is 8 chunks.  8 nodes x 512 slots is 2 GiB of node
   table on the chip; each write call moves q = 64 chunks per node (8
   ranks per node, one transfer each).  Four transfers and an overwrite
   of the first fill 62.5 % of the table.  Everything is read back and
   stat'ed, half the files are removed, and they are stat'ed again.
3. mixed: one mixed-mode batch through a per-scope policy
   (``/ckpt`` HYBRID, ``/shared`` DIST_HASH) on a 512 MiB table, read
   back across nodes.
4. metadata: the mdtest-easy shape, 8 x 1,024 empty files in per-rank
   directories, created, stat'ed, removed and stat'ed again.

``--mesh`` (four chips) runs only the mesh phase: 4 nodes, one per chip,
1 GiB of node table per chip, q = 64, once with each mesh executor the
client can pick (padded ``all_to_all`` and ``ppermute``).

Every answer is compared exactly with ``HostStore``, a dict reference that
shares no code with the engine.  Any mismatch, drop or failed phase exits
non-zero.  The last line of a chip run is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
Without a TPU the script fails; ``--rehearse`` runs the same phases at a
tiny size on the CPU backend instead and prints no result line.

Usage:
    python chip_smoke.py [--seed N]
    python chip_smoke.py --mesh
    python chip_smoke.py --rehearse [--mesh]
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import os
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

from repro.compile_cache import enable_compile_cache  # noqa: E402

#: chunks per IOR transfer: ``-t 4m`` over 512 KiB chunks
CHUNKS_PER_TRANSFER = 8


@dataclasses.dataclass(frozen=True)
class Sizes:
    nodes: int
    ranks_per_node: int
    chunk_words: int          # int32 words per chunk
    cap: int                  # data slots per node
    mcap: int                 # metadata slots per node
    write_calls: int          # distinct transfers written per rank
    md_files_per_node: int
    mixed_cap: int            # data slots per node of the mixed client

    @property
    def q(self) -> int:
        return self.ranks_per_node * CHUNKS_PER_TRANSFER


# the mixed batch's fused write holds ~11 GiB of exchange temporaries at
# q = 64 x 512 KiB (PERF.md), so its client gets a quarter-size table
ONE_CHIP = Sizes(nodes=8, ranks_per_node=8, chunk_words=131072, cap=512,
                 mcap=2048, write_calls=4, md_files_per_node=1024,
                 mixed_cap=128)
FOUR_CHIPS = Sizes(nodes=4, ranks_per_node=8, chunk_words=131072, cap=2048,
                   mcap=256, write_calls=4, md_files_per_node=0, mixed_cap=0)
REHEARSAL = {False: dataclasses.replace(ONE_CHIP, ranks_per_node=2,
                                        chunk_words=256, cap=128, mcap=256,
                                        md_files_per_node=64, mixed_cap=32),
             True: dataclasses.replace(FOUR_CHIPS, ranks_per_node=2,
                                       chunk_words=256, cap=128)}


def log(msg: str) -> None:
    print(msg, flush=True)


# ---------------------------------------------------------------------------
# the plain host reference
# ---------------------------------------------------------------------------
class HostStore:
    """Newest-version-wins chunk store and stat records, in dicts.

    A file's size is one past its highest written chunk id; its location
    is the last writer's rank for HYBRID paths and -1 otherwise (the
    data-location rank is a HYBRID record)."""

    def __init__(self, hybrid_paths):
        self.hybrid = hybrid_paths
        self.chunks = {}
        self.files = {}

    def write(self, paths, cids, payload) -> None:
        for node, row in enumerate(paths):
            for j, path in enumerate(row):
                c = int(cids[node][j])
                self.chunks[(path, c)] = payload[node, j]
                size, loc = self.files.get(path, (0, -1))
                self.files[path] = (max(size, c + 1),
                                    node if self.hybrid(path) else loc)

    def create(self, paths) -> None:
        for row in paths:
            for path in row:
                self.files.setdefault(path, (0, -1))

    def remove(self, paths, valid):
        found = np.zeros(valid.shape, bool)
        for node, row in enumerate(paths):
            for j, path in enumerate(row):
                if valid[node, j]:
                    found[node, j] = self.files.pop(path, None) is not None
        return found

    def check_read(self, what, paths, cids, got, found) -> None:
        got, found = np.asarray(got), np.asarray(found)
        for node, row in enumerate(paths):
            for j, path in enumerate(row):
                want = self.chunks.get((path, int(cids[node][j])))
                if want is None:
                    raise AssertionError(f"{what}: reference lacks {path}")
                if not found[node, j] or not np.array_equal(got[node, j],
                                                            want):
                    raise AssertionError(
                        f"{what}: node {node} slot {j} {path} chunk "
                        f"{cids[node][j]} found={bool(found[node, j])} "
                        "differs from the reference")

    def check_stat(self, what, paths, answer) -> None:
        found, size, loc = (np.asarray(a) for a in answer)
        for node, row in enumerate(paths):
            for j, path in enumerate(row):
                rec = self.files.get(path)
                want = ((True,) + rec) if rec is not None else (False, -1, -1)
                got = (bool(found[node, j]), int(size[node, j]),
                       int(loc[node, j]))
                if got != want:
                    raise AssertionError(f"{what}: stat {path} from node "
                                         f"{node} gave {got}, want {want}")


# ---------------------------------------------------------------------------
# request batches
# ---------------------------------------------------------------------------
def ior_batch(sz: Sizes, transfer: int):
    """Every rank of every node writes ``transfer`` of its own file."""
    paths, cids = [], []
    for node in range(sz.nodes):
        prow, crow = [], []
        for j in range(sz.ranks_per_node):
            rank = node * sz.ranks_per_node + j
            for c in range(CHUNKS_PER_TRANSFER):
                prow.append(f"/bb/ior_fpp.{rank:08d}")
                crow.append(transfer * CHUNKS_PER_TRANSFER + c)
        paths.append(prow)
        cids.append(crow)
    return paths, cids


def ior_files(sz: Sizes):
    return [[f"/bb/ior_fpp.{node * sz.ranks_per_node + j:08d}"
             for j in range(sz.ranks_per_node)] for node in range(sz.nodes)]


def mixed_batch(sz: Sizes, call: int):
    """Half a node's slots append to its own checkpoint file (``/ckpt``),
    half write its strided share of one shared file (``/shared``)."""
    half = sz.q // 2
    paths, cids = [], []
    for node in range(sz.nodes):
        paths.append([f"/ckpt/rank{node:04d}.ckpt"] * half +
                     ["/shared/data"] * half)
        cids.append([call * half + j for j in range(half)] +
                    [(call * sz.nodes + node) * half + j
                     for j in range(half)])
    return paths, cids


def shifted(rows, k: int):
    """Node i takes node (i + k)'s row: reads that cross nodes."""
    return [rows[(i + k) % len(rows)] for i in range(len(rows))]


def random_payload(rng, sz: Sizes):
    return rng.integers(np.iinfo(np.int32).min, np.iinfo(np.int32).max,
                        size=(sz.nodes, sz.q, sz.chunk_words), dtype=np.int32)


def mixed_policy(n_nodes: int):
    from repro.core.layouts import LayoutMode
    from repro.core.policy import LayoutPolicy
    return LayoutPolicy.from_scopes(
        {"/ckpt": LayoutMode.HYBRID, "/shared": LayoutMode.DIST_HASH},
        n_nodes=n_nodes, default=LayoutMode.DIST_HASH)


def hybrid_of(policy):
    from repro.core.layouts import LayoutMode
    return lambda path: policy.mode_for_path(path) == LayoutMode.HYBRID


# ---------------------------------------------------------------------------
# measurement helpers
# ---------------------------------------------------------------------------
class CompileClock:
    """Backend compile seconds and persistent-cache hits, from JAX's own
    monitoring events (a cache hit replaces a compile by a load)."""

    def __init__(self):
        import jax
        self.seconds = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


class Phases:
    """Wall seconds per phase (host clock around work that ends in a
    device-to-host copy, so the device has finished)."""

    def __init__(self, clock: CompileClock):
        self.clock = clock
        self.rows = []

    def run(self, name: str, fn, *args):
        t0, c0 = time.perf_counter(), self.clock.seconds
        log(f"phase {name}: start")
        out = fn(*args)
        wall = time.perf_counter() - t0
        comp = self.clock.seconds - c0
        self.rows.append((name, wall, comp))
        log(f"phase {name}: ok, smoke wall {wall:.3f} s "
            f"(of which compile {comp:.3f} s)")
        return out


def custom_calls(fn, *args) -> int:
    """Pallas kernels in the compiled program of ``fn`` at ``args``."""
    text = fn.lower(*args).compile().as_text()
    return text.count('custom_call_target="tpu_custom_call"')


def table_report(what: str, state, sz: Sizes) -> None:
    used = int(np.asarray(state.data_count).sum())
    dropped = int(np.asarray(state.dropped).sum())
    log(f"{what}: node table {state.data.shape} int32 = "
        f"{state.data.nbytes / 2**30:.3f} GiB on device, "
        f"{used}/{sz.nodes * sz.cap} slots used "
        f"({100.0 * used / (sz.nodes * sz.cap):.1f} %), dropped {dropped}")
    if dropped:
        raise AssertionError(f"{what}: the engine dropped {dropped} rows")
    if 2 * used < sz.nodes * sz.cap:
        raise AssertionError(f"{what}: table less than half full")


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------
def decide(sz: Sizes):
    from repro.core.intent.selector import select_layout
    from repro.core.workloads import workload_by_name
    job = workload_by_name("IOR-A", n_nodes=sz.nodes)
    decision = select_layout(job)
    policy = decision.layout_policy(n_nodes=sz.nodes)
    log(f"decision: {job.name} ({job.description}) -> mode "
        f"{int(decision.mode)} {decision.mode.name}, confidence "
        f"{decision.confidence:.2f}; policy over {policy.n_nodes} nodes, "
        f"modes {sorted(int(m) for m in policy.modes_present())}")
    return policy


def data_phase(policy, kind: str, sz: Sizes, rng, on_tpu: bool) -> None:
    from repro.core.client import BBClient
    client = BBClient(policy, cap=sz.cap, words=sz.chunk_words,
                      mcap=sz.mcap, exchange=kind)
    ref = HostStore(hybrid_of(policy))
    # transfers 0..n-1, then an overwrite of transfer 0 (newest wins)
    for t in list(range(sz.write_calls)) + [0]:
        paths, cids = ior_batch(sz, t)
        payload = random_payload(rng, sz)
        req = client.encode(paths, chunk_id=cids, payload=payload)
        client.write(req)
        ref.write(paths, cids, payload)
    table_report(f"data[{kind}]", client.state, sz)
    for t in range(sz.write_calls):
        paths, cids = ior_batch(sz, t)
        got, found = client.read(client.encode(paths, chunk_id=cids))
        ref.check_read(f"data[{kind}] read transfer {t}", paths, cids, got,
                       found)
    files = ior_files(sz)
    ref.check_stat(f"data[{kind}] stat", files,
                   client.stat(client.encode(files)))
    gone = np.zeros((sz.nodes, sz.ranks_per_node), bool)
    gone[:, ::2] = True
    found = np.asarray(client.remove(client.encode(files, valid=gone)))
    want = ref.remove(files, gone)
    if not np.array_equal(found, want):
        raise AssertionError(f"data[{kind}] remove: found {found} "
                             f"!= reference {want}")
    ref.check_stat(f"data[{kind}] stat after remove", files,
                   client.stat(client.encode(files)))
    log(f"data[{kind}]: {sz.write_calls + 1} writes, {sz.write_calls} "
        f"reads, 2 stats and 1 remove agree exactly with the reference")
    if kind == "compacted" and on_tpu:
        paths, cids = ior_batch(sz, 0)
        req = client.encode(paths, chunk_id=cids,
                            payload=random_payload(rng, sz))
        mode, valid = client._modes(req), client._valid(req)
        ph, cid = req.path_hash, req.chunk_id
        w_cfg = client._call_config("write", mode, ph, cid, valid)
        r_cfg = client._call_config("read", mode, ph, cid, valid)
        n_w = custom_calls(client._ops(w_cfg)[0], client.state, mode, ph,
                           cid, req.payload, valid)
        n_r = custom_calls(client._ops(r_cfg)[1], client.state, mode, ph,
                           cid, valid)
        log(f"data[{kind}]: tpu_custom_call in compiled write program: "
            f"{n_w}, in compiled read program: {n_r}")
        if not (n_w and n_r):
            raise AssertionError("the compacted exchange ran no Pallas "
                                 "kernel on the chip")


def mixed_and_metadata_phase(sz: Sizes, rng, phases: Phases) -> None:
    from repro.core.client import BBClient
    policy = mixed_policy(sz.nodes)
    client = BBClient(policy, cap=sz.mixed_cap, words=sz.chunk_words,
                      mcap=sz.mcap)

    def mixed():
        ref = HostStore(hybrid_of(policy))
        paths, cids = mixed_batch(sz, 0)
        payload = random_payload(rng, sz)
        client.write(client.encode(paths, chunk_id=cids, payload=payload))
        ref.write(paths, cids, payload)
        rp, rc = shifted(paths, 1), shifted(cids, 1)
        got, found = client.read(client.encode(rp, chunk_id=rc))
        ref.check_read("mixed read", rp, rc, got, found)
        files = [[f"/ckpt/rank{(i + 1) % sz.nodes:04d}.ckpt", "/shared/data"]
                 for i in range(sz.nodes)]
        ref.check_stat("mixed stat", files, client.stat(client.encode(files)))
        log("mixed: one HYBRID + DIST_HASH batch written, read across nodes "
            "and stat'ed, exact agreement with the reference")

    def metadata():
        ref = HostStore(hybrid_of(policy))
        files = [[f"/bb/mdtest/rank{node:04d}/file.{j:06d}"
                  for j in range(sz.md_files_per_node)]
                 for node in range(sz.nodes)]
        req = client.encode(files)
        found = np.asarray(client.create(req))
        ref.create(files)
        if not found.all():
            raise AssertionError("metadata: create reported a miss")
        ref.check_stat("metadata stat", files, client.stat(req))
        every = np.ones(found.shape, bool)
        found = np.asarray(client.remove(req))
        if not np.array_equal(found, ref.remove(files, every)):
            raise AssertionError("metadata: remove differs from reference")
        ref.check_stat("metadata stat after remove", files, client.stat(req))
        dropped = int(np.asarray(client.state.dropped).sum())
        if dropped:
            raise AssertionError(f"metadata: {dropped} rows dropped")
        log(f"metadata: {sz.nodes} x {sz.md_files_per_node} files created, "
            "stat'ed, removed, stat'ed: exact agreement with the reference")

    phases.run("mixed", mixed)
    phases.run("metadata", metadata)


def _forced(cfg, executor: str):
    def force(spec):
        return None if spec is None else spec.with_executor(executor)

    return dataclasses.replace(cfg, data_spec=force(cfg.data_spec),
                               meta_spec=force(cfg.meta_spec))


def mesh_phase(sz: Sizes, rng, executor: str) -> None:
    """One mesh executor, forced as tests/test_exchange_plan.py does."""
    import jax
    import jax.numpy as jnp
    from repro.core import burst_buffer as bb
    from repro.core.client import BBClient
    from repro.core.layouts import LayoutMode
    from repro.core.mesh_engine import make_node_mesh
    mesh = make_node_mesh(sz.nodes)
    policy = mixed_policy(sz.nodes)
    client = BBClient(policy, mesh, cap=sz.cap, words=sz.chunk_words,
                      mcap=sz.mcap, exchange="compacted")
    shards = client.state.data.addressable_shards
    per_dev = {s.device.id: s.data.shape for s in shards}
    log(f"mesh[{executor}]: state.data {client.state.data.shape} "
        f"= {client.state.data.nbytes / 2**30:.3f} GiB, shards {per_dev}")
    if len(per_dev) != sz.nodes or any(
            shp != (1,) + client.state.data.shape[1:]
            for shp in per_dev.values()):
        raise AssertionError("mesh state is not one node row per device")
    ranks = jnp.broadcast_to(jnp.arange(sz.nodes, dtype=jnp.int32)[:, None],
                             (sz.nodes, sz.q))
    ref = HostStore(hybrid_of(policy))
    used = set()
    for call in range(sz.write_calls):
        paths, cids = mixed_batch(sz, call)
        payload = random_payload(rng, sz)
        req = client.encode(paths, chunk_id=cids, payload=payload)
        mode, valid = client._modes(req), client._valid(req)
        cfg = _forced(client._call_config("write", mode, req.path_hash,
                                          req.chunk_id, valid), executor)
        used.add(cfg.data_spec.executor)
        client.state = client._ops(cfg)[0](client.state, mode,
                                           req.path_hash, req.chunk_id,
                                           req.payload, valid)
        ref.write(paths, cids, payload)
    table_used = int(np.asarray(client.state.data_count).sum())
    dropped = int(np.asarray(client.state.dropped).sum())
    log(f"mesh[{executor}]: {table_used} slots used, dropped {dropped}")
    if dropped:
        raise AssertionError(f"mesh[{executor}]: {dropped} rows dropped")
    for call in range(sz.write_calls):
        paths, cids = mixed_batch(sz, call)
        rp, rc = shifted(paths, 1), shifted(cids, 1)
        req = client.encode(rp, chunk_id=rc)
        mode, valid = client._modes(req), client._valid(req)
        ph, cid = req.path_hash, req.chunk_id
        probe = valid & (mode == LayoutMode.HYBRID)
        cfg_m = _forced(client._call_config("meta", mode, ph, None, probe),
                        executor)
        fm, loc = client._probe_op(cfg_m)(client.state, mode, ph, probe)
        data_loc = jnp.where(fm & (loc >= 0), loc, ranks)
        cfg = _forced(client._call_config("read", mode, ph, cid, valid,
                                          data_loc=data_loc), executor)
        used.add(cfg.data_spec.executor)
        got, found = client._ops(cfg)[3](client.state, mode, ph, cid, valid,
                                         data_loc)
        ref.check_read(f"mesh[{executor}] read call {call}", rp, rc, got,
                       found)
    files = [[f"/ckpt/rank{(i + 1) % sz.nodes:04d}.ckpt", "/shared/data"]
             for i in range(sz.nodes)]
    req = client.encode(files)
    mode, valid = client._modes(req), client._valid(req)
    shape = req.path_hash.shape
    cfg = _forced(client._call_config("meta", mode, req.path_hash, None,
                                      valid), executor)
    used.add(cfg.meta_spec.executor)
    client.state, found, size, loc = client._ops(cfg)[2](
        client.state, mode, jnp.full(shape, bb.OP_STAT, jnp.int32),
        req.path_hash, jnp.zeros(shape, jnp.int32),
        jnp.full(shape, -1, jnp.int32), valid)
    ref.check_stat(f"mesh[{executor}] stat", files, (found, size, loc))
    if used != {executor}:
        raise AssertionError(f"mesh[{executor}]: ran executors {used}")
    log(f"mesh[{executor}]: {sz.write_calls} writes, {sz.write_calls} "
        "cross-node reads and a stat agree exactly with the reference; "
        f"{jax.device_count()} devices, executor {executor}")


def peak_bytes(devices) -> dict:
    out = {}
    for d in devices:
        stats = d.memory_stats() or {}
        out[str(d.id)] = stats.get("peak_bytes_in_use")
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--mesh", action="store_true",
                    help="four chips: only the mesh phase, both executors")
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes on the CPU backend; no result line")
    args = ap.parse_args(argv)
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        if args.mesh:
            os.environ["XLA_FLAGS"] = (
                os.environ.get("XLA_FLAGS", "") +
                " --xla_force_host_platform_device_count=4")
    cache_dir = enable_compile_cache()
    import jax

    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    on_tpu = device["platform"] == "tpu"
    if not (on_tpu or args.rehearse):
        sys.exit(f"no TPU: JAX sees {device}; this smoke runs only on the "
                 "chip (--rehearse runs it at a tiny size on the CPU)")
    sz = (REHEARSAL[args.mesh] if args.rehearse
          else FOUR_CHIPS if args.mesh else ONE_CHIP)
    if args.mesh and len(devices) < sz.nodes:
        sys.exit(f"--mesh needs {sz.nodes} devices, JAX sees {device}")
    log(f"device: {device}; jax {jax.__version__}; compile cache "
        f"{cache_dir}")
    log(f"sizes: {sz}, q = {sz.q}, chunk {sz.chunk_words * 4} B, seed "
        f"{args.seed}")
    rng = np.random.default_rng(args.seed)
    clock = CompileClock()
    phases = Phases(clock)
    t0 = time.perf_counter()
    if args.mesh:
        for executor in ("padded", "ppermute"):
            phases.run(f"mesh.{executor}", mesh_phase, sz, rng, executor)
            gc.collect()
    else:
        from repro.core import obs
        policy = phases.run("decision", decide, sz)
        for kind in ("auto", "compacted"):
            phases.run(f"data.{kind}", data_phase, policy, kind, sz, rng,
                       on_tpu)
            gc.collect()
        picks = [(r.inputs["q"], r.choice)
                 for r in obs.GLOBAL_AUDIT.records("exchange_backend")]
        log(f"audited exchange_backend picks (q, choice): {picks}")
        mixed_and_metadata_phase(sz, rng, phases)
    log(f"smoke timings (wall s, compile s): "
        f"{[(n, round(w, 3), round(c, 3)) for n, w, c in phases.rows]}; "
        f"total {time.perf_counter() - t0:.3f} s")
    log(f"compile seconds {clock.seconds:.3f}, persistent cache hits "
        f"{clock.cache_hits}")
    log(f"peak_bytes_in_use per device: {peak_bytes(devices)}")
    if args.rehearse:
        log("rehearsal passed (CPU backend, tiny sizes): not a chip result")
        return 0
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
