"""One benchmark cell: set-up, warm-up, the measured window and the check.

A cell is a deployment (``bench/configs/<config>.json``) under a traffic
mix (``bench/traffic/<traffic>.json``).  Set-up runs the decision layer on
the mix's Table-I job, builds a ``BBClient`` on the policy it returns (every
option but the sizes at its default), makes the payload pool on the device
from the seed, and runs one whole round, which builds every program.  The
configuration's ``backend`` picks the data plane: ``"stacked"`` holds every
node table on one chip; ``"mesh"`` spreads the nodes over a 1-D mesh of the
cell's ``chips`` (``mesh_engine.make_node_mesh``), and the tables, the
payload pool and every answer are sharded over its node axis, so each
node's ranks write from their own chip.  The
window then drives the same round plan for ``seconds``, closed loop: each
call is ``encode`` plus one client op, ended by ``block_until_ready``.  A
drain ends every round of a data mix: a ``remove`` of the round's files and
a fresh data table made on the device (the stand-in for stage-out), whose
checksum of the table it empties is kept for the check.

Every answer, from set-up on, is kept on the device and compared with the
host reference (``reference.py``) after the window has closed.
"""
from __future__ import annotations

import dataclasses
import json
import time
from pathlib import Path
from typing import Dict, List, Optional

import numpy as np

import generator
import reference
from generator import STAMP_WORDS, Mix

BENCH = Path(__file__).resolve().parent
#: read outputs kept whole for the byte-for-byte comparison
FULL_READS = 4


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def cell_spec(root: Path, name: str) -> dict:
    """The ``workloads`` entry of ``name`` in ``BENCHMARK.json``."""
    bench = load_json(root / "BENCHMARK.json")
    for cell in bench["workloads"]:
        if cell["name"] == name:
            return cell
    raise SystemExit(f"no workload {name!r} in BENCHMARK.json; known: "
                     f"{[c['name'] for c in bench['workloads']]}")


class CompileClock:
    """Executables built (compiled or loaded from the persistent cache) and
    compile seconds, from JAX's own monitoring events.  Copied from
    ``chip_smoke.py``, with the count of builds added."""

    def __init__(self):
        import jax
        self.seconds = 0.0
        self.builds = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration
            self.builds += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


def _digest_weights(words: int):
    import jax.numpy as jnp
    i = jnp.arange(words, dtype=jnp.uint32)
    return (2 * i + 1) * jnp.uint32(0x9E3779B1)


def _make_pool(seed, *, slots: int, shape, sharding):
    """``slots`` payload blocks of random int32 words, stamp words zero, in
    one jitted call on the device."""
    import jax
    import jax.numpy as jnp

    def make(s):
        key = jax.random.key(s)
        out = []
        for p in range(slots):
            bits = jax.random.bits(jax.random.fold_in(key, p), shape,
                                   jnp.uint32)
            bits = bits.at[..., :STAMP_WORDS].set(0)
            out.append(jax.lax.bitcast_convert_type(bits, jnp.int32))
        return tuple(out)

    return list(jax.jit(make, out_shardings=(sharding,) * slots)(
        jnp.uint32(seed)))


@dataclasses.dataclass
class Record:
    """One answered call, in call order (device arrays until the check)."""
    op: str
    call: Optional[generator.Call]
    rnd: int
    out: tuple = ()
    slot: int = -1                      # payload slot of a write
    full: object = None                 # whole read payload, if sampled
    call_s: float = 0.0                 # host seconds of the timed call


class Cell:
    def __init__(self, root: Path, name: str, seed: int, rehearse: bool,
                 client_options: Optional[dict] = None):
        self.name = name
        self.spec = cell_spec(root, name)
        self.config = load_json(BENCH / "configs" /
                                f"{self.spec['config']}.json")
        self.traffic = load_json(BENCH / "traffic" /
                                 f"{self.spec['traffic']}.json")
        if rehearse:
            self.config = {**self.config, **self.config["rehearse"]}
            self.traffic = {**self.traffic, **self.traffic["rehearse"]}
        self.seed = seed
        self.rehearse = rehearse
        c = self.config
        self.nodes, self.words = int(c["nodes"]), int(c["words"])
        self.cap, self.mcap = int(c["cap"]), int(c["mcap"])
        self.mix = Mix(self.traffic, nodes=self.nodes,
                       ranks_per_node=int(c["ranks_per_node"]),
                       cap=self.cap, seed=seed)
        opts = client_options or {}
        self.client_options = (opts(self.mix.q, self.nodes) if callable(opts)
                               else opts)
        self.records: List[Record] = []
        self.rng = np.random.default_rng([seed, 1])
        self.reads_seen = 0
        self.kept: List[Record] = []        # reads kept whole
        self.rnd = 0
        self.pos = 0
        self.writes = 0
        self.host: Dict[str, float] = {}

    # ---- set-up -----------------------------------------------------------
    def setup(self) -> None:
        import jax
        from repro.core.client import BBClient
        from repro.core.intent.selector import select_layout
        from repro.core.layouts import LayoutMode
        from repro.core.workloads import workload_by_name
        job = workload_by_name(self.traffic["job"], n_nodes=self.nodes)
        t0 = time.perf_counter()
        decision = select_layout(job)
        self.policy = decision.layout_policy(n_nodes=self.nodes)
        self.host["decide_s"] = time.perf_counter() - t0
        self.mode = decision.mode
        self.records_loc = LayoutMode.HYBRID in self.policy.modes_present()
        t1 = time.perf_counter()
        backend = self.config["backend"]
        if backend == "mesh":
            from repro.core.mesh_engine import make_node_mesh
            backend = make_node_mesh(int(self.spec["chips"]))
        self.client = BBClient(self.policy, backend,
                               cap=self.cap, words=self.words,
                               mcap=self.mcap, **self.client_options)
        jax.block_until_ready(self.client.state)
        self._jit_helpers()
        t2 = time.perf_counter()
        self.host["tables_s"] = t2 - t1
        self.pool = []
        if self.mix.pool:
            # the data table's sharding: one chip for the stacked tables,
            # the node axis of the mesh, so that a rank's buffer lives on
            # its own node
            self.pool = _make_pool(
                int(np.random.default_rng(self.seed).integers(0, 2**31)),
                slots=self.mix.pool,
                shape=(self.nodes, self.mix.q, self.words),
                sharding=self.client.state.data.sharding)
            jax.block_until_ready(self.pool)
        self.host["pool_s"] = time.perf_counter() - t2

    def _jit_helpers(self) -> None:
        import jax
        import jax.numpy as jnp
        st = self.client.state
        words = self.words

        def stamp(buf, stamps):
            return buf.at[..., :STAMP_WORDS].set(stamps)

        def drain(data, keys, count):
            w = _digest_weights(words)
            u = jax.lax.bitcast_convert_type(data, jnp.uint32)
            digest = (u * w).sum(axis=(1, 2), dtype=jnp.uint32)
            return (count, digest, jnp.zeros_like(data),
                    jnp.full_like(keys, -1), jnp.zeros_like(count))

        # the new stamps keep the pool's sharding: XLA propagates the
        # donated buffer's
        self._stamp = jax.jit(stamp, donate_argnums=0)
        cs, ds, ks = (st.data_count.sharding, st.data.sharding,
                      st.data_keys.sharding)
        self._drain = jax.jit(drain, donate_argnums=(0, 1, 2),
                              out_shardings=(cs, cs, ds, ks, cs))
        self._head = jax.jit(lambda p: p[..., :STAMP_WORDS])

    # ---- one call ---------------------------------------------------------
    def step(self, times: Optional[Dict[str, list]] = None) -> None:
        """Issue the next call of the round plan (a drain at round end)."""
        import jax
        from jax.profiler import TraceAnnotation
        if self.pos == len(self.mix.calls):
            if self.mix.drain:
                self._drain_round(times)
            self.rnd += 1
            self.pos = 0
            return
        call = self.mix.calls[self.pos]
        self.pos += 1
        op, client = call.op, self.client
        rec = Record(op, call, self.rnd)
        payload = None
        if op == "write":
            rec.slot = self.writes % len(self.pool)
            self.writes += 1
            self.pool[rec.slot] = self._stamp(self.pool[rec.slot],
                                              self.mix.stamps(call, self.rnd))
            payload = self.pool[rec.slot]
        t0 = time.perf_counter()
        with TraceAnnotation(f"bench.call.{op}"):
            with TraceAnnotation("bench.encode"):
                req = client.encode(call.paths, chunk_id=call.cids,
                                    payload=payload)
            t1 = time.perf_counter()
            if op == "write":
                client.write(req)
                out = ()
                jax.block_until_ready(client.state)
            else:
                out = getattr(client, op)(req)
                out = out if isinstance(out, tuple) else (out,)
                jax.block_until_ready(out)
        t2 = time.perf_counter()
        rec.call_s = t2 - t0
        if times is not None:
            times.setdefault(op, []).append(t2 - t0)
            times.setdefault("encode." + op, []).append(t1 - t0)
        if op == "read":
            payload_out, found = out
            rec.out = (self._head(payload_out), found)
            self._sample_read(rec, payload_out)
        else:
            rec.out = out
        self.records.append(rec)

    def _sample_read(self, rec: Record, payload) -> None:
        """Reservoir sample, drawn from the seed, of whole read outputs."""
        k = self.reads_seen
        self.reads_seen += 1
        if len(self.kept) < FULL_READS:
            j = len(self.kept)
            self.kept.append(rec)
        else:
            j = int(self.rng.integers(0, k + 1))
            if j >= FULL_READS:
                return
            self.kept[j].full = None
            self.kept[j] = rec
        rec.full = payload

    def _drain_round(self, times) -> None:
        import jax
        from jax.profiler import TraceAnnotation
        client = self.client
        t0 = time.perf_counter()
        with TraceAnnotation("bench.call.drain"):
            with TraceAnnotation("bench.encode"):
                req = client.encode(self.mix.round_files)
            found = client.remove(req)
            st = client.state
            count, digest, data, keys, cnt = self._drain(
                st.data, st.data_keys, st.data_count)
            client.state = dataclasses.replace(st, data=data, data_keys=keys,
                                               data_count=cnt)
            jax.block_until_ready((found, client.state, count, digest))
        dt = time.perf_counter() - t0
        if times is not None:
            times.setdefault("drain", []).append(dt)
        self.records.append(Record("drain", None, self.rnd,
                                   (found, count, digest), call_s=dt))

    def exchange_plans(self) -> List[str]:
        """The exchanges of the mesh programs the client has built: the
        kind, and the executor that each measured plan picked for data and
        for metadata (``uniform``: no measured plan).  Empty for the
        stacked backend."""
        def executor(spec):
            return getattr(spec, "executor", "uniform")
        cfgs = {**self.client._mesh_ops, **self.client._mesh_probe}
        return sorted({f"{c.kind} data {executor(c.data_spec)} meta "
                       f"{executor(c.meta_spec)}" for c in cfgs})

    def run_round(self) -> None:
        start = self.rnd
        while self.rnd == start:
            self.step()

    # ---- after the window -------------------------------------------------
    def final_stage_out(self) -> None:
        """The checksum of the table the window left, outside any timing."""
        if not self.mix.drain:
            return
        st = self.client.state
        count, digest, *_ = self._drain(st.data, st.data_keys, st.data_count)
        self.client.state = None
        self.records.append(Record("stage_out", None, self.rnd,
                                   (None, count, digest)))

    def dropped(self) -> int:
        return int(np.asarray(self.client.state.dropped).sum())

    def check(self) -> Dict[str, int]:
        """Replay every recorded answer against the host reference."""
        import jax
        pool = [np.asarray(p) for p in self.pool]
        self.pool = []
        host = jax.device_get([(r.out, r.full) for r in self.records])
        chk = reference.Checker(self.records_loc, pool)
        for rec, (out, full) in zip(self.records, host):
            call = rec.call
            if rec.op == "write":
                chk.write(call.paths, call.cids,
                          self.mix.stamps(call, rec.rnd), rec.slot)
            elif rec.op == "read":
                chk.read(call.paths, call.cids, out[0], out[1], full)
            elif rec.op == "create":
                chk.create(call.paths, out[0])
            elif rec.op == "stat":
                chk.stat(call.paths, *out)
            elif rec.op == "remove":
                chk.remove(call.paths, out[0])
            else:                               # drain / final stage-out
                if rec.op == "drain":
                    chk.remove(self.mix.round_files, out[0])
                chk.stage_out(int(np.sum(out[1])),
                              int(np.sum(out[2].astype(np.uint64)) % 2**32))
        return chk.counts
