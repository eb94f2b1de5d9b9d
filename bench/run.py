"""Run one benchmark cell once and print its result line.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``workloads`` in ``BENCHMARK.json``; its
configuration and traffic mix are the files of those names under
``bench/configs/`` and ``bench/traffic/`` (``cell.py``).  Set-up (import,
decision, tables, payload pool, one warm-up round) is
timed from process start to the first timed call; then the window runs for
``--seconds``, closed loop.  With ``--trace 0`` the result carries the
cell's end-to-end metrics; with ``--trace 1`` the window runs under the
profiler and the result carries the per-layer metrics, each read by
``bench/layers/<metric>.py`` from the trace.

After the window every answer is compared with the host reference; each
compared number is printed beside its limit, as the last lines on standard
error and under ``checks``, the last key of the result line, which is the
last line on standard output.

Without a TPU, or with fewer chips than the cell asks for, the run fails
and prints no result.  ``--rehearse`` runs the same cell at the tiny sizes
of its files' ``rehearse`` blocks on the CPU; its result line has no
metric, only the names it would report.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"
sys.path[:0] = [str(BENCH), str(ROOT / "src")]

from cell import Cell, CompileClock, cell_spec, load_json  # noqa: E402

PCT = 95


def log(msg: str) -> None:
    print(msg, flush=True)


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes on the CPU backend; no metric")
    ap.add_argument("--trace-out", default=None,
                    help="copy the traced window's .xplane.pb here")
    return ap.parse_args(argv)


class RunView:
    """What a per-layer reader may use: the trace of the window, readings
    of the host clock in set-up, and the chip's peaks."""

    def __init__(self, trace, host, peaks):
        self.trace, self.host, self.peaks = trace, host, peaks


def read_layer(name: str, view: RunView):
    spec = importlib.util.spec_from_file_location(
        f"layer_{name}", BENCH / "layers" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(view)


def reported(metrics, cell_name: str, e2e_names=None):
    """Names of the metrics in ``metrics`` that this cell reports."""
    out = []
    for m in metrics:
        if "workloads" in m:
            if cell_name in m["workloads"]:
                out.append(m)
        elif e2e_names is None or m["moves"] in e2e_names:
            out.append(m)
    return out


class GcClock:
    """Host seconds and count of the collector's passes while open."""

    def __init__(self):
        self.seconds, self.passes, self._t = 0.0, 0, 0.0
        gc.callbacks.append(self._cb)

    def _cb(self, phase, info):
        if phase == "start":
            self._t = time.perf_counter()
        else:
            self.seconds += time.perf_counter() - self._t
            self.passes += 1

    def close(self):
        gc.callbacks.remove(self._cb)


def window_host_line(window_s, steps, pauses) -> str:
    """Where the window's host time went outside the timed calls: the
    window minus its calls, the longest stretch of one step outside its
    call and the op of that step, and the collector's passes."""
    outside = [(dt - sum(r.call_s for r in recs),
                recs[0].op if recs else "round end") for dt, recs in steps]
    calls = sum(r.call_s for _, recs in steps for r in recs)
    worst, op = max(outside, default=(0.0, "none"))
    return (f"window host time outside calls: {window_s - calls:.3f} s of "
            f"{window_s:.3f} s over {len(steps)} steps; longest "
            f"{1e3 * worst:.3f} ms (step of {op}); garbage collector "
            f"{pauses.passes} passes, {pauses.seconds:.3f} s")


def main(argv=None, client_options=None, patch=None) -> int:
    args = parse(argv)
    spec = cell_spec(ROOT, args.workload)
    chips = int(spec["chips"])
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        if chips > 1 and "jax" not in sys.modules:
            os.environ["XLA_FLAGS"] = (
                os.environ.get("XLA_FLAGS", "") +
                f" --xla_force_host_platform_device_count={chips}")
    # the persistent compile cache lives at one fixed path in the checkout,
    # whatever the environment names, so that only a checkout's first run
    # of a cell compiles; every program is cached, however fast it compiled
    # (JAX writes no entry into a directory that does not exist)
    (ROOT / ".jax_cache").mkdir(exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    from repro.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    import jax
    jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    devices = jax.devices()
    init_s = time.perf_counter() - T_START
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    if not args.rehearse:
        if device["platform"] != "tpu":
            sys.exit(f"no TPU: JAX sees {device}; the benchmark runs only on "
                     "the chip (--rehearse runs a cell tiny on the CPU)")
        if len(devices) < chips:
            sys.exit(f"{args.workload} needs {chips} chips, JAX sees "
                     f"{device}")
    from peaks import peaks_for
    peaks = None if args.rehearse else peaks_for(device["kind"])
    bench = load_json(ROOT / "BENCHMARK.json")
    log(f"device: {device}; jax {jax.__version__}; compile cache "
        f"{cache_dir}")

    clock = CompileClock()
    cell = Cell(ROOT, args.workload, args.seed, args.rehearse,
                client_options)
    cell.setup()
    log(f"cell {args.workload}: {cell.mix.spec['job']} -> mode "
        f"{int(cell.mode)} {cell.mode.name}; {cell.nodes} nodes, q "
        f"{cell.mix.q} per node, {len(cell.mix.calls)} calls per round"
        f"{' + drain' if cell.mix.drain else ''}; cap {cell.cap}, mcap "
        f"{cell.mcap}, chunk {4 * cell.words} B; decide "
        f"{cell.host['decide_s']:.3f} s")
    if patch is not None:
        patch(cell)
    # one whole round builds every program the window uses: each call of a
    # round recurs in every round, the client's ragged spec floors reach
    # their maximum within it, and a round ends with its drain
    t_warm = time.perf_counter()
    cell.run_round()
    warm_s = time.perf_counter() - t_warm
    log(f"warm-up: 1 round; executables built {clock.builds} "
        f"(persistent-cache hits {clock.cache_hits}), compile "
        f"{clock.seconds:.3f} s")
    if cell.exchange_plans():
        log(f"mesh exchange plans built: "
            f"{'; '.join(cell.exchange_plans())}")
    log(f"set-up: import and chip init {init_s:.3f} s, decision "
        f"{cell.host['decide_s']:.3f} s, tables and helpers "
        f"{cell.host['tables_s']:.3f} s, payload pool "
        f"{cell.host['pool_s']:.3f} s, warm-up round {warm_s:.3f} s")

    times: dict = {}
    first = len(cell.records)
    builds0 = clock.builds
    setup_s = time.perf_counter() - T_START
    from jax.profiler import TraceAnnotation
    capture = None
    if args.trace:
        from tracing import Capture
        capture = Capture().__enter__()
    # set-up's objects leave the collector's view, so that no full
    # collection over them stalls a call inside the window
    gc.collect()
    gc.freeze()
    pauses = GcClock()
    steps = []
    t0 = time.perf_counter()
    with TraceAnnotation("bench.window"):
        while (t := time.perf_counter()) - t0 < args.seconds:
            n = len(cell.records)
            cell.step(times)
            steps.append((time.perf_counter() - t, cell.records[n:]))
    window_s = time.perf_counter() - t0
    pauses.close()
    gc.unfreeze()
    trace = None
    if capture is not None:
        capture.__exit__(None, None, None)
        trace = capture.load(args.trace_out)
    in_window = clock.builds - builds0
    window_recs = cell.records[first:]
    log(f"window: {window_s:.3f} s, {len(window_recs)} calls, "
        f"{cell.rnd} rounds so far; executables built inside the window: "
        f"{in_window}")
    if in_window:
        log(f"WARNING: {in_window} executables were built inside the window")

    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices[:chips])
    log(f"memory: peak_bytes_in_use on the fullest chip {peak}")
    dropped = cell.dropped()
    cell.final_stage_out()

    calls = {}
    rows = {}
    per_call = cell.nodes * cell.mix.q
    for r in window_recs:
        calls[r.op] = calls.get(r.op, 0) + 1
        if r.op != "drain":
            rows[r.op] = rows.get(r.op, 0) + per_call
    attempted = sum(rows.values()) + calls.get("drain", 0) * \
        cell.nodes * len(cell.mix.round_files[0])

    found_rows = sum(int(jax.device_get(r.out[1]).sum())
                     for r in window_recs if r.op == "read")
    chunk = 4 * cell.words
    values = {
        "setup_s": setup_s,
        "ckpt_GiBps": ((rows.get("write", 0) + found_rows) * chunk /
                       window_s / 2**30),
        "md_kops": (sum(rows.get(op, 0) for op in
                        ("create", "stat", "remove")) / window_s / 1e3),
    }
    import numpy as np
    if times.get("write"):
        values["write_p95_ms"] = 1e3 * float(np.percentile(times["write"],
                                                           PCT))
    md = sum((times.get(op, []) for op in ("create", "stat", "remove")), [])
    if md:
        values["md_p95_ms"] = 1e3 * float(np.percentile(md, PCT))
    for op, ts in sorted(times.items()):
        log(f"host clock {op}: n {len(ts)}, median "
            f"{1e3 * float(np.median(ts)):.3f} ms, p{PCT} "
            f"{1e3 * float(np.percentile(ts, PCT)):.3f} ms")
    log(window_host_line(window_s, steps, pauses))

    counts = cell.check()
    checks = {"dropped": dropped, **counts}
    failed = dropped + sum(counts.values())

    metrics = {}
    breakdown = None
    e2e = reported(bench["end_to_end"], args.workload)
    if args.trace:
        view = RunView(trace, cell.host, peaks)
        wanted = reported(bench["per_layer"], args.workload,
                          {m["name"] for m in e2e})
        if not args.rehearse:
            for m in wanted:
                v = read_layer(m["name"], view)
                if v is not None:
                    metrics[m["name"]] = {"value": v, "unit": m["unit"]}
            device["busy_s"] = trace.busy_s
            device["window_s"] = trace.window_s
            breakdown = {"device_ops": trace.top_ops(),
                         "idle_gaps": trace.idle_gaps()}
        else:
            log(f"rehearsal trace: {len(trace.spans)} benchmark spans, "
                f"window {trace.window_s:.3f} s")
    else:
        wanted = e2e
        if not args.rehearse:
            for m in wanted:
                metrics[m["name"]] = {"value": values[m["name"]],
                                      "unit": m["unit"]}
    device["memory_peak_bytes"] = peak
    correct = all(v <= 0 for v in checks.values())
    result = {"correct": correct, "attempted": attempted, "failed": failed,
              "metrics": metrics, "device": device}
    if args.rehearse:
        result["would_report"] = [m["name"] for m in wanted]
        log("rehearsal (CPU backend, tiny sizes): not a chip result")
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = {k: {"value": v, "limit": 0}
                        for k, v in checks.items()}
    for k, v in checks.items():
        print(f"check {k}: {v} limit 0", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
