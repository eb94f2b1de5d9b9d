"""Per-program device memory of one cell, as the compiler counts it.

    python bench/memory_report.py --workload <cell> --seed <n>

Runs the cell's set-up and one round, records every distinct engine
program the round called (write, read, probe, meta) with its argument
shapes, and prints ``compiled.memory_analysis()`` of each: argument,
output and temporary bytes.  Beside the chip's ``peak_bytes_in_use`` it
says how the node tables were sized; it is not part of a benchmark run.
"""
import sys

import run


def main(argv=None) -> int:
    calls = {}

    def record(cell):
        client = cell.client
        ops, probe = client._ops, client._probe_op

        def keep(kind, cfg, fn):
            def wrapped(*args):
                calls.setdefault((kind, cfg), (fn, args))
                return fn(*args)
            return wrapped

        def ops_rec(cfg):
            w, r, m, rl = ops(cfg)
            return (keep("write", cfg, w), keep("read", cfg, r),
                    keep("meta", cfg, m), keep("read_loc", cfg, rl))

        client._ops = ops_rec
        client._probe_op = lambda cfg: keep("probe", cfg, probe(cfg))

    args = run.parse(argv)
    import os
    (run.ROOT / ".jax_cache").mkdir(exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(run.ROOT / ".jax_cache")
    from cell import Cell
    cell = Cell(run.ROOT, args.workload, args.seed, args.rehearse)
    cell.setup()
    record(cell)
    cell.run_round()
    gib = 2.0 ** 30
    for (kind, cfg), (fn, a) in sorted(calls.items(), key=lambda kv:
                                       kv[0][0]):
        m = fn.lower(*a).compile().memory_analysis()
        run.log(f"{kind}: argument {m.argument_size_in_bytes / gib:.3f} GiB"
                f", output {m.output_size_in_bytes / gib:.3f} GiB, "
                f"temporaries {m.temp_size_in_bytes / gib:.3f} GiB "
                f"(spec {cfg.data_spec}, {cfg.meta_spec})")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:] + ["--seconds", "0"]))
