"""The control: one cell run with a guarantee broken on purpose.

    python bench/control.py --workload <cell> --seed <n>[,<n>...] --seconds <s>

Several seeds, separated by commas, run one after the other in one
process, which starts the chip once; each prints its own result line.

The configurations state a lossless store.  The program has a path that
breaks exactly that: ``BBClient(exchange="compacted", lossless=False)``
with fixed per-destination budgets, whose overflow is dropped instead of
carried into a second round: the step a later change would take to save
the histogram and its host sync.  The budgets here are half the
uniform-hash expectation, ``q / (2 nodes)`` rows for data and metadata: at
the full expectation the four-chip cell's metadata fits its budgets
exactly and its reads are rescued by the stranded-data broadcast, so that
control drops nothing there.  The check has to read this one as not
correct; the benchmark's own runs never take this path.
"""
import gc
import sys

from run import main


def control_options(q: int, nodes: int) -> dict:
    b = max(1, q // (2 * nodes))
    return {"exchange": "compacted", "lossless": False, "budget": b,
            "meta_budget": b}


def run_seeds(argv) -> int:
    argv = list(argv)
    at = argv.index("--seed") + 1
    rc = 0
    for seed in argv[at].split(","):
        argv[at] = seed
        rc |= main(argv, client_options=control_options)
        gc.collect()            # the cell's tables go before the next seed
    return rc


if __name__ == "__main__":
    sys.exit(run_seeds(sys.argv[1:]))
