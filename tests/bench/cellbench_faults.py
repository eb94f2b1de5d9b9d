"""Drive a rehearsal of one cell with a fault planted under the timed path.

    python tests/bench/cellbench_faults.py <cell> <fault> [<fault> ...]

Each fault runs the cell once, in this process, and prints
``FAULT <fault> <result line>``.

Faults (each one the check must read as not correct):

- ``unchanged``: the engine's mutating programs return the state they were
  given (writes and metadata ops store nothing);
- ``half``: the second half of every request batch is left out;
- ``altered``: a word of every write payload, of every read answer and of
  every stat size is changed where the engine produces it;
- ``exchange``: the exchange between chips is left out (a mesh cell):
  ``all_to_all`` and the ``ppermute`` shift rounds return each chip's own
  buffer;
- ``control``: the control of ``bench/control.py``, the program's own
  lossy fixed-budget exchange.
"""
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "bench"), str(ROOT / "src")]

import run  # noqa: E402


def _half(v):
    return v.at[:, v.shape[1] // 2:].set(False)


def wrap_ops(cell, fault: str) -> None:
    client = cell.client
    orig = client._ops

    def bump(x, idx):
        import jax.numpy as jnp
        return x + jnp.zeros(x.shape, x.dtype).at[idx].set(1)

    def ops(cfg):
        write, read, meta, read_loc = orig(cfg)
        if fault == "unchanged":
            return (lambda st, *a: st, read,
                    lambda st, *a: (st,) + tuple(meta(st, *a)[1:]), read_loc)
        if fault == "half":
            return (lambda st, m, ph, cid, pay, v:
                    write(st, m, ph, cid, pay, _half(v)),
                    read,
                    lambda st, m, op, ph, s, loc, v:
                    meta(st, m, op, ph, s, loc, _half(v)),
                    lambda st, m, ph, cid, v, dl:
                    read_loc(st, m, ph, cid, _half(v), dl))

        def alt_write(st, m, ph, cid, pay, v):
            return write(st, m, ph, cid, bump(pay, (0, 0, -1)), v)

        def alt_meta(*a):
            st, found, size, loc = meta(*a)
            return st, found, bump(size, (0, 0)), loc

        def alt_read(*a):
            pay, found = read_loc(*a)
            return bump(pay, (0, 0, -1)), found

        return alt_write, read, alt_meta, alt_read

    client._ops = ops


def cut_exchange():
    """From here on, the mesh programs built leave out the exchange between
    chips: each chip keeps the buffer it would have sent.  Returns the
    undo."""
    from repro.core import mesh_engine
    saved = mesh_engine.mesh_exchange, mesh_engine.build_mesh_shift

    def undo():
        mesh_engine.mesh_exchange, mesh_engine.build_mesh_shift = saved

    mesh_engine.mesh_exchange = lambda x: x
    mesh_engine.build_mesh_shift = lambda n_dev: lambda x, k: x
    return undo


def run_fault(cell: str, fault: str) -> str:
    """One rehearsal of ``cell`` with ``fault``; its result line."""
    import contextlib
    import io
    argv = ["--workload", cell, "--seed", "7", "--seconds", "0.5",
            "--rehearse"]
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        if fault == "control":
            from control import control_options
            run.main(argv, client_options=control_options)
        elif fault == "exchange":
            # after set-up, which starts JAX on the rehearsal's devices;
            # the client builds its programs in the warm-up round
            undo = []
            try:
                run.main(argv, patch=lambda c: undo.append(cut_exchange()))
            finally:
                for u in undo:
                    u()
        else:
            run.main(argv, patch=lambda c: wrap_ops(c, fault))
    return out.getvalue().strip().splitlines()[-1]


def main(cell: str, *faults: str) -> int:
    for fault in faults:
        print(f"FAULT {fault} {run_fault(cell, fault)}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(*sys.argv[1:]))
