"""client planning: device-idle milliseconds inside the benchmark's write,
read and drain call spans, per call (moves ``ckpt_GiBps``).  A call span
runs from ``encode`` to ``block_until_ready``, so what the device does not
cover in it is host work: encode, planning, syncs and dispatch."""
from layer_common import host_gap_ms


def read(run):
    return host_gap_ms(run.trace, ("write", "read", "drain"))
