"""engine programs: device-busy milliseconds inside the benchmark's read
call spans (metadata probe, data round and the client's eager planning
ops), per read call (moves ``ckpt_GiBps``)."""
from layer_common import dev_ms


def read(run):
    return dev_ms(run.trace, ("read",))
