"""engine programs: device-busy milliseconds inside the benchmark's write
call spans, per write call (moves ``ckpt_GiBps``).  The call blocks on the
new state, so all of its device work lies inside its span."""
from layer_common import dev_ms


def read(run):
    return dev_ms(run.trace, ("write",))
