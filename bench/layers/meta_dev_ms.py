"""engine programs: device-busy milliseconds inside the benchmark's
create, stat and remove call spans, per call (moves ``md_kops``)."""
from layer_common import META_OPS, dev_ms


def read(run):
    return dev_ms(run.trace, META_OPS)
