"""client planning: device-idle milliseconds inside the benchmark's create,
stat and remove call spans, per call (moves ``md_kops``)."""
from layer_common import host_gap_ms


def read(run):
    return host_gap_ms(run.trace, ("create", "stat", "remove"))
