"""device: percent of the traced window in which no op ran on the device
(moves ``md_kops``)."""
from layer_common import idle_share


def read(run):
    return idle_share(run.trace)
