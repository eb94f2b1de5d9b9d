"""decision: host-clock seconds of ``select_layout`` on the cell's Table-I
job in set-up (moves ``setup_s``)."""


def read(run):
    return run.host.get("decide_s")
