"""Share of the traced window in which no operation ran on the device."""
UNIT = "%"
LAYER = "device"
MOVES = "sssp_graphs_per_s"


def read(r):
    return None if r.trace is None else 100.0 * r.trace.idle_share
