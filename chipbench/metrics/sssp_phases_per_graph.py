"""Phases the scheduler needs to drain one graph (program count)."""
UNIT = "phases"
LAYER = "scheduler ops"
MOVES = "sssp_graphs_per_s"


def read(r):
    c = r.counters
    return c["phases"] / c["graphs"] if c.get("graphs") else None
