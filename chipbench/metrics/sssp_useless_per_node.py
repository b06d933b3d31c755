"""Relaxations of nodes that were not yet settled, per node of the graph
(``SSSPRun.useless`` / n): the work that relaxation buys."""
UNIT = "ratio"
LAYER = "scheduler ops"
MOVES = "sssp_graphs_per_s"


def read(r):
    c = r.counters
    return c["useless"] / (c["graphs"] * c["n"]) if c.get("graphs") else None
