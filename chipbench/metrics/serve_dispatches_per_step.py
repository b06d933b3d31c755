"""Device programs the serving loop launches per engine step in the window
(``ServeEngine.dispatches``: prefills, staging batches, plan uploads and
the step programs)."""
UNIT = "dispatches/step"
LAYER = "serving loop"
MOVES = "serve_tpot_p95_ms"


def read(r):
    c = r.counters
    return c["dispatches"] / c["steps"] if c.get("steps") else None
