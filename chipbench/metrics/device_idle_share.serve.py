"""Share of the traced window in which no operation ran on the device."""
UNIT = "%"
LAYER = "device"
MOVES = "serve_tpot_p95_ms"


def read(r):
    return None if r.trace is None else 100.0 * r.trace.idle_share
