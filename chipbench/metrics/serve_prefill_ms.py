"""Device time of one prefill program run (one request's prompt), from the
trace."""
UNIT = "ms"
LAYER = "model"
MOVES = "serve_ttft_mean_ms"
PROGRAMS = ("prefill",)


def read(r):
    if r.trace is None:
        return None
    count, secs = r.trace.module_seconds(*PROGRAMS)
    return 1e3 * secs / count if count else None
