"""Device time of the SSSP phase programs per phase program run (one joint
phase of all the graphs of a call), from the trace."""
UNIT = "us"
LAYER = "phase program"
MOVES = "sssp_graphs_per_s"
PROGRAMS = ("_phase",)          # jit__phase, jit__phase_chunk


def read(r):
    if r.trace is None:
        return None
    count, secs = r.trace.module_seconds(*PROGRAMS)
    return 1e6 * secs / count if count else None
