"""Share of the chip's peak that the whole SSSP phase program reaches: the
least time the chip could take for one phase's work (the larger of its
bytes over peak bandwidth and its operations over peak FLOP/s;
``chipbench/flops.py``), over the phase programs' device time. It bounds
what a kernel's roofline share can claim for the phase."""
from chipbench import flops

UNIT = "%"
LAYER = "phase program"
MOVES = "sssp_graphs_per_s"
PROGRAMS = ("_phase",)


def read(r):
    if r.trace is None:
        return None
    phases, secs = r.trace.module_seconds(*PROGRAMS)
    if not phases or secs <= 0:
        return None
    c = r.counters
    b = c["graphs_per_phase_program"]
    need = max(flops.sssp_phase_bytes(b, c["n"], c["places"])
               / r.peak["hbm_bytes_per_s"],
               flops.sssp_phase_ops(b, c["n"], c["places"])
               / r.peak["bf16_flops_per_s"])
    return 100.0 * phases * need / secs
