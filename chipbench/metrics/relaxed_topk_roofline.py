"""Share of its roofline that the stage-1 selection kernel reaches: the
least time the chip could take for the selection each phase needs (read
every pool priority once, write the winners; ``chipbench/flops.py``), over
the kernel's device time, from the trace. Bandwidth bounds it."""
from chipbench import flops

UNIT = "%"
LAYER = "kernels"
MOVES = "sssp_graphs_per_s"
KERNEL = ("tpu_custom_call",)   # the one Pallas kernel of the phase program
PROGRAMS = ("_phase",)


def read(r):
    if r.trace is None:
        return None
    _, kernel_s = r.trace.op_seconds(*KERNEL)
    phases, _ = r.trace.module_seconds(*PROGRAMS)
    if kernel_s <= 0 or not phases:
        return None
    c = r.counters
    b = c["graphs_per_phase_program"]
    need = max(flops.relaxed_topk_bytes(b, c["n"], c["places"])
               / r.peak["hbm_bytes_per_s"],
               flops.relaxed_topk_ops(b, c["n"]) / r.peak["bf16_flops_per_s"])
    return 100.0 * phases * need / kernel_s
