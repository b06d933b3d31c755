"""Model FLOP utilization of the whole serving step: the model FLOPs of
the prefills submitted and the decode tokens delivered in the window
(``chipbench/flops.py``, from the model's shapes, no padding), over the
window's seconds times the chip's peak bf16 FLOP/s."""
UNIT = "%"
LAYER = "model"
MOVES = "serve_tpot_p95_ms"


def read(r):
    c = r.counters
    if not c.get("model_flop"):
        return None
    return 100.0 * c["model_flop"] / (c["window_s"] * r.peak["bf16_flops_per_s"])
