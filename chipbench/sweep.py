"""Find the highest rate a serving cell sustains: one set-up, then one
window per rate, in one process on the chip.

    python3 -m chipbench.sweep --workload serve_qwen3_steady --seed 1 \\
        --seconds 30 --stream chat --rates 0.5 0.75 1.0 1.25 1.5

For each rate of the named stream (the mix's other streams unchanged) it
prints the rate offered, the requests and tokens completed per second, the
TTFT and TPOT percentiles and the longest client-side queue. A rate is
sustained while every request is delivered and the median request's TTFT
stays within twice its value at the lowest rate tried: past that, the
queue for a decode slot grows through the window. The cell's mix then
takes a fixed share of the highest such rate, written into its file as a
number.
"""
from __future__ import annotations

import argparse
import copy
import json
import sys
import time

import numpy as np

from chipbench import run as harness


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--stream", required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(harness.ROOT / "src"))

    from chipbench import spec, traffic
    from chipbench.common import Context, percentile, with_overrides
    from chipbench.systems import serve

    cell = spec.load_cell(args.workload, harness.ROOT)
    harness.device_info(False, cell.chips)
    harness.enable_cache()
    ctx = Context(config=with_overrides(cell.config, False),
                  traffic=with_overrides(cell.traffic, False),
                  seed=args.seed, seconds=args.seconds, log=harness.log)
    st = serve.setup(ctx)
    rows = []
    for rate in args.rates:
        mix = copy.deepcopy(ctx.traffic)
        for s in mix["streams"]:
            if s["name"] == args.stream:
                s["arrival"]["rate_per_s"] = rate
        st.arrivals = traffic.generate(mix, args.seconds,
                                       st.serve["frontends"])
        rng = np.random.default_rng(int(rate * 1000))
        st.prompts = [rng.integers(0, ctx.config["vocab_size"], a.prompt_len,
                                   dtype=np.int32) for a in st.arrivals]
        t0 = time.perf_counter()
        out = serve.window(ctx, st)
        recs = st.recs
        t_end = st.t_end
        done_in = sum(1 for r in recs if r.done_t is not None
                      and r.done_t < t_end)
        ttft = [1e3 * (r.first_t - r.due) for r in recs
                if r.first_t is not None]
        row = {"rate": rate, "offered": len(recs) / args.seconds,
               "completed_per_s": done_in / args.seconds,
               "tokens_per_s": out.counters["tokens_per_s"],
               "ttft_p50_ms": percentile(ttft, 50),
               "ttft_mean_ms": float(np.mean(ttft)),
               "ttft_p95_ms": out.counters["ttft_p95_ms"],
               "tpot_p95_ms": out.end_to_end["serve_tpot_p95_ms"],
               "undelivered": out.counters["undelivered"],
               "client_wait_max_ms": out.counters["client_wait_max_ms"],
               "wall_s": time.perf_counter() - t0}
        rows.append(row)
        print("SWEEP " + json.dumps(row), flush=True)
    base = rows[0]["ttft_p50_ms"]
    held = [r["rate"] for r in rows
            if r["undelivered"] == 0 and r["ttft_p50_ms"] <= 2 * base]
    print(json.dumps({"sweep": rows, "sustained": max(held, default=None)}),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
