"""Read the numbers that decide ``correct`` over many seeds, for the program
and for its control, in one process on the chip.

    python3 -m chipbench.control --workload serve_qwen3_steady \\
        --seconds 20 --seeds 11 12 13 ...

For each seed it builds the cell as a run does, drives a short window at
the cell's own load, and prints the numbers the run compares (``checks``)
beside the control's reading of the same comparison: the reference put in
the program's place and computed one precision step below the
configuration's (float8 weights for the bfloat16 model, bfloat16
distances for the float32 graphs). The limits in the configuration files
were set between the two (``PERF.md``). Runs of the benchmark never run
the control.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time

from chipbench import run as harness


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    sys.path.insert(0, str(harness.ROOT / "src"))

    from chipbench import spec
    from chipbench.common import Context, with_overrides

    cell = spec.load_cell(args.workload, harness.ROOT)
    harness.device_info(args.rehearse, cell.chips)
    harness.enable_cache()
    drv = spec.system_module(cell.system)
    rows = []
    for seed in args.seeds:
        ctx = Context(config=with_overrides(cell.config, args.rehearse),
                      traffic=with_overrides(cell.traffic, args.rehearse),
                      seed=seed, seconds=args.seconds, log=harness.log)
        t0 = time.perf_counter()
        st = drv.setup(ctx)
        out = drv.window(ctx, st)
        drv.release(st)
        gc.collect()
        checks = drv.check(ctx, st, out)
        row = {"seed": seed, "attempted": out.attempted,
               "program": {c.name: c.value for c in checks},
               "control": drv.control(ctx, st),
               "wall_s": time.perf_counter() - t0}
        rows.append(row)
        print("CONTROL " + json.dumps(row), flush=True)
        del st, out
        gc.collect()
    print(json.dumps({"control": rows}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
