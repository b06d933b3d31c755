"""Benchmark driver: one section per paper table/figure + framework benches.

  PYTHONPATH=src python -m benchmarks.run              # scaled defaults
  PYTHONPATH=src python -m benchmarks.run --full       # paper-scale (slow)
  PYTHONPATH=src python -m benchmarks.run --smoke      # CI budget (<2 min)
  PYTHONPATH=src python -m benchmarks.run --only fig5

The ``sharded`` section measures multi-device scaling; run it under
XLA_FLAGS=--xla_force_host_platform_device_count=8 on a CPU host (on one
device it emits a skip row).

Prints ``name,us_per_call,derived`` CSV rows per the repo convention, plus
the full row dicts to benchmarks/out/BENCH_<section>.json (the files CI
uploads as the perf-trajectory artifact).
"""
from __future__ import annotations

import argparse
import json
import os
import sys


def _emit(section: str, rows):
    os.makedirs("benchmarks/out", exist_ok=True)
    with open(f"benchmarks/out/BENCH_{section}.json", "w") as f:
        json.dump(rows, f, indent=1)
    for r in rows:
        us = r.get("us_per_call", "")
        derived = {k: v for k, v in r.items()
                   if k not in ("us_per_call",)}
        print(f"{section},{us},{json.dumps(derived)}")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="paper-scale (n=10000, P=80, 20 graphs)")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny sizes for CI (<2 min budget)")
    ap.add_argument("--only", default=None)
    args = ap.parse_args()
    if args.full and args.smoke:
        ap.error("--full and --smoke are mutually exclusive")

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()

    from benchmarks import kernels_bench, paper, roofline_table, slo_bench

    n = 10000 if args.full else (600 if args.smoke else 4000)
    graphs = 20 if args.full else 2
    sections = {
        "fig3_simulation": lambda: paper.fig3_simulation(
            n=n, graphs=graphs,
            rhos=(0, 128) if args.smoke else (0, 128, 512)),
        "fig4_scaling": lambda: paper.fig4_scaling(
            n=n, graphs=graphs,
            place_counts=(1, 2, 5, 10, 20, 40, 80) if args.full
            else ((4, 16) if args.smoke else (1, 5, 20, 80))),
        "fig5_ksweep": lambda: paper.fig5_ksweep(
            n=n, graphs=graphs,
            places=16 if args.smoke else 80,
            ks=(1, 8, 32, 128, 512, 2048) if args.full
            else ((4, 64) if args.smoke else (1, 32, 512))),
        "batched_speedup": lambda: paper.batched_speedup(
            n=2000 if args.full else (300 if args.smoke else 800),
            graphs=8 if args.full else (4 if args.smoke else 6)),
        "sharded_speedup": lambda: paper.sharded_speedup(
            n=1600 if args.full else (400 if args.smoke else 800),
            graphs=8),
        "admission": lambda: paper.admission_throughput(
            requests=5000 if args.full else (400 if args.smoke else 2000),
            repeats=1 if args.smoke else 3),
        "fused_step": lambda: paper.fused_step_throughput(
            requests=128 if args.full else (24 if args.smoke else 64),
            steps=96 if args.full else (24 if args.smoke else 48),
            chunk=16 if args.full else (6 if args.smoke else 8),
            repeats=1 if args.smoke else 3),
        "preemption": lambda: paper.preemption_useful_work(
            low=12 if args.full else (6 if args.smoke else 8),
            waves=4 if args.full else (2 if args.smoke else 3),
            steps=72 if args.full else (24 if args.smoke else 48),
            chunk=12 if args.full else (6 if args.smoke else 8),
            repeats=1 if args.smoke else 3),
        # chunk stays 8 in every mode: the CI gate compares continuous vs
        # fused at step_chunk=8 specifically
        "continuous": lambda: paper.continuous_serving(
            requests=128 if args.full else (24 if args.smoke else 64),
            steps=96 if args.full else (32 if args.smoke else 64),
            chunk=8,
            repeats=1 if args.smoke else 3),
        # the bursty §13 trace is fixed-seed (the gate compares planes on
        # THAT trace) — only the drain tail shrinks in smoke mode
        "slo": lambda: slo_bench.slo_serving(
            drain=160 if args.smoke else 240),
        "multiqueue": lambda: paper.multiqueue_section(
            n=2000 if args.full else (300 if args.smoke else 800),
            graphs=graphs,
            places=80 if args.full else (8 if args.smoke else 16),
            ks=(1, 32, 512) if args.full
            else ((4,) if args.smoke else (4, 64)),
            probe_pushes=2000 if args.full
            else (200 if args.smoke else 600),
            serve_requests=96 if args.full else (24 if args.smoke else 48),
            serve_steps=64 if args.full else (24 if args.smoke else 40),
            serve_repeats=1 if args.smoke else 2),
        # deep-capacity pop-cost sweep: the klsm:scaling gate compares the
        # two structures at the DEEPEST capacity, so keep the sweep's max
        # meaningful even in smoke mode
        "klsm": lambda: paper.klsm_section(
            capacities=(65536, 16384, 8192, 2048, 512) if args.full
            else ((2048, 512) if args.smoke else (16384, 8192, 2048, 512)),
            repeats=2 if args.smoke else 5),
        "relaxed_topk": (
            (lambda: kernels_bench.bench_relaxed_topk(n=1 << 13, p=64,
                                                      cs=(64, 8)))
            if args.smoke else kernels_bench.bench_relaxed_topk),
        "flash_attention": (
            (lambda: kernels_bench.bench_flash_attention(
                shapes=((1, 2, 256, 64),)))
            if args.smoke else kernels_bench.bench_flash_attention),
        "roofline": lambda: roofline_table.rows(),
    }
    # per-section dispatch accounting: the serve-plane classes expose a
    # monotone aggregate over instance-scoped counters (dead instances
    # included) — snapshot-delta it around every section so one section's
    # dispatches never skew another's under a multi-match --only, without
    # any shared mutable counter to corrupt
    from repro.serve.fused_step import FusedServeLoop
    from repro.serve.streaming import StreamingAdmitter

    def _serve_dispatches():
        return (StreamingAdmitter.dispatch_total()
                + FusedServeLoop.dispatch_total())

    failures = matched = 0
    for name, fn in sections.items():
        if args.only and args.only not in name:
            continue
        matched += 1
        before = _serve_dispatches()
        rows = []
        try:
            rows = fn()
            _emit(name, rows)
        except Exception as e:  # pragma: no cover
            failures += 1
            print(f"{name},ERROR,{type(e).__name__}: {e}", file=sys.stderr)
        finally:
            d = _serve_dispatches() - before
            if d:
                print(f"# {name}: {d} serve-plane device dispatches",
                      file=sys.stderr)
            # serving-plane rows: aborts/step (the §16 pop contract's
            # aborted selects — 0.0 under exact-pop policies) printed next
            # to the dispatches/step the gates judge
            for r in rows:
                if not isinstance(r, dict) or "dispatches_per_step" not in r:
                    continue
                tag = r.get("plane") or r.get("structure") or "?"
                print(f"# {name}/{tag}: {r['dispatches_per_step']} "
                      f"dispatches/step, {r.get('aborts_per_step', 0.0)} "
                      "aborts/step", file=sys.stderr)
    if args.only and not matched:
        # a typo'd --only used to silently run zero sections (and exit 0,
        # green in CI while measuring nothing) — fail loudly instead
        print(f"--only {args.only!r} matched no section; valid sections: "
              f"{', '.join(sections)}", file=sys.stderr)
        raise SystemExit(2)
    if failures:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
