"""Run one benchmark cell on the chip and print its result line.

    python3 -m chipbench.run --workload <name> --seed <n> --seconds <s> --trace <0|1>

from the directory that holds ``BENCHMARK.json``. The cell's files are
found by name (``chipbench/spec.py``). The run makes its data and weights
from ``--seed``, warms up every shape it uses (that is ``setup_s``),
measures for ``--seconds``, then compares what the window produced with
the benchmark's own reference. The last line of standard output is one
JSON object: ``correct``, ``attempted``, ``failed``, ``metrics`` (the
cell's end-to-end metrics, or with ``--trace 1`` its per-layer metrics),
``device`` and, traced, ``breakdown``; its last key, ``checks``, holds each
number compared beside its limit, and the same lines end standard error.

It exits non-zero and prints no result when JAX finds no TPU, or fewer
chips than the cell asks for. ``--rehearse`` runs the same path on the CPU
at the sizes of each file's ``rehearse`` block, and prints counts and
checks but no metric: a CPU timing is not a device number.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parent.parent
# before JAX loads: the TPU runtime otherwise asks a cloud metadata server
# for the machine's topology, and waits minutes where there is none
os.environ.setdefault("TPU_SKIP_MDS_QUERY", "1")
TRACE_SECONDS = 5.0


class NoDevice(RuntimeError):
    pass


def log(msg: str) -> None:
    print(f"[chipbench] {msg}", flush=True)


def device_info(rehearse: bool, chips: int) -> dict:
    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu" and not rehearse:
        raise NoDevice(f"needs a TPU, JAX found {platform!r}")
    if len(devices) < chips:
        raise NoDevice(f"the cell needs {chips} chips, JAX found "
                       f"{len(devices)}")
    return {"platform": platform, "kind": devices[0].device_kind,
            "count": chips}


def memory_peak(chips: int):
    import jax

    peaks = []
    for d in jax.devices()[:chips]:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


def enable_cache():
    """The program's persistent compile cache (its fixed directory in the
    checkout, or ``JAX_COMPILATION_CACHE_DIR``), keeping every program so
    that only a cell's first run in a checkout compiles."""
    import jax

    from repro.launch.compile_cache import enable_compile_cache

    where = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return where


def run(workload: str, seed: int, seconds: float, trace: bool,
        rehearse: bool = False, root: pathlib.Path = ROOT,
        t_start: float = T_START) -> dict:
    """One run of one cell; returns the result object (raises NoDevice)."""
    from chipbench import spec
    from chipbench.common import Context, with_overrides
    from chipbench.tracing import CompileCounter, Tracer

    cell = spec.load_cell(workload, root)
    device = device_info(rehearse, cell.chips)
    cache = enable_cache()
    counter = CompileCounter()
    drv = spec.system_module(cell.system)
    ctx = Context(config=with_overrides(cell.config, rehearse),
                  traffic=with_overrides(cell.traffic, rehearse),
                  seed=seed, seconds=seconds, log=log)
    log(f"{workload}: device {device} ready {time.perf_counter() - t_start:.3f}"
        f" s after start, compile cache {cache}, seed {seed}")
    state = drv.setup(ctx)
    # what set-up built stays alive through the window: keep the collector
    # from walking it, and from running at all inside the window (a full
    # collection of a JAX process's heap stalls the host for up to seconds;
    # the window frees its garbage by reference counting)
    gc.collect()
    gc.freeze()
    setup_s = time.perf_counter() - t_start
    before = counter.snapshot()
    tmp = tempfile.TemporaryDirectory(prefix="chipbench-trace-")
    try:
        if trace and not rehearse:      # a CPU trace has no device plane
            ctx.tracer = Tracer(tmp.name, min(TRACE_SECONDS, seconds),
                                seconds)
            ctx.tracer.arm(time.perf_counter())
        gc.disable()
        try:
            out = drv.window(ctx, state)
        finally:
            gc.enable()
        if ctx.tracer is not None:
            ctx.tracer.stop()
        after = counter.snapshot()
        inside = {k: after[k] - before[k] for k in after}
        log(f"set-up {setup_s:.3f} s ({before['compiles']} programs "
            f"compiled or loaded, {before['cache_loads']} of them from the "
            f"cache); inside the window: {inside}")
        if inside["compiles"] or inside["cache_loads"]:
            print(f"chipbench: ERROR: {inside['compiles']} compiles and "
                  f"{inside['cache_loads']} cache loads inside the window",
                  file=sys.stderr, flush=True)
        device["memory_peak_bytes"] = memory_peak(cell.chips)
        drv.release(state)
        gc.collect()
        summary = ctx.tracer.reduce() if ctx.tracer is not None else None
    finally:
        tmp.cleanup()
    t0 = time.perf_counter()
    checks = drv.check(ctx, state, out)
    log(f"reference comparison in {time.perf_counter() - t0:.3f} s")
    correct = all(c.ok for c in checks)
    result = {"correct": correct, "attempted": int(out.attempted),
              "failed": int(out.failed)}
    if rehearse:
        result["rehearsal"] = True
        result["counts"] = out.counters
    elif trace:
        result["metrics"] = per_layer(cell, ctx, out, summary, device)
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
    else:
        metrics = {m.name: {"value": out.end_to_end[m.name], "unit": m.unit}
                   for m in cell.end_to_end if m.name != "setup_s"}
        metrics["setup_s"] = {"value": setup_s, "unit": "s"}
        result["metrics"] = metrics
    result["device"] = device
    if summary is not None and not rehearse:
        result["breakdown"] = summary.breakdown()
    result["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                        for c in checks}
    return result


def per_layer(cell, ctx, out, summary, device) -> dict:
    """Each per-layer metric of the cell, read by its own reader; a reader
    that finds nothing to read leaves its metric out."""
    from chipbench import spec
    from chipbench.metrics import Readings

    r = Readings(counters=out.counters, trace=summary, config=ctx.config,
                 traffic=ctx.traffic, peak=spec.peaks()[device["kind"]])
    got = {}
    for m in cell.per_layer:
        value = spec.metric_reader(m.name).read(r)
        if value is not None:
            got[m.name] = {"value": value, "unit": m.unit}
    return got


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU rehearsal at the files' rehearse sizes")
    args = ap.parse_args(argv)
    src = ROOT / "src"
    if not (src / "repro").is_dir():
        print(f"chipbench: the program is not in {src}", file=sys.stderr)
        return 2
    sys.path.insert(0, str(src))
    if args.rehearse:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
    try:
        result = run(args.workload, args.seed, args.seconds, bool(args.trace),
                     rehearse=args.rehearse)
    except NoDevice as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 2
    for name, c in result["checks"].items():
        ok = "ok" if c["value"] <= c["limit"] else "FAILED"
        print(f"check {name}: {c['value']!r} limit {c['limit']!r} {ok}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
