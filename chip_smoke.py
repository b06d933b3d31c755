#!/usr/bin/env python3
"""Chip smoke: drive the scheduler's main paths once on a TPU, at real size.

  python chip_smoke.py [--seed 0]      # one chip: the sssp and serve phases
  python chip_smoke.py --chips 4       # four chips: the multi-device paths only

One chip:

* **sssp** — the paper's own deployment (§5.2): ``run_sssp`` on an
  Erdős–Rényi graph, n = 10 000, p = 0.5 (dense f32 weights, 400 MB of
  HBM), P = 80 places, under IDEAL, CENTRALIZED, HYBRID and WORK_STEALING
  (the fig5 k = 32 for CENTRALIZED/HYBRID, k = 1 otherwise), on the compiled
  ``relaxed_topk`` kernel. Each run must be ``correct`` against
  ``dijkstra_ref`` with ``max_ignored <= rho_bound``, and the compiled phase
  program must hold the kernel (``tpu_custom_call``). Then
  ``run_sssp_batched`` on 4 such graphs under HYBRID must give each graph's
  ``run_sssp`` distances and phase counts (the §4 contract).
* **serve** — ``ServeEngine`` with full-width ``qwen3_1_7b`` (random bf16
  weights from ``--seed``) on the continuous plane with the threaded packer
  answers 64 requests (prompt 128, 16 new tokens, 4 priority classes, 2
  frontends) arriving in waves; every request must finish with 16 tokens
  and the admission order must equal the host oracle's (``step="host"``)
  on the same trace.

Four chips (``--chips 4``): the sharded SSSP batch against its ``mesh=None``
run, the batch × place engine's exactly-once check on a 2 × 2 mesh, and
cross-pod block stealing against its ``HostPodQueues`` twin.

Everything is generated from ``--seed``. Wall times printed are smoke
timings of one run, not benchmark figures. The last line of standard output
is ``{"ok": true, "device": {...}}``; the script exits non-zero, without
that line, when the first device is not a TPU or any phase fails.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time
import traceback

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

SSSP_N, SSSP_EDGE_P, SSSP_PLACES, SSSP_GRAPHS = 10_000, 0.5, 80, 4
SERVE_REQUESTS, SERVE_PROMPT, SERVE_NEW = 64, 128, 16
SERVE_WAVE, SERVE_WAVE_STEPS = 16, 16


def log(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


def _policy_k(policy) -> int:
    """fig5's k: 32 for the k-relaxed structures, 1 for the others."""
    from repro.core import Policy

    return 32 if policy in (Policy.CENTRALIZED, Policy.HYBRID) else 1


def phase_sssp(seed: int, *, n: int = SSSP_N, edge_p: float = SSSP_EDGE_P,
               places: int = SSSP_PLACES, graphs: int = SSSP_GRAPHS,
               require_kernel: bool = True) -> None:
    """The paper's SSSP deployment under the four policies, then batched."""
    import jax
    import numpy as np

    from repro.core import Policy, rho_bound, run_sssp, run_sssp_batched
    from repro.core import engine
    from repro.core import sssp as ss

    t0 = time.perf_counter()
    ws = np.stack([ss.make_er_graph(seed + g, n, edge_p)
                   for g in range(graphs)])
    finals = np.stack([ss.dijkstra_ref(w) for w in ws])
    log(f"sssp: {graphs} ER graphs n={n} p={edge_p} + dijkstra_ref "
        f"{time.perf_counter() - t0:.1f} s (host set-up)")

    single = {}
    for policy in (Policy.IDEAL, Policy.CENTRALIZED, Policy.HYBRID,
                   Policy.WORK_STEALING):
        k = _policy_k(policy)
        wj, fj = jax.numpy.asarray(ws[0]), jax.numpy.asarray(finals[0])
        t0 = time.perf_counter()
        compiled = engine._phase.lower(
            ss.init_sssp(wj, places), jax.random.PRNGKey(seed), wj, fj,
            num_places=places, k=k, policy=policy, arbitration="fused",
            topk_backend="auto",
        ).compile()
        compile_s = time.perf_counter() - t0
        has_kernel = "tpu_custom_call" in compiled.as_text()
        del wj, fj, compiled
        t0 = time.perf_counter()
        run = run_sssp(ws[0], num_places=places, k=k, policy=policy,
                       seed=seed, final=finals[0])
        wall = time.perf_counter() - t0
        bound = rho_bound(policy, k, places)
        log(f"sssp {policy.name} k={k} P={places}: correct={run.correct} "
            f"phases={run.phases} relaxed={run.total_relaxed} "
            f"useless={run.useless} max_ignored={run.max_ignored} "
            f"rho_bound={bound} kernel={has_kernel} "
            f"compile={compile_s:.1f} s wall={wall:.1f} s (smoke timing)")
        assert run.correct, f"{policy.name}: distances differ from dijkstra_ref"
        assert run.max_ignored <= bound, (policy.name, run.max_ignored, bound)
        if require_kernel:
            assert has_kernel, f"{policy.name}: no tpu_custom_call in phase"
        if policy is Policy.HYBRID:
            single[0] = run

    k = _policy_k(Policy.HYBRID)
    for g in range(1, graphs):
        single[g] = run_sssp(ws[g], num_places=places, k=k,
                             policy=Policy.HYBRID, seed=seed + g,
                             final=finals[g])
    t0 = time.perf_counter()
    batch = run_sssp_batched(ws, num_places=places, k=k, policy=Policy.HYBRID,
                             seeds=[seed + g for g in range(graphs)],
                             finals=finals)
    wall = time.perf_counter() - t0
    same = [np.array_equal(batch.runs[g].dist, single[g].dist)
            and batch.runs[g].phases == single[g].phases
            for g in range(graphs)]
    log(f"sssp batched HYBRID G={graphs}: joint_phases={batch.joint_phases} "
        f"per-graph phases={[r.phases for r in batch.runs]} "
        f"identical_to_run_sssp={same} wall={wall:.1f} s (smoke timing)")
    assert all(same), "run_sssp_batched differs from run_sssp (§4 contract)"
    assert all(r.correct for r in batch.runs)


def _serve_trace(seed: int, vocab: int, requests: int, prompt: int):
    import numpy as np

    rng = np.random.default_rng(seed)
    tokens = rng.integers(0, vocab, (requests, prompt)).astype(np.int32)
    classes = rng.integers(0, 4, requests).astype(np.float32)
    return tokens, classes


def _serve_once(cfg, params, config, *, seed: int, requests: int,
                prompt: int, max_new: int, slots: int, max_len: int):
    """Replay the wave trace through one engine; returns (admission_log,
    {rid: tokens}, wall seconds)."""
    from repro.serve.engine import Request, ServeEngine

    tokens, classes = _serve_trace(seed, cfg.vocab_size, requests, prompt)
    eng = ServeEngine(cfg, params, slots=slots, max_len=max_len, frontends=2,
                      k=4, config=config)
    t0 = time.perf_counter()
    done = []
    for start in range(0, requests, SERVE_WAVE):
        for rid in range(start, min(start + SERVE_WAVE, requests)):
            eng.submit(Request(rid=rid, tokens=tokens[rid], max_new=max_new,
                               priority=float(classes[rid])),
                       frontend=rid % 2)
        eng.wait_packed()
        if start + SERVE_WAVE < requests:
            for _ in range(SERVE_WAVE_STEPS):
                done.extend(eng.step())
    done.extend(eng.run())
    wall = time.perf_counter() - t0
    log_ = list(eng.admission_log)
    del eng
    gc.collect()
    return log_, {r.rid: list(r.out) for r in done}, wall


def phase_serve(seed: int, *, reduced: bool = False,
                requests: int = SERVE_REQUESTS, prompt: int = SERVE_PROMPT,
                max_new: int = SERVE_NEW, slots: int = 8,
                max_len: int = 1024, staging_rows: int = 40) -> None:
    """Full-width qwen3_1_7b behind the continuous plane vs the host oracle."""
    import jax

    from repro.configs import get_config, get_reduced
    from repro.models import materialize, model_p
    from repro.serve.config import ServeConfig

    cfg = (get_reduced if reduced else get_config)("qwen3_1_7b")
    t0 = time.perf_counter()
    params = materialize(jax.random.PRNGKey(seed), model_p(cfg))
    jax.block_until_ready(params)
    n_params = sum(x.size for x in jax.tree.leaves(params))
    log(f"serve: {cfg.name} {n_params / 1e9:.3f} B params "
        f"({'reduced' if reduced else 'full width'}), materialized in "
        f"{time.perf_counter() - t0:.1f} s")
    common = dict(seed=seed, requests=requests, prompt=prompt,
                  max_new=max_new, slots=slots, max_len=max_len)
    cont = ServeConfig(step="continuous", packer="thread",
                       admission_capacity=4096, staging_rows=staging_rows)
    c_log, c_out, c_wall = _serve_once(cfg, params, cont, **common)
    h_log, h_out, h_wall = _serve_once(cfg, params, ServeConfig(step="host"),
                                       **common)
    finished = sorted(rid for rid, out in c_out.items()
                      if len(out) == max_new)
    same_tokens = sum(c_out[r] == h_out.get(r) for r in c_out)
    log(f"serve continuous: {len(c_out)}/{requests} answered, "
        f"{len(finished)} with {max_new} tokens, wall={c_wall:.1f} s incl. "
        f"compile (smoke timing)")
    log(f"serve host oracle: {len(h_out)}/{requests} answered, "
        f"wall={h_wall:.1f} s incl. compile (smoke timing)")
    log(f"serve: admission order equal to host oracle: {c_log == h_log}; "
        f"token streams equal for {same_tokens}/{len(c_out)} requests "
        "(informational: eager and fused decode are separate programs)")
    log(f"serve admission order: {c_log}")
    assert finished == list(range(requests)), "not every request finished"
    assert c_log == h_log, "admission order differs from the host oracle"


def phase_sharded_sssp(seed: int, *, n: int = SSSP_N,
                       edge_p: float = SSSP_EDGE_P,
                       places: int = SSSP_PLACES, devices: int = 4) -> None:
    """Graphs sharded over a ``batch`` mesh == the one-device batched run."""
    import numpy as np

    from repro.core import Policy, run_sssp_batched
    from repro.core import sssp as ss
    from repro.launch.mesh import make_batch_mesh

    ws = np.stack([ss.make_er_graph(seed + g, n, edge_p)
                   for g in range(devices)])
    finals = np.stack([ss.dijkstra_ref(w) for w in ws])
    kwargs = dict(num_places=places, k=_policy_k(Policy.HYBRID),
                  policy=Policy.HYBRID,
                  seeds=[seed + g for g in range(devices)], finals=finals)
    ref = run_sssp_batched(ws, **kwargs)
    shard = run_sssp_batched(ws, mesh=make_batch_mesh(devices), **kwargs)
    same = [np.array_equal(a.dist, b.dist) and a.phases == b.phases
            and a.total_relaxed == b.total_relaxed
            for a, b in zip(ref.runs, shard.runs)]
    log(f"sharded sssp G={devices} n={n} P={places}: identical_to_mesh_none="
        f"{same} correct={[r.correct for r in shard.runs]} "
        f"wall mesh=None {ref.wall_s:.1f} s / sharded {shard.wall_s:.1f} s "
        "(smoke timing)")
    assert all(same), "sharded batch differs from mesh=None"
    assert all(r.correct for r in shard.runs)


def phase_batch_place() -> None:
    """Exactly-once on the batch × place engine over a 2 × 2 mesh."""
    from repro.core.sharded_batch import selftest_batch_place

    selftest_batch_place(2, 2)


def phase_pod_steal() -> None:
    """Cross-pod block stealing == the HostPodQueues twin on 4 devices."""
    from repro.core.sharded_batch import selftest_pod
    from repro.launch.mesh import make_production_batch_mesh

    selftest_pod(make_production_batch_mesh(multi_pod=True, batch=1, data=2,
                                            model=1))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the multi-device paths")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {platform!r}",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} devices, "
              f"JAX found {len(devices)}", file=sys.stderr)
        return 2

    from repro.launch.compile_cache import enable_compile_cache

    log(f"device {devices[0].device_kind} x{len(devices)}, jax "
        f"{jax.__version__}, compile cache {enable_compile_cache()}")
    if args.chips == 1:
        phases = [("sssp", lambda: phase_sssp(args.seed)),
                  ("serve", lambda: phase_serve(args.seed))]
    else:
        phases = [("sharded_sssp", lambda: phase_sharded_sssp(args.seed)),
                  ("batch_place", phase_batch_place),
                  ("pod_steal", phase_pod_steal)]
    failed = []
    for name, fn in phases:
        t0 = time.perf_counter()
        try:
            fn()
            status = "ok"
        except Exception:  # noqa: BLE001 - reported, and the exit code fails
            traceback.print_exc()
            failed.append(name)
            status = "FAILED"
        log(f"phase {name}: {status} in {time.perf_counter() - t0:.1f} s "
            "(smoke timing, not a benchmark)")
        gc.collect()
    for d in devices[:args.chips]:
        stats = d.memory_stats() or {}
        log(f"device {d.id} peak_bytes_in_use="
            f"{stats.get('peak_bytes_in_use', 'not reported')}")
    if failed:
        print(f"chip_smoke: failed phases: {failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
