"""Phase-loop drivers for k-priority scheduling.

``run_sssp`` drives the scheduler-based parallel Dijkstra to completion with a
jitted phase step (one compilation per (policy, shapes)); per-phase statistics
are collected host-side, which is what the paper's evaluation reports
(Figs. 3–5).

``run_sssp_batched`` runs G independent graphs under one policy in a single
jitted program (vmap over the graph axis): one XLA dispatch per joint phase
instead of one per graph per phase, and max(phases_g) dispatches instead of
sum(phases_g). Graph g's trajectory is bit-identical to ``run_sssp`` on that
graph alone with the same seed — finished graphs ride along as no-op phases
(empty pool ⇒ no pops, no pushes, distances frozen) until the whole batch
drains. This is what lets the benchmark sweeps amortize compilation and
report per-graph throughput (DESIGN.md §4).
"""
from __future__ import annotations

import dataclasses
import functools
import time
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import kpriority as kp
from repro.core import sssp as ss
from repro.obs import span


@dataclasses.dataclass
class SSSPRun:
    """Per-run summary of one SSSP trajectory (the paper's Figs. 3–5 raw
    material; DESIGN.md §5). ``max_ignored`` is the observed per-phase
    ρ-relaxation — the §2 bound demands it never exceed
    ``rho_bound(policy, k, P)``."""

    dist: np.ndarray
    phases: int
    total_relaxed: int
    total_settled: int
    total_pushes: int
    max_ignored: int
    useless: int                    # relaxations of not-yet-settled nodes
    per_phase: Dict[str, np.ndarray]
    correct: bool


@dataclasses.dataclass
class SSSPBatchRun:
    """Result of one batched multi-graph run: per-graph ``SSSPRun`` summaries
    plus the joint loop's cost."""

    runs: List[SSSPRun]
    joint_phases: int               # phases executed by the batched loop
    wall_s: float                   # wall-clock of the batched loop itself


@functools.partial(
    jax.jit,
    static_argnames=("num_places", "k", "policy", "arbitration", "topk_backend"),
)
def _phase(state, key, w, final, *, num_places, k, policy,
           arbitration, topk_backend):
    return ss.sssp_phase(
        state, key, w, final, num_places=num_places, k=k, policy=policy,
        arbitration=arbitration, topk_backend=topk_backend,
    )


def run_sssp(
    w: np.ndarray,
    *,
    num_places: int,
    k: int,
    policy: kp.Policy,
    seed: int = 0,
    max_phases: int = 100_000,
    final: Optional[np.ndarray] = None,
    arbitration: str = "fused",
    topk_backend: str = "auto",
) -> SSSPRun:
    """Run the parallel SSSP under a scheduling policy until no active tasks
    (DESIGN.md §5; ``w`` f32[n, n] dense weights, ``final`` f64[n] oracle
    distances). One jitted phase per dispatch; per-phase stats are collected
    host-side (the paper's Figs. 3–5 evaluation). The phase inherits the
    policy's ignored ≤ ρ guarantee (§2) — ``max_ignored`` in the result is
    the observed value."""
    if final is None:
        final = ss.dijkstra_ref(w)
    with span("sssp.prepare"):
        wj = jnp.asarray(w)
        fj = jnp.asarray(final)
        state = ss.init_sssp(wj, num_places)
        key = jax.random.PRNGKey(seed)

    cols = {f: [] for f in ss.PhaseStats._fields}
    phases = 0
    while phases < max_phases:
        with span("sssp.phase"):
            with span("sssp.dispatch"):
                key, sub = jax.random.split(key)
                state, stats = _phase(
                    state, sub, wj, fj, num_places=num_places, k=k,
                    policy=policy, arbitration=arbitration,
                    topk_backend=topk_backend,
                )
            with span("sssp.readback"):
                stats = jax.device_get(stats)
            for f in ss.PhaseStats._fields:
                cols[f].append(getattr(stats, f))
            phases += 1
        if stats.active == 0 and stats.relaxed == 0:
            break

    with span("sssp.finish"):
        per_phase = {f: np.asarray(v) for f, v in cols.items()}
        dist = np.asarray(jax.device_get(state.dist))
        return _summarize_run(per_phase, dist, final, phases)


def _summarize_run(
    per_phase: Dict[str, np.ndarray],
    dist: np.ndarray,
    final: np.ndarray,
    phases: int,
) -> SSSPRun:
    """Fold a per-phase stats table into the SSSPRun summary (shared by the
    sequential and batched drivers so their reports cannot drift)."""
    total_relaxed = int(per_phase["relaxed"].sum())
    total_settled = int(per_phase["settled"].sum())
    return SSSPRun(
        dist=dist,
        phases=phases,
        total_relaxed=total_relaxed,
        total_settled=total_settled,
        total_pushes=int(per_phase["pushes"].sum()),
        max_ignored=int(per_phase["ignored"].max(initial=0)),
        useless=total_relaxed - total_settled,
        per_phase=per_phase,
        correct=bool(np.allclose(dist, final, rtol=1e-6, atol=1e-6)),
    )


# ---------------------------------------------------------------------------
# batched multi-graph driver
# ---------------------------------------------------------------------------

def _phase_batched_impl(state, keys, ws, finals, *, num_places, k, policy,
                        arbitration, topk_backend):
    """One joint phase over all G graphs. The per-graph PRNG chain (split,
    use the second half) matches ``run_sssp``'s host-side chain exactly."""

    def one(s, key, w, f):
        key, sub = jax.random.split(key)
        new_s, stats = ss.sssp_phase(
            s, sub, w, f, num_places=num_places, k=k, policy=policy,
            arbitration=arbitration, topk_backend=topk_backend,
        )
        return new_s, stats, key

    return jax.vmap(one)(state, keys, ws, finals)


def _phase_chunk_impl(state, keys, ws, finals, *, chunk, num_places, k,
                      policy, arbitration, topk_backend):
    """``chunk`` joint phases as ONE dispatch (lax.scan over the phase step).

    Per-phase stats come back stacked ([chunk, G] leaves) so the host loop
    still sees every phase; phases past a graph's drain are the documented
    no-op ride-along (empty pool ⇒ nothing pops, nothing pushes), so chunking
    never changes per-graph trajectories — it only amortizes the dispatch
    (and, under ``mesh=``, the multi-device launch) overhead across chunk
    phases.
    """
    def step(carry, _):
        st, ks = carry
        st, stats, ks = _phase_batched_impl(
            st, ks, ws, finals, num_places=num_places, k=k, policy=policy,
            arbitration=arbitration, topk_backend=topk_backend,
        )
        return (st, ks), stats

    (state, keys), stats = jax.lax.scan(
        step, (state, keys), None, length=chunk
    )
    return state, stats, keys


_phase_chunk = functools.partial(
    jax.jit,
    static_argnames=("chunk", "num_places", "k", "policy", "arbitration",
                     "topk_backend"),
)(_phase_chunk_impl)


@functools.lru_cache(maxsize=None)
def _phase_chunk_sharded(mesh, chunk, num_places, k, policy, arbitration,
                         topk_backend):
    """shard_map form of ``_phase_chunk``: graphs spread over the mesh's
    ``batch`` axis, each device advancing its G/D graphs through ``chunk``
    phases with the same batched program (zero cross-device traffic —
    instances are independent, see core/sharded_batch.py)."""
    from jax.sharding import PartitionSpec as PS

    from repro.launch.mesh import BATCH_AXIS

    local = functools.partial(
        _phase_chunk_impl, chunk=chunk, num_places=num_places, k=k,
        policy=policy, arbitration=arbitration, topk_backend=topk_backend,
    )
    f = jax.shard_map(
        local, mesh=mesh,
        in_specs=(PS(BATCH_AXIS),) * 4,
        # stats leaves are [chunk, G]: batch axis is dim 1 there
        out_specs=(PS(BATCH_AXIS), PS(None, BATCH_AXIS), PS(BATCH_AXIS)),
        # no collectives to check, and the Pallas stage-1 kernel is traced
        # without varying-axis types
        check_vma=False,
    )
    return jax.jit(f)


def run_sssp_batched(
    ws: np.ndarray,                     # [G, n, n] stacked weight matrices
    *,
    num_places: int,
    k: int,
    policy: kp.Policy,
    seeds: Optional[Sequence[int]] = None,
    max_phases: int = 100_000,
    finals: Optional[np.ndarray] = None,  # [G, n] oracle distances
    arbitration: str = "fused",
    topk_backend: str = "auto",
    mesh=None,
    phase_chunk: Optional[int] = None,
) -> SSSPBatchRun:
    """Run G graphs × one policy as a single jitted batched program
    (DESIGN.md §4; ``ws`` f32[G, n, n], ``finals`` f64[G, n]). Per-graph
    ρ guarantees are untouched — batching/sharding only change placement.

    ``seeds[g]`` seeds graph g's PRNG chain (default ``range(G)``), matching
    ``run_sssp(ws[g], seed=seeds[g], ...)`` bit-for-bit on distances and
    per-phase statistics.

    ``mesh`` (a ``batch``-axis mesh, e.g. ``launch.mesh.make_batch_mesh()``)
    shards the graph batch across devices: G/D graphs per device, same joint
    phase loop, zero cross-device traffic, bit-identical per-graph results
    (tests/test_sharded_batch.py). G need not divide D — the batch is padded
    with inert empty graphs (drained after their first pop) and the padding
    never appears in the returned runs.

    ``phase_chunk`` fuses that many joint phases into one dispatch
    (lax.scan); per-phase stats and per-graph trajectories are unchanged —
    only the dispatch overhead amortizes. Defaults to 1 unsharded (keeps
    ``joint_phases`` == max per-graph phases) and 16 under ``mesh=`` (the
    multi-device launch overhead is what the chunk exists to bury).
    """
    if phase_chunk is None:
        phase_chunk = 1 if mesh is None else 16
    if phase_chunk < 1:
        raise ValueError(f"phase_chunk must be >= 1, got {phase_chunk}")
    num_graphs = len(ws)
    if seeds is None:
        seeds = list(range(num_graphs))
    if len(seeds) != num_graphs:
        raise ValueError(f"{len(seeds)} seeds for {num_graphs} graphs")
    if finals is None:
        finals = np.stack([ss.dijkstra_ref(np.asarray(w)) for w in ws])

    pad = 0
    if mesh is not None:
        from repro.core.sharded_batch import batch_axis_size

        pad = -num_graphs % batch_axis_size(mesh)
    # the weights go through host memory: bytes= is what crosses each way
    with span("sssp.prepare", bytes=int(ws.nbytes)):
        ws = np.asarray(ws)
        if pad:
            n = ws.shape[1]
            # inert padding: no edges => the source task pops once, nothing
            # improves, the instance drains and rides along as no-op phases
            w_inert = np.full((pad, n, n), np.inf, np.float32)
            f_inert = np.full((pad, n), np.inf, np.float64)
            f_inert[:, 0] = 0.0
            ws = np.concatenate([ws, w_inert], axis=0)
            finals = np.concatenate([finals, f_inert.astype(finals.dtype)],
                                    axis=0)
            seeds = list(seeds) + list(range(pad))

        t0 = time.perf_counter()
        wj = jnp.asarray(ws)
        fj = jnp.asarray(finals)
        state = jax.vmap(
            functools.partial(ss.init_sssp, num_places=num_places)
        )(wj)
        keys = jnp.stack([jax.random.PRNGKey(int(s)) for s in seeds])

    def phase_fn(chunk, state, keys):
        if mesh is None:
            return _phase_chunk(
                state, keys, wj, fj, chunk=chunk, num_places=num_places,
                k=k, policy=policy, arbitration=arbitration,
                topk_backend=topk_backend,
            )
        return _phase_chunk_sharded(
            mesh, chunk, num_places, k, policy, arbitration, topk_backend,
        )(state, keys, wj, fj)

    cols = {f: [] for f in ss.PhaseStats._fields}   # each entry: [G] per phase
    done_at = np.full((num_graphs + pad,), -1, np.int64)
    phases = 0
    while phases < max_phases:
        # shrink the final chunk so execution stops exactly at max_phases —
        # a chunked run truncates bit-identically to an unchunked one (the
        # tail chunk costs one extra compile, and only when the cap is hit)
        chunk = min(phase_chunk, max_phases - phases)
        with span("sssp.phase"):
            with span("sssp.dispatch"):
                state, stats, keys = phase_fn(chunk, state, keys)
            with span("sssp.readback"):
                stats = jax.device_get(stats)          # leaves [chunk, G]
            for t in range(chunk):
                for f in ss.PhaseStats._fields:
                    cols[f].append(getattr(stats, f)[t])
                drained = (stats.active[t] == 0) & (stats.relaxed[t] == 0)
                newly = (done_at < 0) & drained
                done_at[newly] = phases
                phases += 1
        if (done_at >= 0).all():
            break
    done_at[done_at < 0] = phases - 1   # max_phases hit: truncate at the end

    with span("sssp.finish"):
        dist = np.asarray(jax.device_get(state.dist))   # [G, n]
        wall = time.perf_counter() - t0

        runs: List[SSSPRun] = []
        for g in range(num_graphs):
            g_phases = int(done_at[g]) + 1
            per_phase = {
                f: np.asarray([row[g] for row in cols[f][:g_phases]])
                for f in ss.PhaseStats._fields
            }
            runs.append(_summarize_run(per_phase, dist[g], finals[g],
                                       g_phases))
    return SSSPBatchRun(runs=runs, joint_phases=phases, wall_s=wall)
