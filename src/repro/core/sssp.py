"""Parallel single-source shortest paths (paper §5) on k-priority schedulers.

Each pending node-relaxation is a task; its priority is the node's tentative
distance (smaller = better), exactly as in the paper's Listing 5. Task
identity == node id (slot-pool), so re-pushing an improved node overwrites the
stale task — the paper's dead-task elimination done eagerly.

The relax step is the dense-graph vectorization of Listing 5: the ≤P popped
rows of the weight matrix are combined with a min-reduction, improved nodes
are pushed with the place that produced the improvement as creator.
"""
from __future__ import annotations

import heapq
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import kpriority as kp

INF = jnp.inf


# ---------------------------------------------------------------------------
# graphs
# ---------------------------------------------------------------------------

def make_er_graph(seed: int, n: int, p: float) -> np.ndarray:
    """Erdős–Rényi G(n, p), undirected, uniform ]0,1] weights, dense f32
    matrix with +inf for non-edges (paper §5.2.1)."""
    rng = np.random.default_rng(seed)
    upper = np.triu(rng.random((n, n)) < p, 1)
    w = rng.uniform(0.0, 1.0, size=(n, n)).astype(np.float32)
    w = np.where(upper, w, np.inf)
    w = np.minimum(w, w.T)  # symmetrize; diag stays +inf
    return w.astype(np.float32)


def dijkstra_ref(w: np.ndarray, source: int = 0) -> np.ndarray:
    """Sequential Dijkstra oracle (numpy + heapq), float64 (settled-ness
    comparisons against f32 schedulers use an epsilon; see SETTLED_EPS)."""
    n = w.shape[0]
    dist = np.full((n,), np.inf, np.float64)
    dist[source] = 0.0
    done = np.zeros((n,), bool)
    heap = [(0.0, source)]
    while heap:
        d, v = heapq.heappop(heap)
        if done[v]:
            continue
        done[v] = True
        nd = d + w[v].astype(np.float64)
        upd = nd < dist
        dist = np.where(upd, nd, dist)
        for u in np.nonzero(upd)[0]:
            heapq.heappush(heap, (float(dist[u]), int(u)))
    return dist


# settled-ness tolerance: schedulers run f32, the oracle f64; path sums agree
# to ~1e-7 absolute at U]0,1] weights — exact equality would misclassify.
SETTLED_EPS = 1e-6


# ---------------------------------------------------------------------------
# scheduler-driven parallel Dijkstra
# ---------------------------------------------------------------------------

class SSSPState(NamedTuple):
    dist: jnp.ndarray      # f32[n] tentative distances
    pool: kp.PoolState


class PhaseStats(NamedTuple):
    relaxed: jnp.ndarray     # i32[] nodes relaxed this phase
    settled: jnp.ndarray     # i32[] relaxed nodes that were already settled
    pushes: jnp.ndarray      # i32[] tasks spawned this phase
    h_star: jnp.ndarray      # f32[] max-min popped tentative distance
    ignored: jnp.ndarray     # i32[] structural rho-relaxation ignored count
    active: jnp.ndarray      # i32[] remaining active tasks


def init_sssp(w: jnp.ndarray, num_places: int, source: int = 0) -> SSSPState:
    n = w.shape[0]
    dist = jnp.full((n,), INF, jnp.float32).at[source].set(0.0)
    pool = kp.init_pool(n, num_places)
    mask = jnp.zeros((n,), bool).at[source].set(True)
    pool = kp.push(
        pool, mask, dist, jnp.zeros((n,), jnp.int32),
        k=1, policy=kp.Policy.IDEAL,
    )
    # make the seed task visible under every policy
    pool = pool._replace(published=pool.published | mask)
    return SSSPState(dist=dist, pool=pool)


def sssp_phase(
    state: SSSPState,
    key: jax.Array,
    w: jnp.ndarray,
    final: jnp.ndarray,
    *,
    num_places: int,
    k: int,
    policy: kp.Policy,
    arbitration: str = "fused",
    topk_backend: str = "auto",
) -> Tuple[SSSPState, PhaseStats]:
    """One phase: every place pops + relaxes its best visible node. Its
    parts carry the device scopes ``pop``, ``relax``, ``push`` and
    ``stats`` (DESIGN.md §17)."""
    with jax.named_scope("pop"):
        k_pop, k_push = jax.random.split(key)
        pool, res = kp.phase_pop(
            state.pool, k_pop, num_places=num_places, k=k, policy=policy,
            arbitration=arbitration, topk_backend=topk_backend,
        )
        ignored = kp.ignored_count(state.pool, res)

    # ---- relax the popped rows (Listing 5, vectorized) -----------------
    with jax.named_scope("relax"):
        rows = w[res.slot]                               # [P, n]
        cand = jnp.where(res.valid[:, None], res.prio[:, None] + rows, INF)
        best = jnp.min(cand, axis=0)                     # [n]
        src_place = jnp.argmin(cand, axis=0).astype(jnp.int32)
        improved = best < state.dist
        dist = jnp.where(improved, best, state.dist)

    with jax.named_scope("push"):
        pool = kp.push(
            pool, improved, dist, src_place, k=k, policy=policy, key=k_push
        )

    with jax.named_scope("stats"):
        relaxed = jnp.sum(res.valid)
        settled = jnp.sum(
            res.valid & (res.prio <= final[res.slot] + SETTLED_EPS))
        hi = jnp.max(jnp.where(res.valid, res.prio, -INF))
        lo = jnp.min(jnp.where(res.valid, res.prio, INF))
        h_star = jnp.where(relaxed > 0, hi - lo, 0.0)
        stats = PhaseStats(
            relaxed=relaxed.astype(jnp.int32),
            settled=settled.astype(jnp.int32),
            pushes=jnp.sum(improved).astype(jnp.int32),
            h_star=h_star.astype(jnp.float32),
            ignored=ignored.astype(jnp.int32),
            active=jnp.sum(pool.active).astype(jnp.int32),
        )
    return SSSPState(dist=dist, pool=pool), stats
