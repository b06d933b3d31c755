"""The benchmark's own graphs and shortest paths, independent of the program.

``er_graphs`` makes Erdős–Rényi G(n, p) graphs on the device: undirected,
weights uniform on ]0, 1], dense float32 with +inf for a missing edge and
on the diagonal (paper §5.2.1). ``dijkstra`` is the textbook dense O(n²)
Dijkstra: n rounds of "settle the closest unsettled node, relax its row".
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def _er_graph(key, n: int, p: float):
    k_edge, k_w = jax.random.split(key)
    upper = jnp.triu(jax.random.bernoulli(k_edge, p, (n, n)), 1)
    w = 1.0 - jax.random.uniform(k_w, (n, n), jnp.float32)   # ]0, 1]
    w = jnp.where(upper, w, jnp.inf)
    return jnp.minimum(w, w.T)


@functools.partial(jax.jit, static_argnames=("n", "p"))
def er_graph(key, *, n: int, p: float):
    """One graph, f32[n, n]."""
    return _er_graph(key, n, p)


@functools.partial(jax.jit, static_argnames=("n", "p"))
def er_graphs(keys, *, n: int, p: float):
    """``len(keys)`` graphs stacked, f32[G, n, n], made one at a time."""
    return jax.lax.map(lambda k: _er_graph(k, n, p), keys)


@functools.partial(jax.jit, static_argnames=("dtype",))
def dijkstra(w, dtype=jnp.float32):
    """Distances from node 0, computed in ``dtype`` (float32 is the
    configuration's precision; a lower one is the control)."""
    n = w.shape[0]
    dist0 = jnp.full((n,), jnp.inf, dtype).at[0].set(0)

    def settle(_, carry):
        dist, done = carry
        v = jnp.argmin(jnp.where(done, jnp.inf, dist))
        row = jax.lax.dynamic_index_in_dim(w, v, keepdims=False).astype(dtype)
        dist = jnp.where(done, dist, jnp.minimum(dist, dist[v] + row))
        return dist, done.at[v].set(True)

    dist, _ = jax.lax.fori_loop(0, n, settle, (dist0, jnp.zeros((n,), bool)))
    return dist


def rel_gap(dist, ref) -> float:
    """Largest gap between two distance vectors, relative to the reference
    distance (an unreachable node must be unreachable in both)."""
    d = np.asarray(dist, np.float64)
    r = np.asarray(ref, np.float64)
    fin = np.isfinite(r)
    if not np.array_equal(fin, np.isfinite(d)):
        return float("inf")
    gap = np.abs(d[fin] - r[fin]) / np.maximum(np.abs(r[fin]), 1e-30)
    return float(gap.max(initial=0.0))
