"""SSSP cells: ``run_sssp`` or ``run_sssp_batched`` back to back (closed loop).

The configuration gives the graph (n, p) and the scheduler (places,
policy, k); the traffic gives the entry (``run_sssp`` or
``run_sssp_batched``), how many graphs stay resident and how many one call
solves. Graphs are made on the device from the seed and stay there, as a
deployment holds its graph; every call gets fresh PRNG seeds. The
reference distances are the benchmark's own Dijkstra, computed in set-up
because the program's phase takes them as an input for its statistics.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.common import Check, Outcome, seed_words
from chipbench.reference import sssp as ref
from chipbench.tracing import span

POLICY_RHO = {"IDEAL": lambda k, p: 0, "CENTRALIZED": lambda k, p: k,
              "HYBRID": lambda k, p: p * k}
# a correct solve takes a few hundred phases at n = 10^4; a stuck one stops
# here and fails the comparison
MAX_PHASES_PER_NODE = 1


class State:
    pass


def setup(ctx) -> State:
    from repro.core import Policy, run_sssp, run_sssp_batched

    cfg, mix = ctx.config, ctx.traffic
    st = State()
    st.n, st.p, st.places, st.k = cfg["n"], cfg["p"], cfg["places"], cfg["k"]
    st.policy = Policy[cfg["policy"]]
    st.rho = POLICY_RHO[cfg["policy"]](st.k, st.places)
    st.kwargs = dict(num_places=st.places, k=st.k, policy=st.policy,
                     arbitration=cfg["arbitration"],
                     topk_backend=cfg["topk_backend"],
                     max_phases=MAX_PHASES_PER_NODE * st.n)
    st.entry = mix["entry"]
    st.per_call = int(mix["graphs_per_call"])
    st.resident = int(mix["graphs_resident"])
    st.run_sssp, st.run_sssp_batched = run_sssp, run_sssp_batched
    words = seed_words(ctx.seed, 0, st.resident)
    keys = jnp.stack([jax.random.PRNGKey(int(w)) for w in words])
    t0 = time.perf_counter()
    with span("make_graphs"):
        if st.entry == "run_sssp_batched":
            st.graphs = ref.er_graphs(keys, n=st.n, p=st.p)
            st.finals = np.stack([np.asarray(ref.dijkstra(st.graphs[g]))
                                  for g in range(st.resident)])
        else:
            st.graphs = [ref.er_graph(keys[g], n=st.n, p=st.p)
                         for g in range(st.resident)]
            st.finals = np.stack([np.asarray(ref.dijkstra(w))
                                  for w in st.graphs])
    ctx.log(f"{st.resident} graphs n={st.n} p={st.p} and reference "
            f"distances in {time.perf_counter() - t0:.3f} s")
    st.solve_seeds = iter(seed_words(ctx.seed, 1, 1 << 16))
    t0 = time.perf_counter()
    with span("warm_up"):
        _call(st, 0, seeds=[2**31 - 2 - i for i in range(st.per_call)])
    ctx.log(f"warm-up call in {time.perf_counter() - t0:.3f} s")
    return st


def _call(st: State, i: int, seeds=None):
    """One call of the entry: returns [(graph index, SSSPRun)]."""
    if seeds is None:
        seeds = [int(next(st.solve_seeds)) for _ in range(st.per_call)]
    if st.entry == "run_sssp_batched":
        with span("solve_batch"):
            res = st.run_sssp_batched(st.graphs, seeds=seeds,
                                      finals=st.finals, **st.kwargs)
        return list(enumerate(res.runs))
    g = i % st.resident
    with span("solve"):
        run = st.run_sssp(st.graphs[g], seed=seeds[0], final=st.finals[g],
                          **st.kwargs)
    return [(g, run)]


def window(ctx, st: State) -> Outcome:
    solves = []
    t0 = time.perf_counter()
    end = t0 + ctx.seconds
    i, t_last = 0, t0
    while time.perf_counter() < end:
        ctx.poll()
        for g, run in _call(st, i):
            solves.append((g, run.dist, run.phases, run.useless,
                           run.max_ignored))
        i += 1
        t_last = time.perf_counter()
        ctx.poll(t_last)
    span_s = t_last - t0
    phases = sum(s[2] for s in solves)
    useless = sum(s[3] for s in solves)
    return Outcome(
        end_to_end={"sssp_graphs_per_s": len(solves) / span_s},
        counters={"graphs": len(solves), "calls": i, "phases": phases,
                  "useless": useless, "n": st.n, "places": st.places,
                  "graphs_per_phase_program": st.per_call,
                  "window_s": span_s},
        attempted=len(solves), failed=0, data=solves)


def release(st: State):
    st.graphs_for_control, st.graphs = st.graphs, None


def check(ctx, st: State, out: Outcome) -> list:
    """Every solve of the window against the reference distances, the
    per-phase ρ bound, and completion within the phase cap."""
    worst_gap, worst_ignored, unfinished, wrong = 0.0, 0, 0, 0
    cap = MAX_PHASES_PER_NODE * st.n
    limit = ctx.config["limits"]["dist_rel_gap"]
    for g, dist, phases, _useless, max_ignored in out.data:
        gap = ref.rel_gap(dist, st.finals[g])
        worst_gap = max(worst_gap, gap)
        worst_ignored = max(worst_ignored, int(max_ignored))
        bad_solve = phases >= cap or gap > limit
        unfinished += phases >= cap
        wrong += bad_solve
    out.failed = wrong
    return [Check("dist_rel_gap", worst_gap, limit),
            Check("max_ignored", worst_ignored, st.rho),
            Check("unfinished", unfinished, 0)]


def control(ctx, st: State) -> dict:
    """The control's reading of ``dist_rel_gap``: the same Dijkstra in
    bfloat16, put in the program's place, against the float32 reference,
    on every resident graph."""
    import jax.numpy as jnp

    graphs = st.graphs_for_control
    worst = 0.0
    for g in range(st.resident):
        low = ref.dijkstra(graphs[g], dtype=jnp.bfloat16)
        worst = max(worst, ref.rel_gap(np.asarray(low, np.float32),
                                       st.finals[g]))
    return {"dist_rel_gap": worst}
