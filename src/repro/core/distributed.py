"""Hybrid k-priority scheduler with EXPLICIT collectives (shard_map).

One *place* per device. The pjit engine (kpriority.py) models the paper's
structures with a global-array state; this module is the TPU-native runtime
form: each device owns its local task slots, and the ρ-relaxation contract is
what bounds the wire traffic —

  * push: local, free (the paper's lock-free local-list insert),
  * publish: once a place accumulates ≥ k unpublished tasks it contributes
    them to a bounded per-phase publication buffer; one jax.lax.all_gather of
    (k_buf) items per phase makes them globally visible — collective bytes
    per phase ≤ P·k_buf·item, *independent of queue depth* (the paper's
    scalability argument, literally as ICI bytes),
  * pop: every device proposes its best visible task; one tiny all_gather of
    (P, 3) proposals + a deterministic, replicated arbitration (the
    CAS-winner analogue) assigns ≤ P distinct tasks per phase.

Run ``python -m repro.core.distributed --selftest`` under
XLA_FLAGS=--xla_force_host_platform_device_count=8 (see tests/test_distributed).
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as PS

INF = jnp.inf
AXIS = "place"


def vary_like(x: jnp.ndarray, ref: jnp.ndarray) -> jnp.ndarray:
    """Mark ``x`` as varying over every mesh axis ``ref`` varies over.

    A scan carry that mixes with collective-derived data must carry the same
    varying-axis set as that data: ``place`` alone inside the engine, but
    ``batch`` and ``place`` when the engine runs per instance on a
    (batch × place) mesh (core/sharded_batch.py)."""
    return jax.lax.pcast(x, tuple(jax.typeof(ref).vma), to="varying")


class ShardState(NamedTuple):
    """Per-device leaves (leading dim = places when viewed globally)."""
    loc_prio: jnp.ndarray    # f32[M] local slots (unpublished or published-own)
    loc_id: jnp.ndarray      # i32[M] task ids (-1 = empty)
    loc_pub: jnp.ndarray     # bool[M] already published
    unpub: jnp.ndarray       # i32[] count since last publication
    glob_prio: jnp.ndarray   # f32[G] replicated view of published tasks
    glob_id: jnp.ndarray     # i32[G]
    glob_n: jnp.ndarray      # i32[] filled prefix of the global view


def init_state(m_loc: int, g_cap: int) -> ShardState:
    return ShardState(
        loc_prio=jnp.full((m_loc,), INF, jnp.float32),
        loc_id=jnp.full((m_loc,), -1, jnp.int32),
        loc_pub=jnp.zeros((m_loc,), bool),
        unpub=jnp.zeros((), jnp.int32),
        glob_prio=jnp.full((g_cap,), INF, jnp.float32),
        glob_id=jnp.full((g_cap,), -1, jnp.int32),
        glob_n=jnp.zeros((), jnp.int32),
    )


def _push_local(st: ShardState, prio, tid) -> ShardState:
    """Insert one task into a free local slot (prio=inf marks free)."""
    slot = jnp.argmax(~(st.loc_id >= 0))
    return st._replace(
        loc_prio=st.loc_prio.at[slot].set(prio),
        loc_id=st.loc_id.at[slot].set(tid),
        loc_pub=st.loc_pub.at[slot].set(False),
        unpub=st.unpub + 1,
    )


def phase(st: ShardState, k: int, k_buf: int) -> Tuple[ShardState, jnp.ndarray, jnp.ndarray]:
    """One scheduling phase inside shard_map. Returns
    (state, popped_id i32[], popped_prio f32[]) — one pop per place (-1 if
    none visible)."""
    p = jax.lax.axis_index(AXIS)
    nplaces = jax.lax.axis_size(AXIS)

    # ---- publish: if >= k unpublished, move up to k_buf into the buffer ----
    must_pub = st.unpub >= k
    unpub_mask = (st.loc_id >= 0) & ~st.loc_pub
    order = jnp.argsort(jnp.where(unpub_mask, st.loc_prio, INF))
    take = jnp.arange(st.loc_id.shape[0]) < k_buf
    sel = jnp.zeros_like(unpub_mask).at[order].set(take) & unpub_mask & must_pub
    buf_prio = jnp.full((k_buf,), INF, jnp.float32)
    buf_id = jnp.full((k_buf,), -1, jnp.int32)
    idxs = jnp.nonzero(sel, size=k_buf, fill_value=-1)[0]
    valid = idxs >= 0
    buf_prio = jnp.where(valid, st.loc_prio[idxs], INF)
    buf_id = jnp.where(valid, st.loc_id[idxs], -1)
    st = st._replace(
        loc_pub=st.loc_pub | sel,
        unpub=jnp.where(must_pub, 0, st.unpub),
    )

    # ---- the bounded collective: P x k_buf items per phase ---------------
    all_prio = jax.lax.all_gather(buf_prio, AXIS).reshape(-1)   # [P*k_buf]
    all_id = jax.lax.all_gather(buf_id, AXIS).reshape(-1)
    # append to the replicated global view (identical on all devices)
    app_order = jnp.argsort(jnp.where(all_id >= 0, 0, 1))
    all_prio, all_id = all_prio[app_order], all_id[app_order]
    n_new = jnp.sum(all_id >= 0)
    g_cap = st.glob_prio.shape[0]
    pos = (st.glob_n + jnp.arange(all_id.shape[0])) % g_cap
    write = all_id >= 0
    glob_prio = st.glob_prio.at[pos].set(
        jnp.where(write, all_prio, st.glob_prio[pos]))
    glob_id = st.glob_id.at[pos].set(
        jnp.where(write, all_id, st.glob_id[pos]))
    st = st._replace(glob_prio=glob_prio, glob_id=glob_id,
                     glob_n=st.glob_n + n_new)

    # ---- pop: top-R of (global view ∪ own local) per place ----------------
    R = 4
    merged_prio = jnp.concatenate([
        jnp.where(st.loc_id >= 0, st.loc_prio, INF),
        jnp.where(st.glob_id >= 0, st.glob_prio, INF),
    ])
    merged_id = jnp.concatenate([st.loc_id, st.glob_id])
    neg, top_i = jax.lax.top_k(-merged_prio, R)
    cand_prio = -neg                                              # [R]
    cand_id = jnp.where(jnp.isfinite(cand_prio), merged_id[top_i], -1)

    # deterministic replicated greedy (the CAS-winner analogue): in place
    # order, each place claims its best unclaimed candidate
    props = jax.lax.all_gather(
        jnp.stack([cand_prio, cand_id.astype(jnp.float32)], axis=-1), AXIS
    )                                                             # [P, R, 2]
    all_ids = props[:, :, 1].astype(jnp.int32)                    # [P, R]

    def claim(claimed, pl):
        cands = all_ids[pl]                                       # [R]
        free = (cands >= 0) & ~jnp.isin(cands, claimed)
        j = jnp.argmax(free)
        pick = jnp.where(jnp.any(free), cands[j], -1)
        claimed = claimed.at[pl].set(pick)
        return claimed, pick

    claimed0 = vary_like(jnp.full((nplaces,), -1, jnp.int32), all_ids)
    claimed, picks = jax.lax.scan(claim, claimed0, jnp.arange(nplaces))
    my_pick = picks[p]
    popped_id = my_pick
    pj = jnp.argmax(cand_id == my_pick)
    popped_prio = jnp.where(my_pick >= 0, cand_prio[pj], INF)

    # ---- mark taken everywhere (replicated view + own slots) --------------
    taken_ids = claimed                                           # [P]
    g_taken = jnp.isin(st.glob_id, taken_ids) & (st.glob_id >= 0)
    l_taken = jnp.isin(st.loc_id, taken_ids) & (st.loc_id >= 0)
    st = st._replace(
        glob_prio=jnp.where(g_taken, INF, st.glob_prio),
        glob_id=jnp.where(g_taken, -1, st.glob_id),
        loc_prio=jnp.where(l_taken, INF, st.loc_prio),
        loc_id=jnp.where(l_taken, -1, st.loc_id),
    )
    return st, popped_id, popped_prio


def make_engine(mesh: Mesh, m_loc: int, g_cap: int, k: int, k_buf: int):
    """Returns jitted (state, pushes) -> (state, popped_ids, popped_prios)
    where pushes = (prio f32[P, n], id i32[P, n]) per-place new tasks."""

    @functools.partial(
        jax.shard_map, mesh=mesh,
        in_specs=(PS(AXIS), (PS(AXIS), PS(AXIS))),
        out_specs=(PS(AXIS), PS(AXIS), PS(AXIS)),
    )
    def step(state, pushes):
        st = jax.tree.map(lambda a: a[0], state)      # drop place dim
        prios, tids = pushes
        def body(s, xy):
            pr, ti = xy
            return jax.lax.cond(
                ti >= 0, lambda ss: _push_local(ss, pr, ti), lambda ss: ss, s
            ), None
        st, _ = jax.lax.scan(body, st, (prios[0], tids[0]))
        st, pid, pprio = phase(st, k, k_buf)
        st = jax.tree.map(lambda a: a[None], st)
        return st, pid[None], pprio[None]

    return jax.jit(step)


def selftest(nplaces: int) -> None:  # pragma: no cover - exercised via subprocess
    import numpy as np
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((nplaces,), (AXIS,))
    m_loc, g_cap, k, k_buf = 64, 512, 3, 8
    engine = make_engine(mesh, m_loc, g_cap, k, k_buf)
    state = jax.tree.map(
        lambda a: jnp.broadcast_to(a, (nplaces,) + a.shape),
        init_state(m_loc, g_cap),
    )
    rng = np.random.default_rng(0)
    n_push, pushed, popped = 6, set(), []
    tid = 0
    for phase_i in range(200):
        pr = np.full((nplaces, n_push), np.inf, np.float32)
        ti = np.full((nplaces, n_push), -1, np.int32)
        if phase_i < 8:
            for pl in range(nplaces):
                for j in range(rng.integers(1, n_push)):
                    pr[pl, j] = rng.random()
                    ti[pl, j] = tid
                    pushed.add(tid)
                    tid += 1
        state, pid, pprio = engine(state, (jnp.asarray(pr), jnp.asarray(ti)))
        ids = np.asarray(pid).ravel()
        popped.extend(int(i) for i in ids if i >= 0)
        if phase_i >= 8 and not any(i >= 0 for i in ids):
            break
    assert sorted(popped) == sorted(pushed), (
        f"exactly-once violated: {len(popped)} popped vs {len(pushed)} pushed")
    assert len(set(popped)) == len(popped)
    print(f"DISTRIBUTED_OK places={nplaces} tasks={len(pushed)}")


if __name__ == "__main__":
    import sys
    if "--selftest" in sys.argv:
        selftest(len(jax.devices()))
