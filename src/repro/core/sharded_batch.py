"""Device-sharded batched k-priority engine: B pool instances over D devices.

The batched engine (core/batched.py) advances B independent instances in one
XLA program on ONE device. This module is the next scale step the paper's
argument calls for: because instances are independent, the batch axis shards
with ZERO cross-device traffic — ``shard_map`` over a ``batch`` mesh axis
places B/D instances per device, each advanced by the same natively-batched
program (one fused-arbitration kernel launch per device per phase). This is
the Multi-Queues / k-LSM move ("distribute, then relax the ordering to bound
coordination") with the coordination bound taken to its limit: the instances
never coordinate at all, and the ρ-relaxation lives entirely inside each
instance's fused arbitration.

Layouts compose: a (batch × place) mesh runs B instances of the
explicit-collective engine (core/distributed.py), each spanning its own
``place`` sub-mesh — instance-parallel on ``batch``, the ρ-bounded
publication/proposal collectives confined to ``place``
(:func:`make_engine_batched`).

Bit-identity contract (tests/test_sharded_batch.py): sharded == single-device
batched == per-instance loop, including the B % D != 0 case, which pads with
inert instances (empty pools — no pops, no pushes) and slices them back off.

Run ``python -m repro.core.sharded_batch --selftest`` under
XLA_FLAGS=--xla_force_host_platform_device_count=8.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as PS

from repro.core import batched
from repro.core import kpriority as kp
from repro.core.distributed import vary_like
from repro.launch.mesh import BATCH_AXIS


def batch_axis_size(mesh: Mesh) -> int:
    """D = devices along the ``batch`` axis (works on the plain 1-D batch
    mesh and on composed batch × … meshes, DESIGN.md §8/§9)."""
    return mesh.shape[BATCH_AXIS]


# ---------------------------------------------------------------------------
# padding: B % D != 0 rides along as inert instances
# ---------------------------------------------------------------------------

def pad_batch_tree(tree, batch: int, multiple: int, pad_tree):
    """Pad every leaf's leading ``batch`` dim up to a multiple of ``multiple``
    by appending rows from ``pad_tree`` (an inert-instance tree of the same
    structure with any leading dim >= the padding)."""
    pad = -batch % multiple
    if pad == 0:
        return tree
    return jax.tree.map(
        lambda x, f: jnp.concatenate([x, f[:pad]], axis=0), tree, pad_tree
    )


def unpad_batch_tree(tree, batch: int):
    return jax.tree.map(lambda x: x[:batch], tree)


def inert_pool(num_slots: int, num_places: int, batch: int) -> kp.PoolState:
    """Fresh (empty) pool instances: no active tasks, so a phase on them pops
    nothing and pushes nothing — safe batch padding."""
    return batched.init_pool(num_slots, num_places, batch=batch)


# ---------------------------------------------------------------------------
# sharded phase_pop
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _sharded_phase_pop_fn(
    mesh: Mesh,
    num_places: int,
    k: int,
    policy: kp.Policy,
    arbitration: str,
    topk_backend: str,
    block_size: int,
):
    """Build (and cache per config) the jitted shard_map phase program: each
    device advances its local B/D instances with the natively-batched engine
    — one fused-arbitration kernel launch per device, no collectives."""

    def local(state, keys):
        return batched.phase_pop(
            state, keys, num_places=num_places, k=k, policy=policy,
            arbitration=arbitration, topk_backend=topk_backend,
            block_size=block_size,
        )

    f = jax.shard_map(
        local, mesh=mesh,
        in_specs=(PS(BATCH_AXIS), PS(BATCH_AXIS)),
        out_specs=(PS(BATCH_AXIS), PS(BATCH_AXIS)),
        # no collectives to check, and the Pallas stage-1 kernel is traced
        # without varying-axis types
        check_vma=False,
    )
    return jax.jit(f)


def phase_pop_sharded(
    state: kp.PoolState,
    keys: jax.Array,          # [B] batch of PRNG keys
    *,
    mesh: Mesh,
    num_places: int,
    k: int,
    policy: kp.Policy,
    arbitration: str = "fused",
    topk_backend: str = "auto",
    block_size: int = 1024,
) -> Tuple[kp.PoolState, kp.PopResult]:
    """Batched :func:`kpriority.phase_pop` sharded over ``mesh``'s batch axis
    (DESIGN.md §8; state leaves [B, M]/[B, P]/[B, P, M], keys [B]).

    Bit-identical to :func:`batched.phase_pop` on one device (instances never
    interact, so sharding the batch axis only changes placement — each
    instance's ignored ≤ ρ guarantee (§2) is untouched). B need not divide
    the device count: the batch is padded with inert instances and the
    padding is sliced off the result.
    """
    b = state.prio.shape[0]
    d = batch_axis_size(mesh)
    pad = -b % d
    if pad:
        m, p = state.prio.shape[1], state.unpub_pushes.shape[1]
        state = pad_batch_tree(state, b, d, inert_pool(m, p, pad))
        keys = jnp.concatenate([keys, jnp.zeros((pad, 2), keys.dtype)], axis=0)
    fn = _sharded_phase_pop_fn(
        mesh, num_places, k, policy, arbitration, topk_backend, block_size
    )
    new_state, res = fn(state, keys)
    if pad:
        new_state = unpad_batch_tree(new_state, b)
        res = unpad_batch_tree(res, b)
    return new_state, res


# ---------------------------------------------------------------------------
# admission-pool placement on a composed serving mesh (DESIGN.md §9)
# ---------------------------------------------------------------------------

# per-place / scalar bookkeeping leaves of PoolState and AdmissionBuffer:
# always replicated — sharding tiny [P] counters over batch would force
# gratuitous collectives into every fold/pop
_ADMISSION_REPLICATED_FIELDS = frozenset({"unpub_pushes", "next_seq", "count"})


def admission_shardings(mesh: Mesh, tree):
    """NamedShardings placing a device-resident admission pool (or its
    staging buffers) on a composed serving mesh
    (``launch.mesh.make_production_batch_mesh``): leaves whose trailing dim
    is slot-like — the [M]/[P, M] ``PoolState`` task leaves, the [P, C]
    ``AdmissionBuffer`` staging rows — shard over ``batch`` when divisible;
    the per-place/scalar bookkeeping fields (``unpub_pushes``, ``next_seq``,
    ``count``) and non-divisible leaves replicate; everything replicates
    over the data/model axes, i.e. the pool co-locates with the model shards
    it schedules for. Placement only: the admission ops are ordinary jit
    programs, so GSPMD inserts whatever collectives the sharded argmin/
    scatter need — semantics (and the host-oracle equivalence, §9) are
    unchanged on any mesh."""
    from jax.sharding import NamedSharding

    d = batch_axis_size(mesh)

    def spec_for(name, x):
        if (name in _ADMISSION_REPLICATED_FIELDS or x.ndim == 0
                or x.shape[-1] % d != 0):
            return NamedSharding(mesh, PS())
        return NamedSharding(
            mesh, PS(*((None,) * (x.ndim - 1) + (BATCH_AXIS,)))
        )

    if hasattr(tree, "_fields"):   # PoolState / AdmissionBuffer NamedTuples
        return type(tree)(
            *(spec_for(n, getattr(tree, n)) for n in tree._fields)
        )
    return jax.tree.map(lambda x: spec_for("", x), tree)


def klsm_shardings(mesh: Mesh, store):
    """NamedShardings for the klsm level store (``kpriority.KlsmState``,
    DESIGN.md §15) on a composed serving mesh: replicate every leaf except
    ``in_level`` (the only [M] slot-indexed leaf, which follows the pool's
    slot placement). The level rows are [P, W]/[P, K] sorted runs that the
    cascade/merge reads and rewrites wholesale — sharding a sort network's
    operand over ``batch`` would buy nothing but collectives — and the
    front probe only gathers P·L heads from them. Placement only, like
    :func:`admission_shardings`: klsm ops are ordinary jit programs and the
    host equivalence is mesh-independent."""
    from jax.sharding import NamedSharding

    d = batch_axis_size(mesh)
    rep = NamedSharding(mesh, PS())

    def spec_for(name, x):
        if name == "in_level" and x.ndim == 1 and x.shape[0] % d == 0:
            return NamedSharding(mesh, PS(BATCH_AXIS))
        return rep

    return type(store)(
        *(spec_for(n, getattr(store, n)) for n in store._fields)
    )


def slot_dim_sharding(mesh: Mesh):
    """THE slot-dim placement rule, shared by the eager engine's decode
    caches, the fused carry, and the fused staging (DESIGN.md §9.4/§10):
    returns a spec fn sharding axis 1 (the slot dim, the engine cache
    convention) over ``batch`` when divisible, replicating otherwise (same
    divisibility fallback as launch/sharding.py). One definition on purpose
    — eager and fused placement must stay identical on any mesh."""
    from jax.sharding import NamedSharding

    d = batch_axis_size(mesh)
    rep = NamedSharding(mesh, PS())

    def spec(x):
        if x.ndim >= 2 and x.shape[1] % d == 0:
            return NamedSharding(mesh, PS(None, BATCH_AXIS))
        return rep

    return spec


def fused_carry_shardings(mesh: Mesh, carry):
    """NamedShardings for the fused serving step's scan carry
    (serve/fused_step.py, DESIGN.md §10/§11) on a composed
    ``make_production_batch_mesh``: the admission pool follows
    :func:`admission_shardings`; decode-cache leaves shard their slot dim
    (axis 1, the engine's cache convention) over ``batch`` when divisible —
    the same placement ``ServeEngine(mesh=...)`` gives the eager path, so
    the fused program's decode slots stay co-located with the pool shards
    that feed them; the tiny per-slot cursor/priority/uid vectors replicate;
    the resume staging (in the carry since §11 — preemption writes it
    in-trace) follows :func:`fused_staging_shardings`. Placement only: the
    fused step is an ordinary jit program, so GSPMD supplies whatever
    collectives the sharded pops/splices need and the host-oracle
    equivalence holds on any mesh (§9.4)."""
    from jax.sharding import NamedSharding

    cache_spec = slot_dim_sharding(mesh)
    rep = NamedSharding(mesh, PS())
    st_sh, sc_sh = fused_staging_shardings(
        mesh, carry.staging, carry.staged_caches)
    return carry._replace(
        pool=admission_shardings(mesh, carry.pool),
        caches=jax.tree.map(cache_spec, carry.caches),
        cur_tok=rep, pos=rep, slot_req=rep, out_len=rep, budget=rep,
        slot_prio=rep, slot_uid=rep, slot_creator=rep,
        slot_deadline=rep, clock=rep,
        staging=st_sh, staged_caches=sc_sh,
        # ping-pong arrival plans (§12): tiny [2, P, C] bookkeeping the
        # boundary fold reads in full — replicate, like the buffers
        plan=jax.tree.map(lambda _: rep, carry.plan),
        plan_sel=rep,
        # §16 pop-contract scalars: the MQ attempt counter and the abort
        # tally are global bookkeeping, like clock
        mq_pops=rep, pop_aborts=rep,
        # klsm level store (§15): None under storage="flat" (empty subtree)
        store=(None if carry.store is None
               else klsm_shardings(mesh, carry.store)),
    )


def fused_staging_shardings(mesh: Mesh, staging, staged_caches):
    """Shardings for the fused step's prefill staging (serve/fused_step.py):
    staged cache leaves shard the pool-slot dim (axis 1) over ``batch`` when
    divisible — consistent with :func:`admission_shardings`' placement of
    the pool they are keyed by — and the scalar-per-slot vectors replicate.
    Returns ``(staging_shardings, staged_cache_shardings)``."""
    from jax.sharding import NamedSharding

    rep = NamedSharding(mesh, PS())
    return (
        jax.tree.map(lambda _: rep, staging),
        jax.tree.map(slot_dim_sharding(mesh), staged_caches),
    )


# ---------------------------------------------------------------------------
# batch × place composition: B instances of the explicit-collective engine
# ---------------------------------------------------------------------------

def make_engine_batched(mesh: Mesh, m_loc: int, g_cap: int, k: int, k_buf: int):
    """B instances of the shard_map hybrid engine (core/distributed.py) on a
    (batch × place) mesh (DESIGN.md §8): state leaves are [B, P, ...]; the
    ``batch`` axis is collective-free, the per-phase publication/proposal
    all_gathers run over ``place`` only — so each instance keeps the hybrid
    structure's ρ = P·k bound with traffic independent of queue depth.
    Returns jitted (state, pushes) ->
    (state, popped_ids [B, P], popped_prios [B, P])."""
    from repro.core import distributed as dist

    spec = PS(BATCH_AXIS, dist.AXIS)

    @functools.partial(
        jax.shard_map, mesh=mesh,
        in_specs=(spec, (spec, spec)),
        out_specs=(spec, spec, spec),
    )
    def step(state, pushes):
        st = jax.tree.map(lambda a: a[0, 0], state)   # drop (batch, place)
        prios, tids = pushes

        def body(s, xy):
            pr, ti = xy
            return jax.lax.cond(
                ti >= 0, lambda ss: dist._push_local(ss, pr, ti),
                lambda ss: ss, s,
            ), None

        st, _ = jax.lax.scan(body, st, (prios[0, 0], tids[0, 0]))
        st, pid, pprio = dist.phase(st, k, k_buf)
        st = jax.tree.map(lambda a: a[None, None], st)
        return st, pid[None, None], pprio[None, None]

    return jax.jit(step)


# ---------------------------------------------------------------------------
# cross-pod work-stealing of published blocks (DESIGN.md §14.1)
# ---------------------------------------------------------------------------

POD_AXIS = "pod"


def make_pod_engine(
    mesh: Mesh, *, num_slots: int, k: int, block_cap: int,
    margin: float = 0.0,
):
    """The pod-scale steal plane on a ``batch × pod [× data × model]`` mesh
    (``launch.mesh.make_production_batch_mesh(multi_pod=True)``): each pod
    owns a :class:`kpriority.PodState` slot pool (state leaves [N_POD, ...],
    sharded over ``pod``; the batch/data/model axes replicate — the pool
    co-locates with every model shard of its pod). One jitted step =
    push → steal → pop, with the steal phase's ONLY collective a bounded
    all_gather over ``pod`` of (header, front, serialized-best-block)
    triples — ≤ N·(block_cap + 5) scalars per phase, independent of queue
    depth, the paper's traffic argument lifted to the pod level. The claim
    scan itself (:func:`kpriority.pod_steal_plan`) runs replicated on every
    pod from the gathered headers, mirroring ``distributed.phase``'s
    deterministic CAS-winner analogue.

    Returns jitted ``(state, (prios f32[N, n], uids i32[N, n]))
    -> (state, fire bool[N], victim i32[N], pop_prio f32[N],
    pop_uid i32[N], pop_valid bool[N])``; ``uids < 0`` are padding.
    Host twin: ``host_queue.HostPodQueues`` (bit-identical — the
    ``--selftest-pod`` differential and tests/test_sharded_batch.py pin it).
    """
    spec = PS(POD_AXIS)

    @functools.partial(
        jax.shard_map, mesh=mesh,
        in_specs=(spec, (spec, spec)),
        out_specs=(spec, spec, spec, spec, spec, spec),
    )
    def step(state, pushes):
        st = jax.tree.map(lambda a: a[0], state)          # drop pod dim
        prios, uids = pushes
        st = kp.pod_push(st, prios[0], uids[0], k=k)

        # my header/front/payload, then the one bounded collective
        head_p, head_u, has, members = kp.pod_best_block(st)
        _, front_p, _, front_v = kp.pod_front(st)
        pay_p, pay_u = kp.pod_extract_block(st, members, block_cap)
        heads_p = jax.lax.all_gather(head_p, POD_AXIS)    # [N]
        heads_u = jax.lax.all_gather(head_u, POD_AXIS)
        hases = jax.lax.all_gather(has, POD_AXIS)
        fronts_p = jax.lax.all_gather(front_p, POD_AXIS)
        fronts_v = jax.lax.all_gather(front_v, POD_AXIS)
        pays_p = jax.lax.all_gather(pay_p, POD_AXIS)      # [N, block_cap]
        pays_u = jax.lax.all_gather(pay_u, POD_AXIS)

        n = heads_p.shape[0]
        claimed0 = vary_like(jnp.zeros((n,), bool), heads_p)
        fire, victim = kp.pod_steal_plan(
            heads_p, heads_u, hases, fronts_p, fronts_v,
            margin=margin, claimed0=claimed0,
        )

        # apply: remove my block if claimed (pre-phase members — payloads
        # were extracted before any pod mutates), splice my stolen payload
        me = jax.lax.axis_index(POD_AXIS)
        st = jax.lax.cond(
            jnp.any(fire & (victim == me)),
            lambda s: kp.pod_remove_block(s, members), lambda s: s, st,
        )
        my_fire, my_victim = fire[me], victim[me]
        st = jax.lax.cond(
            my_fire,
            lambda s: kp.pod_insert_block(
                s, pays_p[my_victim], pays_u[my_victim]),
            lambda s: s, st,
        )

        st, pop_p, pop_u, pop_v = kp.pod_pop(st)
        st = jax.tree.map(lambda a: a[None], st)
        return (st, my_fire[None], my_victim[None],
                pop_p[None], pop_u[None], pop_v[None])

    return jax.jit(step)


def init_pod_sharded(num_slots: int, num_pods: int) -> kp.PodState:
    """[N_POD, ...] pod-state tree for :func:`make_pod_engine`."""
    return jax.tree.map(
        lambda a: jnp.broadcast_to(a, (num_pods,) + a.shape),
        kp.init_pod(num_slots),
    )


# ---------------------------------------------------------------------------
# selftest (subprocess: device count locks at jax init)
# ---------------------------------------------------------------------------

def _assert_trees_equal(a, b, msg):  # pragma: no cover - selftest helper
    import numpy as np

    for la, lb in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb),
                                      err_msg=msg)


def _selftest_pool_bit_identity(nbatch: int):  # pragma: no cover
    """phase_pop_sharded == batched.phase_pop, bit-for-bit, over a multi-phase
    push/pop trace (covers the padded B % D != 0 path when nbatch % D != 0)."""
    import numpy as np

    from repro.launch.mesh import make_batch_mesh

    mesh = make_batch_mesh()
    m, places, k, phases = 96, 4, 3, 6
    policy = kp.Policy.HYBRID
    rng = np.random.default_rng(11)
    st_ref = batched.init_pool(m, places, batch=nbatch)
    st_shard = batched.init_pool(m, places, batch=nbatch)

    for t in range(phases):
        mask = jnp.asarray(rng.random((nbatch, m)) < 0.2)
        prios = jnp.asarray(rng.random((nbatch, m)).astype(np.float32))
        creators = jnp.asarray(
            rng.integers(0, places, (nbatch, m)).astype(np.int32))
        push_keys = jnp.stack(
            [jax.random.PRNGKey(100 * t + b) for b in range(nbatch)])
        pop_keys = jnp.stack(
            [jax.random.PRNGKey(900 * t + b) for b in range(nbatch)])
        st_ref = batched.push(
            st_ref, mask, prios, creators, k=k, policy=policy, key=push_keys)
        st_shard = batched.push(
            st_shard, mask, prios, creators, k=k, policy=policy, key=push_keys)
        st_ref, res_ref = batched.phase_pop(
            st_ref, pop_keys, num_places=places, k=k, policy=policy)
        st_shard, res_shard = phase_pop_sharded(
            st_shard, pop_keys, mesh=mesh,
            num_places=places, k=k, policy=policy)
        _assert_trees_equal(res_ref, res_shard, f"B={nbatch} phase {t} result")
        _assert_trees_equal(st_ref, st_shard, f"B={nbatch} phase {t} state")
    print(f"SHARDED_POOL_OK B={nbatch} D={batch_axis_size(mesh)}")


def _selftest_sssp_bit_identity(graphs: int):  # pragma: no cover
    """run_sssp_batched(mesh=) == run_sssp_batched() per graph."""
    import numpy as np

    from repro.core.engine import run_sssp_batched
    from repro.core.sssp import dijkstra_ref, make_er_graph
    from repro.launch.mesh import make_batch_mesh

    ws = np.stack([make_er_graph(40 + g, 60, 0.15) for g in range(graphs)])
    finals = np.stack([dijkstra_ref(w) for w in ws])
    kwargs = dict(num_places=4, k=2, policy=kp.Policy.HYBRID,
                  seeds=list(range(graphs)), finals=finals)
    ref = run_sssp_batched(ws, **kwargs)
    shard = run_sssp_batched(ws, mesh=make_batch_mesh(), **kwargs)
    assert len(shard.runs) == graphs
    for g in range(graphs):
        np.testing.assert_array_equal(shard.runs[g].dist, ref.runs[g].dist)
        assert shard.runs[g].phases == ref.runs[g].phases, g
        assert shard.runs[g].total_relaxed == ref.runs[g].total_relaxed, g
        assert shard.runs[g].total_pushes == ref.runs[g].total_pushes, g
        assert shard.runs[g].correct
    print(f"SHARDED_SSSP_OK G={graphs}")


def selftest_batch_place(nbatch: int, nplace: int):  # pragma: no cover
    """Exactly-once per instance on the composed (batch × place) engine,
    on the first ``nbatch × nplace`` devices."""
    import numpy as np

    from repro.core import distributed as dist
    from repro.launch.mesh import make_batch_place_mesh

    mesh = make_batch_place_mesh(nbatch, nplace)
    m_loc, g_cap, k, k_buf = 32, 256, 3, 8
    engine = make_engine_batched(mesh, m_loc, g_cap, k, k_buf)
    state = jax.tree.map(
        lambda a: jnp.broadcast_to(a, (nbatch, nplace) + a.shape),
        dist.init_state(m_loc, g_cap),
    )
    rng = np.random.default_rng(5)
    n_push = 4
    pushed = [set() for _ in range(nbatch)]
    popped = [[] for _ in range(nbatch)]
    tid = 0
    for phase_i in range(120):
        pr = np.full((nbatch, nplace, n_push), np.inf, np.float32)
        ti = np.full((nbatch, nplace, n_push), -1, np.int32)
        if phase_i < 5:
            for b in range(nbatch):
                for pl in range(nplace):
                    for j in range(rng.integers(1, n_push)):
                        pr[b, pl, j] = rng.random()
                        ti[b, pl, j] = tid
                        pushed[b].add(tid)
                        tid += 1
        state, pid, _ = engine(state, (jnp.asarray(pr), jnp.asarray(ti)))
        ids = np.asarray(pid)
        for b in range(nbatch):
            popped[b].extend(int(i) for i in ids[b].ravel() if i >= 0)
        if phase_i >= 5 and not (ids >= 0).any():
            break
    for b in range(nbatch):
        assert sorted(popped[b]) == sorted(pushed[b]), (
            f"instance {b}: {len(popped[b])} popped vs {len(pushed[b])} pushed")
    print(f"BATCH_PLACE_OK B={nbatch} P={nplace}")


def _selftest_serve_mesh():  # pragma: no cover
    """ServeEngine(mesh=) must emit token streams identical to the unsharded
    engine (decode is argmax-deterministic; slot axis shards D ways)."""
    import numpy as np

    from repro.configs import get_reduced
    from repro.launch.mesh import make_batch_mesh
    from repro.models import materialize, model_p
    from repro.serve.config import ServeConfig
    from repro.serve.engine import Request, ServeEngine

    cfg = get_reduced("qwen3_1_7b")
    params = materialize(jax.random.PRNGKey(0), model_p(cfg))
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, cfg.vocab_size, 6).astype(np.int32)
               for _ in range(6)]

    def run(mesh):
        eng = ServeEngine(cfg, params, slots=len(jax.devices()), max_len=32,
                          frontends=2, k=2, config=ServeConfig(mesh=mesh))
        for i, toks in enumerate(prompts):
            eng.submit(Request(rid=i, tokens=toks, max_new=4,
                               priority=float(i)), frontend=i % 2)
        eng.flush_frontends()
        return {r.rid: r.out for r in eng.run()}

    ref = run(None)
    sharded = run(make_batch_mesh())
    assert ref.keys() == sharded.keys()
    for rid in ref:
        assert ref[rid] == sharded[rid], (rid, ref[rid], sharded[rid])
    print(f"SERVE_MESH_OK slots={len(jax.devices())}")


def selftest_pod(mesh: Mesh | None = None, seed: int = 7,
                 phases: int = 90) -> None:  # pragma: no cover
    """Cross-pod steal plane == HostPodQueues replay, bit-for-bit: steal
    decisions (fire + victim), per-pod pop streams, and the full sorted
    (prio, uid, block) state records after every phase, over a randomized
    uneven-push trace on ``mesh`` (default: the 8-device multi-pod test
    mesh); exactly-once at drain."""
    import numpy as np

    from repro.core.host_queue import HostPodQueues
    from repro.launch.mesh import make_test_production_batch_mesh

    if mesh is None:
        mesh = make_test_production_batch_mesh(multi_pod=True)
    npods = mesh.shape[POD_AXIS]
    m, k, n_push, margin = 128, 3, 4, 0.25
    block_cap = k + n_push
    engine = make_pod_engine(
        mesh, num_slots=m, k=k, block_cap=block_cap, margin=margin)
    state = init_pod_sharded(m, npods)
    host = HostPodQueues(npods, k=k, block_cap=block_cap, margin=margin)

    rng = np.random.default_rng(seed)
    uid = 0
    pushed, popped = set(), []
    steals = 0
    for phase_i in range(phases):
        pr = np.full((npods, n_push), np.inf, np.float32)
        ui = np.full((npods, n_push), -1, np.int32)
        if phase_i < 12:
            for p in range(npods):
                # uneven on purpose: pods that drain early must steal
                for j in range(rng.integers(0, n_push + 1)):
                    pr[p, j] = np.float32(rng.random())
                    ui[p, j] = uid
                    pushed.add(uid)
                    uid += 1
        for p in range(npods):
            host.push(p, [(float(pr[p, j]), int(ui[p, j]))
                          for j in range(n_push) if ui[p, j] >= 0])
        host_plan = {t: (v, pay) for (t, v, pay) in host.steal_phase()}
        host_pops = [host.pop(p) for p in range(npods)]

        state, fire, victim, pop_p, pop_u, pop_v = engine(
            state, (jnp.asarray(pr), jnp.asarray(ui)))
        fire, victim = np.asarray(fire), np.asarray(victim)
        pop_p, pop_u = np.asarray(pop_p), np.asarray(pop_u)
        pop_v = np.asarray(pop_v)
        prio_a, uid_a = np.asarray(state.prio), np.asarray(state.uid)
        blk_a = np.asarray(state.block)

        for p in range(npods):
            assert bool(fire[p]) == (p in host_plan), (phase_i, p)
            if fire[p]:
                assert int(victim[p]) == host_plan[p][0], (phase_i, p)
                steals += 1
            hp = host_pops[p]
            assert bool(pop_v[p]) == (hp is not None), (phase_i, p)
            if hp is not None:
                assert (float(pop_p[p]), int(pop_u[p])) == hp, (phase_i, p)
                popped.append(int(pop_u[p]))
            dev = sorted(
                (float(prio_a[p, i]), int(uid_a[p, i]), int(blk_a[p, i]))
                for i in range(m) if uid_a[p, i] >= 0)
            assert dev == host.snapshot(p), (phase_i, p)
        if phase_i >= 12 and len(host) == 0:
            break
    assert len(host) == 0, f"{len(host)} items left after {phases} phases"
    assert sorted(popped) == sorted(pushed), (
        f"exactly-once violated: {len(popped)} popped vs {len(pushed)} pushed")
    assert steals > 0, "trace never exercised a steal"
    print(f"POD_STEAL_OK pods={npods} tasks={len(pushed)} steals={steals}")


def selftest() -> None:  # pragma: no cover - exercised via subprocess
    d = len(jax.devices())
    _selftest_pool_bit_identity(d)            # B divisible by D
    _selftest_pool_bit_identity(d - 2)        # B % D != 0: padded path
    _selftest_sssp_bit_identity(d)
    _selftest_sssp_bit_identity(d - 3)        # padded SSSP batch
    if d >= 8:
        selftest_batch_place(2, 4)
    _selftest_serve_mesh()
    print(f"SHARDED_OK devices={d}")


if __name__ == "__main__":
    import sys

    if "--selftest-pod" in sys.argv:
        selftest_pod()
    elif "--selftest" in sys.argv:
        selftest()
