"""Device-resident streaming admission (DESIGN.md §9).

The serving hot loop used to route every front-end push through the host-side
``HybridKQueue`` — the exact centralization the paper's hybrid structure
exists to avoid. This module is the device-resident port: front-end pushes
append to **per-place device buffers** (one jitted scatter, no host queue, no
readback), and between decode steps a single jitted **fold** drains the
buffers into the device-resident ``PoolState`` with *stream-accurate*
publish-on-k — each place publishes its local list at exactly the push that
brings its unpublished count to k, replayed from the buffered arrival order,
so the visible set at every pop equals the host queue's bit-for-bit.
Admission pops are :func:`repro.core.kpriority.stream_pop` (published ∪ own ∪
persistent spy refs, deterministic min-index spy, (priority, seq) tie-break
== the host heap's (priority, uid)).

Equivalence contract (tests/test_streaming.py, and under the 8-device
composed mesh via ``python -m repro.serve.streaming --selftest``): on any
trace of push bursts / folds / pop bursts, :class:`StreamingAdmitter` pops
the same (priority, item) sequence as ``HybridKQueue(spy="min_index")`` with
pushes applied at the preceding fold point. The ρ = P·k ordering bound holds
throughout (the pool is the §2 HYBRID structure; the fold publishes exactly
the host queue's publication set, never less).
"""
from __future__ import annotations

import functools
import threading
import weakref
from collections import ChainMap
from typing import Any, Callable, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import kpriority as kp

INF = jnp.inf


# ---------------------------------------------------------------------------
# dispatch accounting: instance-scoped counters + an aggregating ledger
# ---------------------------------------------------------------------------

class _DispatchCell:
    """One instance's dispatch counter (a tiny mutable cell so the ledger's
    finalizer can fold the count of a dead instance without resurrecting
    it)."""

    __slots__ = ("n", "__weakref__")

    def __init__(self):
        self.n = 0


class DispatchLedger:
    """Aggregate view over per-instance dispatch counters — one ledger per
    serve-plane class. Counters are *instance-scoped* (two live engines can
    never skew each other's counts — the PR-5 class-level counter did
    exactly that), and the ledger folds a dying instance's count into a
    retired total, so :meth:`total` is the same monotone
    dispatches-since-import aggregate the old class attribute provided,
    now by aggregation instead of shared mutation. benchmarks/run.py
    snapshot-deltas ``total()`` around each section."""

    def __init__(self):
        self._cells: set = set()
        self._retired = 0
        self._lock = threading.Lock()

    def attach(self, owner) -> _DispatchCell:
        cell = _DispatchCell()
        with self._lock:
            self._cells.add(cell)
        weakref.finalize(owner, self._retire, cell)
        return cell

    def _retire(self, cell: _DispatchCell):
        with self._lock:
            self._cells.discard(cell)
            self._retired += cell.n

    def total(self) -> int:
        with self._lock:
            return self._retired + sum(c.n for c in self._cells)


# ---------------------------------------------------------------------------
# shared-but-weakly-held jitted helpers (compile sharing without pinning)
# ---------------------------------------------------------------------------

class _JitHolder:
    """Weak-referenceable callable wrapper for a shared jitted helper: live
    engines with the same static config share one compiled program through
    the weak-value cache below, and when the last holder dies the entry —
    with its compiled executables, their baked device constants, and any
    mesh references in their sharding keys — is freed instead of being
    pinned module-wide for the process lifetime (the PR-5 ``lru_cache``
    retained all of it forever)."""

    __slots__ = ("fn", "__weakref__")

    def __init__(self, fn):
        self.fn = fn

    def __call__(self, *args, **kwargs):
        return self.fn(*args, **kwargs)


_jit_cache: "weakref.WeakValueDictionary" = weakref.WeakValueDictionary()
_jit_cache_lock = threading.Lock()


def shared_jit(key, build: Callable[[], Callable]) -> _JitHolder:
    """Return the weakly-cached :class:`_JitHolder` for ``key``, building
    (and jitting) via ``build()`` on first use. Callers MUST keep a strong
    reference to the returned holder for as long as they want the compile
    shared — a transient lookup compiles, runs, and is dropped."""
    with _jit_cache_lock:
        holder = _jit_cache.get(key)
        if holder is None:
            holder = _JitHolder(build())
            _jit_cache[key] = holder
        return holder


class AdmissionBuffer(NamedTuple):
    """Per-place device staging buffers — the local lists' streaming inbox.

    ``arrival`` is the global submission index (the host queue's uid): the
    fold assigns pool ``seq`` in arrival order so priority ties break
    identically to the host heap. C (buffer capacity) is static; ``count[p]``
    is the live prefix length of place p's rows.
    """

    prio: jnp.ndarray      # f32[P, C]
    slot: jnp.ndarray      # i32[P, C]  pool slot reserved for the item
    arrival: jnp.ndarray   # i32[P, C]  global arrival index (uid analogue)
    count: jnp.ndarray     # i32[P]


def init_buffer(num_places: int, cap: int) -> AdmissionBuffer:
    return AdmissionBuffer(
        prio=jnp.full((num_places, cap), INF, jnp.float32),
        slot=jnp.full((num_places, cap), -1, jnp.int32),
        arrival=jnp.zeros((num_places, cap), jnp.int32),
        count=jnp.zeros((num_places,), jnp.int32),
    )


def buffer_push(
    buf: AdmissionBuffer,
    place: jnp.ndarray,     # i32[]
    slot: jnp.ndarray,      # i32[]
    prio: jnp.ndarray,      # f32[]
    arrival: jnp.ndarray,   # i32[]
) -> AdmissionBuffer:
    """Append one push to ``place``'s device buffer (pure jnp scatter; the
    whole front-end push path — no host-side queue state). The caller
    guarantees room (StreamingAdmitter auto-folds on a full buffer)."""
    i = buf.count[place]
    return AdmissionBuffer(
        prio=buf.prio.at[place, i].set(jnp.float32(prio)),
        slot=buf.slot.at[place, i].set(jnp.int32(slot)),
        arrival=buf.arrival.at[place, i].set(jnp.int32(arrival)),
        count=buf.count.at[place].add(1),
    )


def fold(
    pool: kp.PoolState,
    buf: AdmissionBuffer,
    *,
    k: int,
    force: bool = False,
    force_places: Optional[jnp.ndarray] = None,   # bool[P], traced
    count_clobbers: bool = False,
) -> Tuple[kp.PoolState, AdmissionBuffer]:
    """Drain the buffers into the pool with stream-accurate publish-on-k.

    Replays each place's buffered pushes in arrival order against its
    ``unpub_pushes`` counter u (< k between folds, the host invariant):
    with c buffered pushes there are ``(u + c) // k`` publish events; the
    first publishes the place's pre-existing unpublished items too, and
    buffered item j (0-based stream index) is published iff
    ``j < ((u + c) // k) * k - u``. The new counter is ``(u + c) mod k`` —
    exactly ``len(local)`` after the host queue processed the same pushes,
    so the post-fold visible set matches ``HybridKQueue`` bit-for-bit
    (DESIGN.md §9). ``force`` (or k == 0) publishes everything — the
    ``flush`` analogue; ``force_places`` (bool[P], traced) flushes exactly
    the marked places while the rest keep stream-accurate publish-on-k —
    the per-place ``HybridKQueue.flush(p)`` analogue (because publication
    is a pure function of each place's stream position, draining the other
    places' buffered rows early is transparent, DESIGN.md §9.1/§10).
    Publishing is monotone ⇒ ignored ≤ P·k is preserved.

    One fused device program: pure jnp, jit/shard_map-compatible; returns
    the updated pool and an empty buffer. ``count_clobbers=True`` arms the
    admission-plane capacity guard: colliding writes to LIVE pool slots are
    masked out (the incumbent request survives) and the return grows a
    third element — the i32[] collision count — which
    :class:`StreamingAdmitter` accumulates and surfaces as a loud error
    (ISSUE 9 satellite; the phase plane keeps the default overwrite
    semantics, where slot reuse IS the paper's dead-task elimination).
    """
    num_places, cap = buf.prio.shape
    m = pool.prio.shape[0]
    j = jnp.arange(cap, dtype=jnp.int32)[None, :]           # [1, C]
    valid = j < buf.count[:, None]                          # [P, C]

    if force or k == 0:
        limit = buf.count                                   # publish all
        pub_prev = jnp.ones((num_places,), bool)
        new_unpub = jnp.zeros((num_places,), jnp.int32)
    else:
        total = pool.unpub_pushes + buf.count               # [P]
        events = total // k
        limit = events * k - pool.unpub_pushes
        pub_prev = events >= 1
        new_unpub = total - events * k
        if force_places is not None:
            limit = jnp.where(force_places, buf.count, limit)
            pub_prev = pub_prev | force_places
            new_unpub = jnp.where(force_places, 0, new_unpub)

    # scatter the buffered items into slot-indexed [M] layouts (invalid rows
    # target index M and are dropped; live slots are unique by construction —
    # a slot is only re-buffered after its previous item was popped)
    tgt = jnp.where(valid, buf.slot, m).reshape(-1)
    places = jnp.broadcast_to(
        jnp.arange(num_places, dtype=jnp.int32)[:, None], (num_places, cap)
    )
    mask_m = jnp.zeros((m,), bool).at[tgt].set(True, mode="drop")
    prio_m = jnp.full((m,), INF, jnp.float32).at[tgt].set(
        buf.prio.reshape(-1), mode="drop")
    creator_m = jnp.zeros((m,), jnp.int32).at[tgt].set(
        places.reshape(-1), mode="drop")
    # keep arrivals integer end-to-end: a float32 tie would collide uids
    # past 2^24 and silently break the (priority, uid) host-oracle tie-break
    arr_m = jnp.zeros((m,), jnp.int32).at[tgt].set(
        buf.arrival.reshape(-1), mode="drop")
    pub_new_m = jnp.zeros((m,), bool).at[tgt].set(
        (j < limit[:, None]).reshape(-1), mode="drop")

    if count_clobbers:
        # admission-plane capacity guard (ISSUE 9 satellite): on this plane
        # pool slots are request identities handed out by alloc_pool_slot,
        # so a buffered slot landing on a LIVE slot is never legitimate
        # "dead-task elimination" — it means capacity accounting desynced
        # and a request would be silently dropped. Mask the collision (the
        # incumbent survives) and count it; StreamingAdmitter raises when
        # the counter moves.
        clobbered = jnp.sum(mask_m & pool.active).astype(jnp.int32)
        mask_m = mask_m & ~pool.active

    st = kp.push_batch(pool, mask_m, prio_m, creator_m, tie=arr_m)
    published = (
        st.published
        | (mask_m & pub_new_m)
        | (~mask_m & st.active & pub_prev[st.creator])
    )
    st = st._replace(published=published, unpub_pushes=new_unpub)
    if count_clobbers:
        return st, init_buffer(num_places, cap), clobbered
    return st, init_buffer(num_places, cap)


def _jitted_fold(k: int, force: bool) -> _JitHolder:
    """Shared fold per (k, force): live admitter instances with the same k
    share one compiled program, but the cache holds it *weakly* — callers
    keep the returned holder alive (the old ``lru_cache`` pinned every
    (mesh, k) program, and its donated-buffer constants, for the process
    lifetime)."""
    return shared_jit(
        ("fold", k, force),
        lambda: jax.jit(
            functools.partial(fold, k=k, force=force), donate_argnums=(0, 1)
        ),
    )


def _jitted_fold_places(k: int) -> _JitHolder:
    """Shared per-place flush fold: the ``force_places`` mask is a traced
    argument, so one program serves every place choice."""

    def build():
        def f(pool, buf, mask):
            return fold(pool, buf, k=k, force_places=mask)

        return jax.jit(f, donate_argnums=(0, 1))

    return shared_jit(("fold_places", k), build)


def _jitted_fold_guarded(k: int, force: bool) -> _JitHolder:
    """Admitter-plane fold with the live-slot clobber guard: threads the
    i32[] collision counter through the same program (zero extra
    dispatches; the counter is read back at pop time, an existing sync
    point)."""

    def build():
        def f(pool, buf, clob):
            pool, buf, n = fold(pool, buf, k=k, force=force,
                                count_clobbers=True)
            return pool, buf, clob + n

        return jax.jit(f, donate_argnums=(0, 1, 2))

    return shared_jit(("fold_guard", k, force), build)


def _jitted_fold_places_guarded(k: int) -> _JitHolder:
    def build():
        def f(pool, buf, mask, clob):
            pool, buf, n = fold(pool, buf, k=k, force_places=mask,
                                count_clobbers=True)
            return pool, buf, clob + n

        return jax.jit(f, donate_argnums=(0, 1, 3))

    return shared_jit(("fold_places_guard", k), build)


def _jitted_klsm_fold(k: int, force: bool, batch_cap: int) -> _JitHolder:
    """klsm-storage fold: guarded flat fold + :func:`kp.klsm_sync` in ONE
    program — the pool stays the source of truth, the level store is
    re-derived from whatever the fold published (DESIGN.md §15)."""

    def build():
        def f(pool, buf, store, clob):
            pool, buf, n = fold(pool, buf, k=k, force=force,
                                count_clobbers=True)
            store = kp.klsm_sync(pool, store, batch_cap=batch_cap)
            return pool, buf, store, clob + n

        return jax.jit(f, donate_argnums=(0, 1, 2, 3))

    return shared_jit(("klsm_fold", k, force, batch_cap), build)


def _jitted_klsm_fold_places(k: int, batch_cap: int) -> _JitHolder:
    def build():
        def f(pool, buf, mask, store, clob):
            pool, buf, n = fold(pool, buf, k=k, force_places=mask,
                                count_clobbers=True)
            store = kp.klsm_sync(pool, store, batch_cap=batch_cap)
            return pool, buf, store, clob + n

        return jax.jit(f, donate_argnums=(0, 1, 3, 4))

    return shared_jit(("klsm_fold_places", k, batch_cap), build)


def _jitted_klsm_fold_dyn(k: int, force: bool) -> _JitHolder:
    """klsm fold for one-shot (variable-width) buffers — the fused loop's
    flush path. batch_cap derives from the buffer width at trace time, so
    each bucketed flush width compiles its own sync: the same per-width
    specialization the flat flush already pays."""

    def build():
        def f(pool, buf, store):
            pool, _ = fold(pool, buf, k=k, force=force)
            store = kp.klsm_sync(
                pool, store, batch_cap=buf.prio.shape[-1] + max(k, 1))
            return pool, store

        return jax.jit(f, donate_argnums=(0, 2))

    return shared_jit(("klsm_fold_dyn", k, force), build)


def _jitted_klsm_fold_places_dyn(k: int) -> _JitHolder:
    def build():
        def f(pool, buf, mask, store):
            pool, _ = fold(pool, buf, k=k, force_places=mask)
            store = kp.klsm_sync(
                pool, store, batch_cap=buf.prio.shape[-1] + max(k, 1))
            return pool, store

        return jax.jit(f, donate_argnums=(0, 3))

    return shared_jit(("klsm_fold_places_dyn", k), build)


def _jitted_klsm_repush(k: int, batch_cap: int) -> _JitHolder:
    """klsm twin of :func:`_jitted_repush`: the ordinary HYBRID re-push may
    publish (publish-on-k), so the level store is re-synced in the same
    program — a re-push publishes ≤ K entries for one place, well under
    ``batch_cap``."""

    def build():
        def f(pool, store, slot, place, prio):
            m = pool.prio.shape[0]
            mask = jnp.arange(m) == slot
            pool = kp.push(
                pool, mask,
                jnp.full((m,), jnp.float32(prio)),
                jnp.full((m,), jnp.int32(place), jnp.int32),
                k=k, policy=kp.Policy.HYBRID,
            )
            store = kp.klsm_sync(pool, store, batch_cap=batch_cap)
            return pool, store

        return jax.jit(f, donate_argnums=(0, 1))

    return shared_jit(("klsm_repush", k, batch_cap), build)


_jitted_buffer_push = jax.jit(buffer_push, donate_argnums=(0,))
_jitted_stream_pop = jax.jit(kp.stream_pop, donate_argnums=(0,))
_jitted_stream_peek = jax.jit(kp.stream_peek, donate_argnums=(0,))
_jitted_stream_pop_mq = jax.jit(kp.stream_pop_mq, donate_argnums=(0,))
_jitted_klsm_pop = jax.jit(kp.klsm_pop, donate_argnums=(0, 1))
_jitted_klsm_peek = jax.jit(kp.klsm_peek, donate_argnums=(1,))


def _jitted_repush(k: int) -> _JitHolder:
    """Shared immediate re-push (preemption re-queue, DESIGN.md §11):
    one item re-enters the pool through the ordinary HYBRID push/publish
    path — ``kp.push`` = ``push_batch`` + publish-on-k — with a fresh seq,
    exactly what ``HybridKQueue.push`` does for a re-queued victim."""

    def build():
        def f(pool, slot, place, prio):
            m = pool.prio.shape[0]
            mask = jnp.arange(m) == slot
            return kp.push(
                pool, mask,
                jnp.full((m,), jnp.float32(prio)),
                jnp.full((m,), jnp.int32(place), jnp.int32),
                k=k, policy=kp.Policy.HYBRID,
            )

        return jax.jit(f, donate_argnums=(0,))

    return shared_jit(("repush", k), build)


def alloc_pool_slot(occupied, next_slot: int, capacity: int):
    """THE pool-slot allocator, shared by every device admission plane
    (StreamingAdmitter and the fused loop): a monotone cursor over
    ``capacity`` slots skipping in-flight ones. One definition on purpose —
    the planes must reserve identical slot sequences on identical traces so
    their popped-slot streams stay comparable bit-for-bit
    (tests/test_fused_step.py pins this). Returns ``(slot, new_cursor)``."""
    if len(occupied) >= capacity:
        raise RuntimeError(
            f"admission pool full ({capacity} in-flight requests); "
            "raise capacity= or pop before pushing")
    while next_slot in occupied:
        next_slot = (next_slot + 1) % capacity
    return next_slot, (next_slot + 1) % capacity


# ---------------------------------------------------------------------------
# double-buffered arrival plans (continuous serving, DESIGN.md §12)
# ---------------------------------------------------------------------------

class PlanSlot:
    """One host-side arrival plan: the packer's half of a double-buffered
    ``AdmissionBuffer``. The packer ``publish``\\ es submissions into the
    open slot while the device runs a chunk against the other; at the chunk
    boundary the consumer ``seal``\\ s (via :class:`PlanBook`), uploads the
    arrays into the device-resident plan slot, and ``clear``\\ s. Arrays are
    numpy so packing never touches the device — upload is one scatter at the
    boundary."""

    def __init__(self, num_places: int, cap: int):
        self.num_places = num_places
        self.cap = cap
        self.clear()

    def publish(self, place: int, pool_slot: int, prio: float,
                arrival: int) -> bool:
        """Append one submission to ``place``'s row; False = row full
        (backpressure — the packer waits for the next seal and the entry
        spills into the next plan)."""
        i = int(self.count[place])
        if i >= self.cap:
            return False
        self.prio[place, i] = np.float32(prio)
        self.slot[place, i] = pool_slot
        self.arrival[place, i] = arrival
        self.count[place] += 1
        self.entries.append((int(place), int(pool_slot), float(prio),
                             int(arrival)))
        return True

    def total(self) -> int:
        return int(self.count.sum())

    def clear(self):
        """Start an empty plan in FRESH arrays. Never refill in place: the
        sealed arrays were just handed to an asynchronous upload, which may
        still read them (JAX can use a numpy buffer without copying it)."""
        p, c = self.num_places, self.cap
        self.prio = np.full((p, c), np.inf, np.float32)
        self.slot = np.full((p, c), -1, np.int32)
        self.arrival = np.zeros((p, c), np.int32)
        self.count = np.zeros((p,), np.int32)
        #: publish order, (place, pool_slot, prio, arrival) — the host-side
        #: replay record the engine needs at fold time
        self.entries: List[Tuple[int, int, float, int]] = []


class PlanBook:
    """Ping-pong pair of :class:`PlanSlot`\\ s with the publish/seal
    protocol between the async packer (producer) and the chunk-dispatch loop
    (consumer). ``publish`` targets the open slot; ``seal`` hands the open
    slot to the consumer and flips, so packing of the next plan proceeds
    while the sealed one is uploaded and the chunk runs. The consumer must
    ``clear()`` a sealed slot before the next seal hands it back — ``seal``
    raises on a dirty flip target, so protocol misuse can't silently
    double-admit."""

    def __init__(self, num_places: int, cap: int):
        self._slots = (PlanSlot(num_places, cap), PlanSlot(num_places, cap))
        self._open = 0
        #: notified on every seal — blocked publishers retry into the newly
        #: opened slot (the backpressure path)
        self.cond = threading.Condition()

    def publish(self, place: int, pool_slot: int, prio: float,
                arrival: int) -> bool:
        with self.cond:
            return self._slots[self._open].publish(
                place, pool_slot, prio, arrival)

    def publish_wait(self, place: int, pool_slot: int, prio: float,
                     arrival: int, timeout: Optional[float] = None) -> bool:
        """Blocking :meth:`publish`: when the open plan's row is full, wait
        for a seal and spill into the next plan. False only on timeout."""
        with self.cond:
            while not self._slots[self._open].publish(
                    place, pool_slot, prio, arrival):
                if not self.cond.wait(timeout=timeout):
                    return False
            return True

    def seal(self) -> PlanSlot:
        """Hand the open plan to the consumer and flip — whatever the packer
        has published rides this chunk; later submissions land in the next
        plan (legal within ρ = P·k, DESIGN.md §12)."""
        with self.cond:
            sealed = self._slots[self._open]
            self._open ^= 1
            if self._slots[self._open].total() != 0:
                raise RuntimeError(
                    "plan ping-pong protocol violation: sealed slot handed "
                    "back before the consumer cleared it (would double-admit)")
            self.cond.notify_all()
            return sealed

    def pending(self) -> int:
        """Entries packed into the open plan so far (not yet sealed)."""
        with self.cond:
            return self._slots[self._open].total()


class StreamingAdmitter:
    """Device-resident drop-in for the serving ``HybridKQueue`` (DESIGN.md §9).

    ``push`` appends to a per-place device buffer (one async dispatch, no
    host queue, no readback); ``fold`` — called by the engine between decode
    steps — drains the buffers into the device pool with stream-accurate
    publish-on-k; ``pop`` is the functional :func:`kpriority.stream_pop`.
    Items themselves (request objects) stay host-side keyed by pool slot —
    only priorities, slots, and arrival order live on device, which is all
    admission arbitration needs.

    ``mesh``: place the pool on a composed serving mesh
    (``launch.mesh.make_production_batch_mesh``) — slot-indexed leaves shard
    over the ``batch`` axis (co-located with the decode slots they feed) and
    replicate over data/model, via ``sharded_batch.admission_shardings``.

    Pop order is bit-identical to ``HybridKQueue(spy="min_index")`` on the
    same trace with pushes applied at fold points (tests/test_streaming.py);
    admission therefore inherits the host path's ρ = P·k guarantee: a
    request is overtaken by at most places·k later arrivals. One contract
    caveat: the device pool stores priorities as float32, so the host
    comparison must see f32-quantized priorities too — ``ServeEngine.submit``
    quantizes at the boundary for both planes; feed this class f32-exact
    priorities when driving it directly against a host oracle.

    ``retain=True`` enables the preemption plane (DESIGN.md §11): a pop
    keeps its pool slot *reserved* (occupied for the allocator, excluded
    from ``__len__``) until the engine either :meth:`release`\\ s it on
    completion or :meth:`repush`\\ es the running item back into the queue
    with its original priority — the re-queue half of decode-slot
    preemption. With ``retain`` the pool capacity therefore bounds
    submitted-plus-running requests, not just the queued backlog.

    ``policy="multiqueue"`` (DESIGN.md §14.2) swaps the admission structure
    for the MultiQueue: a push routes to the (priority, uid)-HASHED home
    place (the ``place`` argument is ignored by design — computed host-side
    with ``kpriority.mq_place_host``, bit-identical to the traced hash),
    and a pop samples c=2 places from the instance's pop-attempt counter
    (:func:`kpriority.stream_pop_mq`; misses advance the counter too) with
    NO global top-k or fallback. Bit-identical to ``host_queue.MultiQueue``
    on any trace (tests/test_multiqueue.py). The sampled pop has no
    peek-then-pop front contract, so ``retain``/:meth:`peek`/:meth:`repush`
    (the preemption plane) are unavailable — ``ServeEngine`` rejects the
    combination up front.
    """

    #: aggregating ledger over per-instance dispatch counters — benchmarks
    #: snapshot-delta :meth:`dispatch_total` per ``--only`` section. The
    #: counters themselves are instance-scoped (``self.dispatches``), so two
    #: live admitters can never corrupt each other's deltas.
    dispatch_ledger = DispatchLedger()

    def __init__(
        self,
        num_places: int,
        k: int,
        *,
        capacity: int = 256,
        buffer_cap: int = 64,
        mesh=None,
        retain: bool = False,
        policy: str = "hybrid",
        storage: str = "flat",
    ):
        if policy not in ("hybrid", "multiqueue"):
            raise ValueError(f"unknown admission policy: {policy!r}")
        if policy == "multiqueue" and retain:
            raise ValueError(
                "policy='multiqueue' cannot retain pool slots: the sampled "
                "pop has no peek-then-pop front, so the preemption plane "
                "(the only retain user) is HYBRID-only")
        if storage not in ("flat", "klsm"):
            raise ValueError(f"unknown admission storage: {storage!r}")
        if storage == "klsm" and policy != "hybrid":
            raise ValueError(
                "storage='klsm' indexes the HYBRID published set; the "
                "MULTIQUEUE pop samples places instead of probing a global "
                "front, so it has nothing for the level store to index")
        self.num_places = num_places
        self.k = k
        self.policy = policy
        self.storage = storage
        self.capacity = capacity
        self.buffer_cap = buffer_cap
        self.retain = retain
        self.pool = kp.init_pool(capacity, num_places)
        self.buf = init_buffer(num_places, buffer_cap)
        # klsm level store (DESIGN.md §15): fixed-shape sorted levels over
        # the published set, re-derived inside the fold program. batch_cap
        # bounds newly published entries per place per sync: one fold drains
        # ≤ buffer_cap staged pushes plus ≤ K carried-unpublished.
        self.store = (kp.klsm_init(capacity, num_places, k=k)
                      if storage == "klsm" else None)
        self._batch_cap = buffer_cap + max(k, 1)
        #: device-scalar live-slot clobber counter (ISSUE 9 satellite):
        #: accumulated inside the guarded fold program, read back (and
        #: raised on) at pop time — an existing sync point, so the guard
        #: costs zero extra dispatches.
        self._clob = jnp.zeros((), jnp.int32)
        self.mesh = mesh
        if mesh is not None:
            from repro.core.sharded_batch import admission_shardings

            self.pool = jax.tree.map(
                jax.device_put, self.pool, admission_shardings(mesh, self.pool)
            )
            if self.store is not None:
                from repro.core.sharded_batch import klsm_shardings

                self.store = jax.tree.map(
                    jax.device_put, self.store,
                    klsm_shardings(mesh, self.store))
        self._items = {}                       # slot -> item (host-side)
        self._running = {}                     # slot -> item (retain mode)
        self._next_slot = 0
        self._arrival = 0
        self._pops = 0                         # MQ pop-attempt counter (§14.2)
        self._pop_misses = 0                   # aborted MQ selects (§16)
        self._staged = [0] * num_places        # unfolded pushes (host mirror)
        self._unpub = [0] * num_places         # device unpub_pushes mirror
        self._push_fn = _jitted_buffer_push
        # holders, not bare functions: keeping them on the instance is what
        # keeps the weakly-cached compiled programs alive (and shared with
        # other live admitters of the same k)
        if storage == "klsm":
            bc = self._batch_cap
            self._fold_fn = _jitted_klsm_fold(k, False, bc)
            self._flush_fn = _jitted_klsm_fold(k, True, bc)
            self._flush_place_fn = _jitted_klsm_fold_places(k, bc)
            self._pop_fn = _jitted_klsm_pop
            self._peek_fn = _jitted_klsm_peek
            self._repush_fn = _jitted_klsm_repush(k, bc)
        else:
            self._fold_fn = _jitted_fold_guarded(k, False)
            self._flush_fn = _jitted_fold_guarded(k, True)
            self._flush_place_fn = _jitted_fold_places_guarded(k)
            self._pop_fn = _jitted_stream_pop
            self._peek_fn = _jitted_stream_peek
            self._repush_fn = _jitted_repush(k)
        self._pop_mq_fn = _jitted_stream_pop_mq
        self._dispatch_cell = type(self).dispatch_ledger.attach(self)

    @property
    def dispatches(self) -> int:
        """Device programs launched by THIS instance (instance-scoped — a
        second live admitter never skews it)."""
        return self._dispatch_cell.n

    @property
    def pop_misses(self) -> int:
        """MULTIQUEUE pop attempts whose sampled draw came up empty — the
        aborted selects of the §16 pop contract (``host_queue.MultiQueue``
        mirror; 0 under HYBRID, whose pop is exact)."""
        return self._pop_misses

    def _count(self, n: int = 1):
        self._dispatch_cell.n += n

    @classmethod
    def dispatch_total(cls) -> int:
        """Monotone aggregate of every instance's dispatches since import,
        dead instances included — benchmarks/run.py snapshot-deltas this
        around each section instead of resetting shared state."""
        return cls.dispatch_ledger.total()

    # ------------------------------------------------------------------ push
    def _alloc_slot(self) -> int:
        # ChainMap: O(1) membership/len view over queued + retained slots —
        # no per-push dict copy on the submission hot path
        occupied = (ChainMap(self._items, self._running) if self._running
                    else self._items)
        s, self._next_slot = alloc_pool_slot(
            occupied, self._next_slot, self.capacity)
        return s

    def push(self, place: int, priority: float, item: Any,
             k: Optional[int] = None):
        """Stream one request into ``place``'s device buffer (lower priority
        value = admitted first, matching ``HybridKQueue.push``). ``k`` is
        accepted for signature parity but must equal the constructor's —
        per-push k-override stays a host-queue-only feature. Under
        ``policy="multiqueue"`` the ``place`` argument is ignored: the item
        buffers into its HASHED home place (``kp.mq_place_host`` of the
        f32-quantized priority and the arrival uid), exactly like
        ``host_queue.MultiQueue.push``."""
        if k is not None and min(self.k, k) != self.k:
            raise ValueError("StreamingAdmitter folds with a fixed k; "
                             "per-push k overrides are host-queue-only")
        if self.policy == "multiqueue":
            place = kp.mq_place_host(
                float(np.float32(priority)), self._arrival, self.num_places)
        if self._staged[place] >= self.buffer_cap:
            self.fold()
        slot = self._alloc_slot()
        self._items[slot] = item
        self.buf = self._push_fn(
            self.buf, place, slot, float(priority), self._arrival)
        self._arrival += 1
        self._staged[place] += 1
        self._count()

    # ----------------------------------------------------- clobber guard
    @property
    def clobbered(self) -> int:
        """Buffered pushes that targeted a LIVE pool slot and were masked
        out by the guarded fold (ISSUE 9 satellite). Always 0 in correct
        operation — the host-side allocator never hands out an occupied
        slot — so any nonzero value means the slot accounting desynced
        (e.g. the pool was mutated behind the admitter's back). Reading
        this forces a device sync; :meth:`pop_ex`/:meth:`peek` check it
        for free at their existing readback and raise."""
        return int(self._clob)

    def _check_clobbers(self):
        # piggybacks on a sync point the caller already paid for (the
        # pop/peek validity readback) — jnp scalar comparison is free then
        if int(self._clob) != 0:
            raise RuntimeError(
                f"admission pool slot collision: {int(self._clob)} buffered "
                "push(es) targeted a live pool slot and were dropped by the "
                "guarded fold. The incumbent item survived, but the pushed "
                "item is lost — the host-side slot accounting has desynced "
                "from the device pool (was the pool mutated directly?)")

    # ------------------------------------------------------------------ fold
    def _account_fold(self, force: bool, place: Optional[int] = None):
        for p in range(self.num_places):
            total = self._unpub[p] + self._staged[p]
            if force or self.k == 0 or p == place:
                self._unpub[p] = 0
            else:
                self._unpub[p] = total % self.k
            self._staged[p] = 0

    def fold(self):
        """Drain buffered pushes into the pool (stream-accurate publish-on-k);
        the engine calls this once per decode step, before admission pops.
        Folds run guarded (``fold(count_clobbers=True)``): a buffered entry
        landing on a live pool slot is masked out — the incumbent survives —
        and counted in the device-side ``self._clob`` scalar, surfaced as a
        loud ``RuntimeError`` at the next pop/peek readback."""
        if self.storage == "klsm":
            self.pool, self.buf, self.store, self._clob = self._fold_fn(
                self.pool, self.buf, self.store, self._clob)
        else:
            self.pool, self.buf, self._clob = self._fold_fn(
                self.pool, self.buf, self._clob)
        self._account_fold(force=False)
        self._count()

    def flush(self, place: Optional[int] = None):
        """Publish staged + unpublished requests: every place's when
        ``place`` is None (the all-frontends ``HybridKQueue.flush`` loop as
        one device program), exactly one place's otherwise — the per-place
        ``HybridKQueue.flush(p)`` analogue. The per-place form drains the
        whole buffer into the pool (partially-drained buffers can't be left
        behind mid-stream) but only the flushed place publishes
        unconditionally; the rest keep stream-accurate publish-on-k, which
        is position- not fold-timing-dependent, so the host-oracle visible
        set is matched exactly (DESIGN.md §9.1/§10)."""
        if place is not None:
            mask = jnp.zeros((self.num_places,), bool).at[place].set(True)
            if self.storage == "klsm":
                (self.pool, self.buf, self.store,
                 self._clob) = self._flush_place_fn(
                    self.pool, self.buf, mask, self.store, self._clob)
            else:
                self.pool, self.buf, self._clob = self._flush_place_fn(
                    self.pool, self.buf, mask, self._clob)
            self._account_fold(force=False, place=place)
        else:
            if self.storage == "klsm":
                self.pool, self.buf, self.store, self._clob = self._flush_fn(
                    self.pool, self.buf, self.store, self._clob)
            else:
                self.pool, self.buf, self._clob = self._flush_fn(
                    self.pool, self.buf, self._clob)
            self._account_fold(force=True)
        self._count()

    # ------------------------------------------------------------------- pop
    def pop(self, place: int) -> Optional[Tuple[float, Any]]:
        """Pop ``place``'s best visible request — one device call, host
        readback only for the winning (slot, valid) pair (the admitted
        request must be prefetched host-side anyway)."""
        got = self.pop_ex(place)
        return None if got is None else got[:2]

    def pop_ex(self, place: int) -> Optional[Tuple[float, Any, int]]:
        """:meth:`pop` that also reports the popped pool slot — the handle
        the preemption plane needs for :meth:`repush`/:meth:`release`. In
        ``retain`` mode the slot stays reserved until one of those is
        called; otherwise it frees immediately (today's behaviour). Under
        ``policy="multiqueue"`` the ``place`` argument is ignored — the pop
        samples c=2 places from the instance's attempt counter, which
        advances on EVERY attempt (misses included, like
        ``MultiQueue.pop``)."""
        if self.policy == "multiqueue":
            t = self._pops
            self._pops += 1
            self.pool, slot, prio, valid = self._pop_mq_fn(
                self.pool, jnp.uint32(t))
        elif self.storage == "klsm":
            self.pool, self.store, slot, prio, valid = self._pop_fn(
                self.pool, self.store, jnp.int32(place))
        else:
            self.pool, slot, prio, valid = self._pop_fn(
                self.pool, jnp.int32(place))
        self._count()
        self._check_clobbers()
        if not bool(valid):
            if self.policy == "multiqueue":
                self._pop_misses += 1
            return None
        s = int(slot)
        item = self._items.pop(s)
        if self.retain:
            self._running[s] = item
        return float(prio), item, s

    # ------------------------------------------------- preemption (retain)
    def peek(self, place: int) -> Optional[float]:
        """Priority of the item :meth:`pop` would return for ``place``,
        without popping — the ``HybridKQueue.peek`` mirror
        (:func:`repro.core.kpriority.stream_peek`; spy refs persist either
        way, so peek-then-pop agrees with the host oracle, DESIGN.md §11)."""
        if self.policy == "multiqueue":
            raise RuntimeError(
                "MULTIQUEUE has no peek: the sampled pop commits to the "
                "c=2 draw, so there is no stable front to preview")
        if self.storage == "klsm":
            self.store, _slot, prio, valid = self._peek_fn(
                self.pool, self.store, jnp.int32(place))
        else:
            self.pool, _slot, prio, valid = self._peek_fn(
                self.pool, jnp.int32(place))
        self._count()
        self._check_clobbers()
        return float(prio) if bool(valid) else None

    def repush(self, slot: int, place: int, priority: float):
        """Re-queue a *running* (retained) request: its reserved pool slot
        re-enters the pool through the ordinary push/publish path with a
        fresh seq — exactly ``HybridKQueue.push`` of a re-queued victim, so
        the (priority, uid) tie-break stays stable across re-insertion
        (DESIGN.md §11). Immediate (not buffered): callers re-queue between
        a fold and the next step's pushes, so buffers are drained and the
        push order matches the host queue's call order."""
        if self.policy == "multiqueue":
            raise RuntimeError("repush is part of the preemption plane, "
                               "which is HYBRID-only (no MQ peek)")
        if sum(self._staged) != 0:
            raise RuntimeError(
                "repush with undrained buffers would reorder publish-on-k "
                "vs the host oracle; fold() first")
        item = self._running.pop(slot)
        self._items[slot] = item
        if self.storage == "klsm":
            self.pool, self.store = self._repush_fn(
                self.pool, self.store, jnp.int32(slot), jnp.int32(place),
                float(priority))
        else:
            self.pool = self._repush_fn(
                self.pool, jnp.int32(slot), jnp.int32(place), float(priority))
        self._arrival += 1
        u = self._unpub[place] + 1
        self._unpub[place] = 0 if (self.k == 0 or u >= self.k) else u
        self._count()

    def release(self, slot: int):
        """Free a retained pool slot (the running request completed)."""
        del self._running[slot]

    # --------------------------------------------------------------- queries
    def __len__(self) -> int:
        return len(self._items)

    def pending(self, place: int) -> int:
        """Unpublished + still-buffered pushes of ``place`` (the host queue's
        ``len(local)`` analogue, mirrored host-side — no device readback)."""
        return self._staged[place] + self._unpub[place]


# ---------------------------------------------------------------------------
# selftest (subprocess: run under XLA_FLAGS=--xla_force_host_platform_device_count=8)
# ---------------------------------------------------------------------------

def _selftest_trace_equivalence(mesh=None):  # pragma: no cover
    """StreamingAdmitter == HybridKQueue(spy="min_index") pop-for-pop on a
    randomized push/fold/pop trace (priorities drawn from a small grid to
    exercise the (priority, uid) tie-break)."""
    import numpy as np

    from repro.core.host_queue import HybridKQueue

    places, k = 4, 3
    rng = np.random.default_rng(7)
    dev = StreamingAdmitter(places, k, capacity=128, buffer_cap=32, mesh=mesh)
    host = HybridKQueue(places, k, spy="min_index")
    uid = 0
    for _ in range(60):
        for _ in range(int(rng.integers(0, 6))):
            p = int(rng.integers(places))
            pr = float(rng.integers(0, 8)) / 4.0
            dev.push(p, pr, uid)
            host.push(p, pr, uid)
            uid += 1
        dev.fold()
        if rng.random() < 0.15:
            dev.flush()
            for p in range(places):
                host.flush(p)
        for _ in range(int(rng.integers(0, 5))):
            p = int(rng.integers(places))
            a, b = dev.pop(p), host.pop(p)
            assert (a is None) == (b is None), (a, b)
            if a is not None:
                assert a[0] == b[0] and a[1] == b[1], (a, b)
    dev.flush()
    for p in range(places):
        host.flush(p)
    p = 0
    while True:
        a, b = dev.pop(p % places), host.pop(p % places)
        p += 1
        assert (a is None) == (b is None), (a, b)
        if a is None:
            if len(dev) == 0 and len(host) == 0:
                break
            continue
        assert a[0] == b[0] and a[1] == b[1], (a, b)
    tag = "mesh" if mesh is not None else "local"
    print(f"STREAM_TRACE_OK {tag} uid={uid}")


def _selftest_engine_equivalence():  # pragma: no cover
    """ServeEngine(admission="device", mesh=composed) admits in exactly the
    host-oracle order (the ISSUE 3 acceptance criterion, under the 8-device
    batch × data × model mesh)."""
    import numpy as np

    from repro.configs import get_reduced
    from repro.launch.mesh import make_test_production_batch_mesh
    from repro.models import materialize, model_p
    from repro.serve.config import ServeConfig
    from repro.serve.engine import Request, ServeEngine

    cfg = get_reduced("qwen3_1_7b")
    params = materialize(jax.random.PRNGKey(0), model_p(cfg))
    mesh = make_test_production_batch_mesh()
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, cfg.vocab_size, 6).astype(np.int32)
               for _ in range(8)]
    prios = [float(v) for v in rng.permutation(len(prompts))]

    def run(admission, mesh_):
        eng = ServeEngine(cfg, params, slots=4, max_len=32, frontends=2, k=2,
                          config=ServeConfig(admission=admission, mesh=mesh_))
        for i, toks in enumerate(prompts):
            eng.submit(Request(rid=i, tokens=toks, max_new=4,
                               priority=prios[i]), frontend=i % 2)
        eng.run()
        return eng.admission_log

    ref = run("host", None)
    dev = run("device", mesh)
    assert ref == dev, (ref, dev)
    print(f"STREAM_ENGINE_OK order={ref}")


def selftest() -> None:  # pragma: no cover - exercised via subprocess
    from repro.launch.mesh import make_test_production_batch_mesh

    d = len(jax.devices())
    _selftest_trace_equivalence()
    if d >= 8:
        mesh = make_test_production_batch_mesh()
        _selftest_trace_equivalence(mesh=mesh)
        _selftest_engine_equivalence()
    print(f"STREAM_OK devices={d}")


if __name__ == "__main__":
    import sys

    if "--selftest" in sys.argv:
        selftest()
