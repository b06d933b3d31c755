"""Serving: continuous batching with hybrid k-priority admission.

The paper's structure is the admission control plane: every front-end host is
a *place* pushing requests into a HybridKQueue (priority = user-supplied,
e.g. deadline or shortest-job-first); a request becomes globally visible
after its front-end has admitted k requests (or on flush), and slot
assembly pops the best visible requests — so a request is never overtaken by
more than ρ = places·k later arrivals (tested), while front-ends stay
uncoordinated between publishes. This is the paper's scalability/ordering
trade applied to continuous batching.

The engine itself is vLLM-style: a fixed decode batch of slots; prefill runs
per-admission (batch 1) and its cache is spliced into the slot; decode steps
the whole active batch.

``admission=`` selects the control plane (DESIGN.md §9): ``"host"`` keeps the
Python ``HybridKQueue`` (the equivalence oracle), ``"device"`` streams pushes
into per-place device buffers and folds them into a device-resident pool
between decode steps (serve/streaming.py) — same admission order bit-for-bit,
no host queue on the hot path.

``step=`` selects how far the step itself is fused (DESIGN.md §10):
``"host"``/``"device"`` are the eager per-step oracles (aliases for the
matching ``admission=``), ``"fused"`` runs admission + pop + splice + decode
as ONE lax.scan-chunked dispatch per ``step_chunk`` steps
(serve/fused_step.py) — same admission order and token streams, one device
program on the entire hot path. ``"continuous"`` (DESIGN.md §12) is the
fused plane plus double-buffered arrival plans: an async host packer drains
``submit`` into ready plans while the device runs the current chunk, and
each chunk boundary folds whatever the host has published — submissions
batch into ~2 device programs per PLAN instead of 2 per request, and a
submission landing a chunk later only spends relaxation budget inside
ρ = P·k.
"""
from __future__ import annotations

import dataclasses
import functools
import threading
import time
import warnings
import weakref
from collections import deque
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core.host_queue import HybridKQueue, MultiQueue
from repro.models import decode_step, init_cache, prefill
from repro.obs import span
from repro.serve.config import LEGACY_KWARGS, ServeConfig


@functools.lru_cache(maxsize=None)
def _fused_model_fns(cfg: ModelConfig, max_len: int):
    """Model fns for the fused loop with (cfg, max_len)-stable identity:
    ``fused_step.build_chunk_fn`` caches compiled chunk programs keyed on the
    decode fn, so engines (and serving restarts) with an equal config share
    one compile instead of each pinning a fresh per-instance lambda's
    programs forever (ModelConfig is a frozen dataclass — hashable by
    value)."""

    def decode_fn(p, c, t, q):
        return decode_step(p, cfg, c, t, q)

    def prefill_fn(p, t):
        return prefill(p, cfg, {"tokens": t}, max_len)

    return decode_fn, prefill_fn


class _PlanPacker:
    """Async host-side packer (DESIGN.md §12): a daemon thread drains
    ``ServeEngine.submit`` calls into ready arrival plans — pool-slot
    reservation + prefill via ``FusedServeLoop.submit_planned``, then a
    publish into the open :class:`~repro.serve.streaming.PlanSlot` — ahead
    of the device. When the open plan's row is full the publish blocks until
    the consumer seals (``PlanBook.publish_wait``): the packer-behind
    backpressure path, where the entry spills into the NEXT plan instead of
    being dropped. Exceptions are captured and re-raised on the engine
    thread at the next ``submit``/``drain``."""

    def __init__(self, loop, book, max_backlog: int = 4096):
        self._loop, self._book = loop, book
        self._max_backlog = max_backlog
        self._inbox = deque()
        self._cv = threading.Condition()
        self._busy = 0                 # entries popped but not yet published
        self._stop = False
        self._error: Optional[BaseException] = None
        self._thread = threading.Thread(
            target=self._run, name="plan-packer", daemon=True)
        self._thread.start()

    def submit(self, frontend: int, qprio: float, req):
        with self._cv:
            if self._error is not None:
                raise RuntimeError("plan packer died") from self._error
            while len(self._inbox) >= self._max_backlog:
                self._cv.wait(timeout=1.0)     # submit-side backpressure
            self._inbox.append((frontend, qprio, req))
            self._cv.notify_all()

    def _run(self):
        while True:
            with self._cv:
                while not self._inbox and not self._stop:
                    self._cv.wait()
                if not self._inbox and self._stop:
                    return
                frontend, qprio, req = self._inbox.popleft()
                self._busy += 1
                self._cv.notify_all()
            try:
                with span("serve.pack", rid=req.rid):
                    with span("serve.prefill"):
                        pool_slot, uid = self._loop.submit_planned(
                            frontend, qprio, req, req.tokens, req.max_new,
                            deadline=getattr(req, "deadline", None))
                    # place_of == frontend under HYBRID; under MULTIQUEUE it
                    # is the hashed home place the fold routes by (§14.2/§16)
                    with span("serve.publish_wait"):
                        self._book.publish_wait(
                            self._loop.place_of(pool_slot), pool_slot, qprio,
                            uid)
            except BaseException as e:  # noqa: BLE001 - relayed to engine
                with self._cv:
                    self._error = e
            finally:
                with self._cv:
                    self._busy -= 1
                    self._cv.notify_all()

    def backlog(self) -> int:
        """Submissions not yet published into a plan (queued + in flight)."""
        with self._cv:
            return len(self._inbox) + self._busy

    def wait_progress(self, timeout: float = 0.01):
        """Block briefly until the packer makes progress (or timeout)."""
        with self._cv:
            if self._error is not None:
                raise RuntimeError("plan packer died") from self._error
            if self._inbox or self._busy:
                self._cv.wait(timeout=timeout)

    def check(self):
        with self._cv:
            if self._error is not None:
                raise RuntimeError("plan packer died") from self._error

    def stop(self):
        """Stop the thread and wait for it, so that no packer is still
        unwinding when the interpreter shuts down (a daemon thread caught
        mid-exit there aborts the process)."""
        with self._cv:
            self._stop = True
            self._cv.notify_all()
        if threading.current_thread() is not self._thread:
            self._thread.join(timeout=60.0)


@dataclasses.dataclass
class Request:
    rid: int
    tokens: np.ndarray           # prompt [S]
    max_new: int
    priority: float              # smaller = more urgent
    out: List[int] = dataclasses.field(default_factory=list)
    admitted_at: int = -1
    frontend: int = -1           # submitting place (set by ServeEngine.submit)
    preemptions: int = 0         # times evicted from a decode slot (§11)
    slo_steps: Optional[int] = None  # relative deadline in engine steps (§13)
    deadline: Optional[int] = None   # absolute deadline step (set at submit)


class ServeEngine:
    """Continuous-batching serving engine with ρ-bounded priority admission.

    Admission is the paper's HYBRID structure (DESIGN.md §2): a request is
    overtaken by at most ρ = ``frontends``·``k`` later arrivals, while
    front-ends stay uncoordinated between publishes. ``admission="host"``
    (default) uses the sequential ``HybridKQueue`` oracle;
    ``admission="device"`` uses the device-resident ``StreamingAdmitter``
    (§9) — identical admission order, pinned by tests/test_streaming.py.
    Both use the deterministic min-index spy so the two planes are
    interchangeable mid-deployment.

    ``admission_policy="multiqueue"`` (DESIGN.md §14.2) swaps the admission
    structure for the sampled MultiQueue on EVERY step mode — pushes route
    to a (priority, uid)-hashed home place, pops sample c=2 places, no
    global top-k at all — with host (``host_queue.MultiQueue``), device
    (``StreamingAdmitter(policy="multiqueue")``) and the fused/continuous
    chunk programs (miss-tolerant ``stream_pop_fill_mq``, DESIGN.md §16)
    bit-identical (tests/test_multiqueue.py, tests/test_fused_step.py).
    Preemption keeps HYBRID admission (the sampled pop has no peek
    contract for the preemption rounds).

    ``admission_storage="klsm"`` (DESIGN.md §15) swaps the published-set
    INDEX — not the semantics — for the hierarchical k-LSM level store:
    pops probe ≤ P·L sorted-level heads instead of scanning the pool.
    Admission order is bit-identical to the flat storage on every plane
    (host = ``HostKLSM``, device = ``StreamingAdmitter(storage="klsm")``,
    fused/continuous = the level-synced chunk program;
    tests/test_klsm.py) — including under fused preemption, whose fire
    branch re-syncs the store after the in-trace re-push and pops the
    challenger through the level heads (DESIGN.md §16).

    ``mesh``: shard the decode-cache slot axis over the mesh's ``batch``
    axis (§8) — with a composed ``make_production_batch_mesh`` the admission
    pool co-locates with the decode slots it feeds.

    ``preemption="margin"`` (§11) arms priority-aware preemption of decode
    slots on EVERY plane: after each step's admission fill, while the
    queue's visible front beats the worst running slot by
    ``preempt_margin`` (f32 arithmetic, ``kpriority.preempt_beats``), the
    victim's decode cursor and KV cache are saved, the victim re-enters the
    admission plane with its original priority (a fresh uid — the ρ bound
    is untouched), and the challenger takes the seat; a later pop resumes
    the victim exactly where it stopped. All three planes stay
    bit-identical (tests/test_fused_step.py).
    """

    def __init__(
        self,
        cfg: ModelConfig,
        params,
        *,
        slots: int = 8,
        max_len: int = 512,
        frontends: int = 4,
        k: int = 4,
        config: Optional[ServeConfig] = None,
        **legacy,
    ):
        # ------------------------------------------------ config front door
        # All scheduling knobs live on ServeConfig (serve/config.py,
        # DESIGN.md §16) — validated there by ONE declarative rule table.
        # The legacy per-kwarg call form keeps working through this shim,
        # which builds the config and warns; model geometry (slots,
        # max_len, frontends, k) stays on the engine call.
        if legacy:
            unknown = sorted(set(legacy) - set(LEGACY_KWARGS))
            if unknown:
                raise TypeError(
                    "ServeEngine got unexpected keyword argument(s) "
                    f"{unknown}")
            if config is not None:
                raise TypeError(
                    "pass config=ServeConfig(...) OR the legacy per-field "
                    "kwargs, not both")
            warnings.warn(
                "ServeEngine(admission=..., step=..., preemption=..., ...) "
                "kwargs are deprecated; pass config=ServeConfig(...) "
                "(repro.serve.config) instead",
                DeprecationWarning, stacklevel=2)
            config = ServeConfig(**legacy)
        elif config is None:
            config = ServeConfig()
        # resolved(): step=None falls back to the admission plane,
        # step="host"/"device" forces admission to match; validation ran at
        # ServeConfig construction (invalid combinations are
        # unrepresentable — serve/config.py owns the rule table)
        config = config.resolved()
        self.config = config
        mesh = config.mesh
        admission = config.admission
        admission_policy = config.admission_policy
        admission_storage = config.admission_storage
        admission_capacity = config.admission_capacity
        step = config.step
        step_chunk = config.step_chunk
        preemption = config.preemption
        staging_rows = config.staging_rows
        packer = config.packer
        slo = config.slo

        self.cfg, self.params = cfg, params
        self.slots, self.max_len = slots, max_len
        self.preemption = preemption
        self.preempt_margin = float(config.preempt_margin)
        # §13 SLO policy (serve/slo.py): priority aging at the submit
        # boundary, slack-derived preemption margins, restage-cost victim
        # packing — identical f32 math on every plane
        self.slo = slo
        self.admission_policy = admission_policy
        self.admission_storage = admission_storage
        self.step_mode = step
        self.step_chunk = step_chunk
        self.admission = admission
        self._fused = None
        self._book = None
        self._packer = None
        self._packer_mode = packer
        self._dispatches = 0
        if step in ("fused", "continuous"):
            self.queue = None        # installed after caches exist, below
        elif admission == "host":
            if admission_policy == "multiqueue":
                self.queue = MultiQueue(frontends, k)
            elif admission_storage == "klsm":
                # the host-side klsm twin (DESIGN.md §15): bit-identical to
                # HybridKQueue(spy="min_index") by construction, so the
                # host plane stays the equivalence oracle under either
                # storage
                from repro.core.host_queue import HostKLSM

                self.queue = HostKLSM(frontends, k)
            else:
                # min-index spy: pins the same victim choice as the device
                # plane so "host" stays the bit-exact equivalence oracle
                # (DESIGN.md §9)
                self.queue = HybridKQueue(frontends, k, spy="min_index")
        elif admission == "device":
            from repro.serve.streaming import StreamingAdmitter

            self.queue = StreamingAdmitter(
                frontends, k, capacity=admission_capacity, mesh=mesh,
                retain=preemption == "margin", policy=admission_policy,
                storage=admission_storage)
        else:
            raise ValueError(f"unknown admission plane: {admission!r}")
        self.frontends = frontends
        self.caches = init_cache(cfg, slots, max_len)
        self.mesh = mesh
        if mesh is not None:
            # decode data-parallelism: shard the slot axis (dim 1 of every
            # cache leaf) over the mesh's batch axis so each device decodes
            # slots/D sequences per step; admission stays host-side (the
            # hybrid k-priority queue is the uncoordinated control plane).
            # One shared rule with the fused carry/staging placement
            # (sharded_batch.slot_dim_sharding) so eager and fused decode
            # slots land identically on any mesh.
            from repro.core.sharded_batch import slot_dim_sharding

            spec = slot_dim_sharding(mesh)
            self.caches = jax.tree.map(
                lambda x: jax.device_put(x, spec(x)), self.caches)
        self.cur_tok = np.zeros((slots,), np.int32)
        self.pos = np.zeros((slots,), np.int32)
        self.active: List[Optional[Request]] = [None] * slots
        self.clock = 0
        self.admission_log: List[int] = []
        self.preempt_log: List[int] = []       # rids, eviction order (§11)
        self._push_seq = 0                     # queue uid mirror (§11)
        self._stash = {}                       # rid -> saved decode cursor
        self._filled: set = set()              # slots admitted this step

        self._decode = jax.jit(
            lambda p, c, t, q: decode_step(p, cfg, c, t, q)
        )
        self._prefill = jax.jit(
            lambda p, t: prefill(p, cfg, {"tokens": t}, max_len)
        )
        if step in ("fused", "continuous"):
            from repro.serve.fused_step import FusedServeLoop
            from repro.serve.streaming import PlanBook

            decode_fn, prefill_fn = _fused_model_fns(cfg, max_len)
            self._fused = FusedServeLoop(
                slots=slots, frontends=frontends, k=k, max_len=max_len,
                capacity=admission_capacity, params=params,
                caches=self.caches, decode_fn=decode_fn,
                prefill_fn=prefill_fn, mesh=mesh,
                preemption=preemption, margin=self.preempt_margin,
                staging_rows=staging_rows, continuous=step == "continuous",
                slo=slo, storage=admission_storage,
                policy=admission_policy,
            )
            self.queue = self._fused       # queue-like: __len__/flush/pending
            # cache ownership moves into the fused carry (donated each
            # chunk); the ``caches`` property reads the live carry so the
            # engine never exposes donated-and-deleted buffers
            self._caches = None
            if step == "continuous":
                self._book = PlanBook(frontends, self._fused.buffer_cap)
                if packer == "thread":
                    self._packer = _PlanPacker(self._fused, self._book)
                    # stop the packer thread when the engine is dropped —
                    # otherwise its loop/book references pin the fused
                    # carry's device buffers past engine deletion
                    weakref.finalize(self, _PlanPacker.stop, self._packer)

    # ------------------------------------------------------------- caches
    @property
    def caches(self):
        """Decode caches, valid in every step mode: eager modes own them
        directly; ``step="fused"`` hands ownership to the fused scan carry
        (whose buffers are donated per chunk), so the property reads the
        LIVE carry instead of aliasing deleted arrays (DESIGN.md §10)."""
        if self._fused is not None:
            return self._fused.carry.caches
        return self._caches

    @caches.setter
    def caches(self, value):
        self._caches = value

    # ------------------------------------------------------------ submission
    def submit(self, req: Request, frontend: int):
        """Front-end push (lower priority = admitted first). Host plane:
        appends to the Python queue; device plane: one async device-buffer
        scatter — no host queue state on the submission path (§9).

        Priorities are quantized to float32 on BOTH planes: the device pool
        stores f32, so comparing full-precision host floats against it would
        let f64-distinct/f32-equal priorities order differently — quantizing
        at the boundary keeps the two planes bit-identical for arbitrary
        float inputs (e.g. epoch-seconds deadlines).

        Under ``slo=`` (§13) the boundary also applies priority aging — the
        queue key becomes ``kpriority.aged_key(qprio, clock, aging_rate)``,
        computed HERE on the engine thread (not in the async packer) so the
        key never depends on packer timing — and stamps the absolute
        ``req.deadline`` from ``req.slo_steps`` / ``slo.default_slack``."""
        qprio = float(np.float32(req.priority))
        if self.slo is not None:
            qprio = self.slo.age(qprio, self.clock)
            req.deadline = self.slo.deadline_for(req.slo_steps, self.clock)
        req.frontend = frontend
        req._qprio = qprio
        if self.step_mode == "continuous":
            if self._packer is not None:
                self._packer.submit(frontend, qprio, req)
            else:                              # packer="sync": pack inline
                pool_slot, uid = self._fused.submit_planned(
                    frontend, qprio, req, req.tokens, req.max_new,
                    deadline=req.deadline)
                if not self._book.publish(
                        self._fused.place_of(pool_slot), pool_slot, qprio,
                        uid):
                    raise RuntimeError(
                        "arrival plan full (buffer_cap rows per frontend "
                        "and no async packer to backpressure); run a chunk "
                        "or raise buffer_cap")
        elif self._fused is not None:
            self._fused.submit(frontend, qprio, req, req.tokens, req.max_new,
                               deadline=req.deadline)
        else:
            self._push_seq += 1
            req._uid = self._push_seq
            self.queue.push(frontend, qprio, req)

    def wait_packed(self, timeout: float = 60.0):
        """Block until the async packer has published every submission so
        far into the open arrival plan, so the next step folds all of them —
        the submit-then-step hand-off of the eager planes, which a replay
        against the host oracle needs. A no-op unless ``step="continuous"``
        with ``packer="thread"``."""
        if self._packer is None:
            return
        deadline = time.monotonic() + timeout
        while self._packer.backlog():
            if time.monotonic() > deadline:
                raise TimeoutError("plan packer failed to drain")
            self._packer.wait_progress()
        self._packer.check()

    def _drain_plans(self, timeout: float = 60.0):
        """Drain the continuous submission path onto the exact flush path:
        seal plans (unblocking any backpressured publish) and adopt their
        entries as ordinary next-step arrivals until the packer and both
        plan slots are empty."""
        deadline = time.monotonic() + timeout
        while True:
            sealed = self._book.seal()
            if sealed.total():
                self._fused.adopt_plan(sealed)
            busy = (self._packer.backlog() if self._packer is not None
                    else 0)
            if busy == 0 and self._book.pending() == 0:
                return
            if time.monotonic() > deadline:
                raise TimeoutError("plan packer failed to drain")
            if self._packer is not None:
                self._packer.wait_progress()

    def flush_frontends(self):
        """Make every front-end's unpublished requests globally visible
        (shutdown / straggler handoff; the ρ bound only ever tightens)."""
        if self.step_mode == "continuous":
            self._drain_plans()
            self.queue.flush()
        elif self._fused is not None or self.admission == "device":
            self.queue.flush()
        else:
            for p in range(self.frontends):
                self.queue.flush(p)

    # ----------------------------------------------------------------- admit
    def _splice_cache(self, slot: int, new_cache):
        def splice(full, one):
            return full.at[:, slot].set(one[:, 0].astype(full.dtype))
        self.caches = jax.tree.map(splice, self.caches, new_cache)

    def _pop_from(self, place: int):
        """Pop the admission plane for ``place``; the preemptive device
        plane tracks the retained pool slot on the request (the handle
        ``StreamingAdmitter.repush``/``release`` need, §11)."""
        if self.preemption == "margin" and self.admission == "device":
            got = self.queue.pop_ex(place)
            if got is None:
                return None
            prio, req, pool_slot = got
            req._pool_slot = pool_slot
            return prio, req
        return self.queue.pop(place)

    def _seat(self, slot: int, req: Request):
        """Admit ``req`` into decode slot ``slot`` — fresh (prefill, first
        token emitted) or resumed (cursor + KV restored from the preemption
        stash, nothing re-emitted; §11)."""
        req.admitted_at = self.clock
        self.admission_log.append(req.rid)
        self._filled.add(slot)
        self.active[slot] = req
        saved = self._stash.pop(req.rid, None)
        if saved is not None:
            tok, pos, col = saved
            self.cur_tok[slot] = tok
            self.pos[slot] = pos
            self._splice_cache(slot, col)
            return
        prompt = jnp.asarray(req.tokens[None, :], jnp.int32)
        logits, cache = self._prefill(self.params, prompt)
        self._dispatches += 1
        self._splice_cache(slot, cache)
        self.cur_tok[slot] = int(jnp.argmax(logits[0]))
        self.pos[slot] = len(req.tokens)
        req.out.append(int(self.cur_tok[slot]))

    def _admit(self):
        """Fill empty decode slots from the admission plane. The device plane
        folds its buffers first (one fused device program per step) so pops
        see every request submitted before this step — the same visible set
        the host oracle has at this point (§9 equivalence contract).

        HYBRID keeps the stop-at-first-miss contract (an empty visible
        front really is empty). MULTIQUEUE is miss-tolerant (DESIGN.md §16):
        a sampled miss says nothing about global emptiness, so each empty
        slot retries up to ``MQ_POP_RETRIES`` extra attempts and then moves
        ON to the next slot instead of stopping — every attempt, hit or
        miss, advances the shared pop counter, which is exactly the retry
        loop the fused ``stream_pop_fill_mq`` runs in-trace, keeping all
        planes' counter streams aligned attempt-for-attempt."""
        from repro.core.kpriority import MQ_POP_RETRIES

        if self.admission == "device":
            self.queue.fold()
        miss_tolerant = self.admission_policy == "multiqueue"
        for slot in range(self.slots):
            if self.active[slot] is not None:
                continue
            got = self._pop_from(slot % self.frontends)
            if miss_tolerant:
                for _ in range(MQ_POP_RETRIES):
                    if got is not None:
                        break
                    got = self._pop_from(slot % self.frontends)
                if got is None:
                    continue
            elif got is None:
                return
            self._seat(slot, got[1])

    def _victim_slack(self, req: Request) -> float:
        """Slack (steps) of a running request at the preempt point (§13):
        ``deadline − clock − remaining budget``; +inf when best-effort.
        Matches the fused in-trace ``slot_deadline − (clock + budget −
        out_len)`` — integer math, so the single f32 cast in
        ``slack_margin`` is exact on both planes."""
        if req.deadline is None:
            return float("inf")
        return req.deadline - self.clock - (req.max_new - len(req.out))

    def _preempt(self):
        """§11 preemption rounds, after the admission fill: while the
        queue's visible front beats the worst running slot — lexicographic
        max of (priority, uid), the dual of the pop order — by
        ``preempt_margin`` (f32 arithmetic via ``kpriority.preempt_beats``),
        evict that slot (decode cursor + KV cache column stashed
        host-side), re-queue the victim with its original priority and a
        fresh uid, and pop the challenger into the seat. Slots admitted
        this step are protected (one admission per slot per step), so the
        loop is bounded by ``slots`` rounds — the exact host mirror of the
        fused in-trace preempt phase (`kpriority.preempt_plan`).

        Under ``slo=`` (§13) two refinements, mirrored bit-for-bit by the
        fused plane: ``victim="cheapest"`` breaks equal-priority victim
        ties toward the smallest decode position (max of (priority, −pos,
        uid) — pos IS the restage copy cost), and ``margin_scale > 0``
        replaces the static margin with the victim's slack-derived one."""
        from repro.core.kpriority import preempt_beats

        slo = self.slo
        cheapest = slo is not None and slo.victim == "cheapest"
        for _ in range(self.slots):
            elig = [s for s in range(self.slots)
                    if self.active[s] is not None and s not in self._filled]
            if not elig:
                return
            if cheapest:
                v = max(elig, key=lambda s: (self.active[s]._qprio,
                                             -int(self.pos[s]),
                                             self.active[s]._uid))
            else:
                v = max(elig, key=lambda s: (self.active[s]._qprio,
                                             self.active[s]._uid))
            margin = self.preempt_margin
            if slo is not None and slo.slack_margins:
                margin = slo.margin_for(self._victim_slack(self.active[v]))
            place = v % self.frontends
            top = self.queue.peek(place)
            if top is None or not preempt_beats(
                    top, margin, self.active[v]._qprio):
                return
            victim = self.active[v]
            col = jax.tree.map(lambda full: full[:, v:v + 1], self.caches)
            self._stash[victim.rid] = (
                int(self.cur_tok[v]), int(self.pos[v]), col)
            self.active[v] = None
            victim.preemptions += 1
            self.preempt_log.append(victim.rid)
            self._push_seq += 1
            victim._uid = self._push_seq
            if self.admission == "device":
                self.queue.repush(victim._pool_slot, victim.frontend,
                                  victim._qprio)
            else:
                self.queue.push(victim.frontend, victim._qprio, victim)
            got = self._pop_from(place)
            assert got is not None, "peeked front vanished before pop"
            self._seat(v, got[1])

    def _consume(self, records) -> List[Request]:
        """Replay fused StepRecords into the engine's host bookkeeping —
        same event order as the eager step (admissions and preemption
        rounds, then decode tokens, then completions), so admission_log,
        preempt_log, and Request.out are identical across step modes
        (DESIGN.md §10/§11)."""
        done: List[Request] = []
        for rec in records:
            self.clock += 1
            for slot, req, _pool_slot in rec.preempted:
                req.preemptions += 1
                self.preempt_log.append(req.rid)
                self.active[slot] = None
            for slot, req, tok0, _ps in rec.order:
                req.admitted_at = self.clock
                self.admission_log.append(req.rid)
                if tok0 is not None:            # fresh admission: first token
                    req.out.append(tok0)
                self.active[slot] = req
            for _slot, req, tok in rec.tokens:
                req.out.append(tok)
            for slot, req in rec.finished:
                done.append(req)
                self.active[slot] = None
        return done

    # ------------------------------------------------------------------ step
    def _publish_boundary(self):
        """Chunk-boundary handoff (§12): seal whatever the packer has
        published so far and upload it for the next chunk's fold."""
        if self._packer is not None:
            self._packer.check()
        self._fused.publish_plan(self._book.seal())

    def step(self) -> List[Request]:
        """Admit (+ preempt) + one decode step for all active slots; returns
        finished. Traced as the span ``serve.step`` (``repro.obs``)."""
        with span("serve.step"):
            return self._step()

    def _step(self) -> List[Request]:
        if self._fused is not None:
            if self.step_mode == "continuous":
                with span("serve.plan"):
                    self._publish_boundary()
            records = self._fused.run_steps(1)
            with span("serve.consume"):
                return self._consume(records)
        self.clock += 1
        self._filled = set()
        self._admit()
        if self.preemption == "margin":
            self._preempt()
        if not any(r is not None for r in self.active):
            return []
        logits, self.caches = self._decode(
            self.params, self.caches,
            jnp.asarray(self.cur_tok), jnp.asarray(self.pos),
        )
        self._dispatches += 1
        nxt = np.asarray(jnp.argmax(logits, axis=-1), np.int32)
        done: List[Request] = []
        for slot, req in enumerate(self.active):
            if req is None:
                continue
            self.pos[slot] += 1
            self.cur_tok[slot] = nxt[slot]
            req.out.append(int(nxt[slot]))
            if len(req.out) >= req.max_new or self.pos[slot] >= self.max_len - 1:
                done.append(req)
                self.active[slot] = None
                if self.preemption == "margin" and self.admission == "device":
                    self.queue.release(req._pool_slot)
        return done

    def run(self, max_steps: int = 10_000) -> List[Request]:
        """Step until every submitted request finishes (or ``max_steps``).
        Unflushed requests are still admitted — own-place visibility and
        spying reach them — just possibly later (the ρ trade, §2). The fused
        step mode advances ``step_chunk`` steps per dispatch; trailing no-op
        steps inside a final chunk are observationally inert (nothing is
        active, so no admissions and no tokens)."""
        finished: List[Request] = []
        steps = 0
        while steps < max_steps:
            if self.step_mode == "continuous":
                n = min(self.step_chunk, max_steps - steps)
                self._publish_boundary()
                finished.extend(self._consume(self._fused.run_steps(n)))
                steps += n
            elif self._fused is not None:
                n = min(self.step_chunk, max_steps - steps)
                finished.extend(self._consume(self._fused.run_steps(n)))
                steps += n
            else:
                finished.extend(self.step())
                steps += 1
            if (not any(self.active)) and len(self.queue) == 0:
                if self.step_mode != "continuous":
                    break
                # continuous: the packer may still be packing — wait for it
                # rather than dispatching empty chunks, and only stop once
                # both plan slots are empty too
                busy = (self._packer.backlog()
                        if self._packer is not None else 0)
                if busy == 0 and self._book.pending() == 0:
                    break
                if self._packer is not None:
                    self._packer.wait_progress()
        return finished

    # --------------------------------------------------------------- queries
    @property
    def dispatches(self) -> int:
        """Device programs launched so far, across decode/prefill and the
        admission plane — the metric ``benchmarks --only fused_step`` tracks
        (DESIGN.md §10 dispatch-count math)."""
        return self._dispatches + getattr(self.queue, "dispatches", 0)

    @property
    def splices(self) -> int:
        """Decode slots the fused step programs spliced a staged request
        into so far (``FusedServeLoop.splices``): one per admission from the
        queue's fill, so the ``splice_in`` scope's device time over it is
        the time of one splice. A preempt round's challenger is written
        into its slot in place too, but is not counted. 0 on the eager step
        modes, which splice on the host's side of the step."""
        return self._fused.splices if self._fused is not None else 0
