"""Single-dispatch fused decode step, with priority-aware slot preemption
(DESIGN.md §10, §11).

PR 3 made streaming admission device-resident, but the serving loop still
interleaved it with decode as SEPARATE host-driven dispatches per step —
fold, then one ``stream_pop`` per empty slot, then prefill splices, then
decode: the host round-trip (the centralization bottleneck the paper's
hybrid k-priority structure exists to avoid) reappeared at the dispatch
boundary. This module lifts the remaining host-side control flow into one
traced program: a :class:`FusedServeLoop` step is

  1. **fold** — the stream-accurate publish-on-k fold of this step's
     :class:`~repro.serve.streaming.AdmissionBuffer` arrival rows
     (arrival-scheduled per step, packed host-side before dispatch),
  2. **admit** — :func:`repro.core.kpriority.stream_pop_fill`: the engine's
     sequential fill of empty decode slots (stop at the first failed pop)
     as a ``lax.scan`` threading the :class:`PoolState` through its carry,
  3. **splice** — admitted slots take their resume state (next token,
     position, emitted count, token budget, KV cache) from a device-resident
     staging area, through a pool-slot → staging-row indirection; the KV
     cache moves by one in-place write per admitted slot
     (:func:`splice_in`),
  4. **preempt** (``preemption="margin"``, §11) — up to ``slots`` rounds of
     :func:`repro.core.kpriority.preempt_plan`: whenever the queue's visible
     front beats the worst running slot by ``margin``, the victim's decode
     cursor and KV cache are written back to its staging row, the victim
     re-enters the pool through the ordinary push/publish path with its
     original priority (a fresh seq — the ρ bound is untouched), and the
     challenger is popped into the freed slot,
  5. **decode + complete** — one decode step for the whole batch; slots
     whose budget (or context) is exhausted free themselves for the next
     step's admission.

``lax.scan`` chunks N such steps into ONE XLA dispatch (events come back
stacked ``[N, ...]``), so the dispatch count per step drops from
O(slots + admissions) to 1/N. The relaxed ρ = P·k ordering contract is what
makes the fusion legal (admission never needed a host-synchronized total
order — only publish-on-k visibility), and the fused path is pinned
bit-identical to the host ``HybridKQueue(spy="min_index")`` oracle and to
``ServeEngine(admission="device")`` on randomized traces — with and without
preemption (tests/test_fused_step.py; 8-device composed-mesh subprocess
selftest: ``python -m repro.serve.fused_step --selftest`` under
XLA_FLAGS=--xla_force_host_platform_device_count=8).
"""
from __future__ import annotations

import heapq
import threading
from typing import Any, Callable, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import kpriority as kp
from repro.obs import span
from repro.serve import streaming
from repro.serve.streaming import AdmissionBuffer, PlanSlot, fold


class Staging(NamedTuple):
    """Device-resident resume staging, one ROW per in-flight request: what a
    (re-)admitted request needs to start (or resume) decoding. Fresh
    submissions write their row at submit time (prefill runs at submission —
    deterministic in the prompt, so moving it off the admission step changes
    no output); preemption writes the victim's live cursor + KV back to the
    same row (DESIGN.md §10/§11).

    ``row`` is the pool-slot → staging-row indirection (the ROADMAP staging
    hop): cache staging is O(``staging_rows`` × per-slot cache) — bounded by
    concurrently in-flight requests, not by the admission pool's roomy
    ``capacity``."""

    tok: jnp.ndarray      # i32[R]  next input token (prefill argmax / cursor)
    pos: jnp.ndarray      # i32[R]  decode position to resume at
    out_len: jnp.ndarray  # i32[R]  tokens already emitted (1 for fresh)
    budget: jnp.ndarray   # i32[R]  max_new token budget
    deadline: jnp.ndarray  # f32[R] absolute deadline step; +inf best-effort
    row: jnp.ndarray      # i32[capacity]  pool slot -> staging row


class FusedCarry(NamedTuple):
    """The scan carry of the fused step program — everything the serving hot
    loop used to keep host-side, now device-resident (DESIGN.md §10):
    admission pool, decode caches, per-slot decode cursor, the running
    requests' (priority, uid, creator) — the preemption plane's victim keys
    (§11) — and the resume staging (in the carry because preemption mutates
    it in-trace)."""

    pool: kp.PoolState    # admission pool (M = capacity slots, P frontends)
    caches: Any           # decode caches; every leaf [lead, slots, ...]
    cur_tok: jnp.ndarray  # i32[S] next input token per decode slot
    pos: jnp.ndarray      # i32[S] decode position per slot
    slot_req: jnp.ndarray  # i32[S] pool slot of the active request; -1 empty
    out_len: jnp.ndarray  # i32[S] tokens emitted for the active request
    budget: jnp.ndarray   # i32[S] max_new of the active request
    slot_prio: jnp.ndarray     # f32[S] priority of the active request
    slot_uid: jnp.ndarray      # i32[S] pool seq of its latest push
    slot_creator: jnp.ndarray  # i32[S] its submitting frontend
    slot_deadline: jnp.ndarray  # f32[S] absolute deadline step; +inf none
    clock: jnp.ndarray    # i32[] engine step counter (device mirror, §13)
    staging: Staging      # resume staging + pool-slot indirection
    staged_caches: Any    # staged KV; every leaf [lead, staging_rows, ...]
    plan: AdmissionBuffer  # ping-pong arrival plans; leaves [2, P, C]/[2, P]
    plan_sel: jnp.ndarray  # i32[] plan slot the NEXT chunk folds (§12)
    mq_pops: jnp.ndarray   # u32[] MULTIQUEUE pop-attempt counter (§14.2/§16):
                           # the sampled pop's c=2 draw is a pure function of
                           # this counter, which advances on EVERY attempt —
                           # misses included — so it must persist across steps
                           # (and chunks) to match the eager planes' counters
    pop_aborts: jnp.ndarray  # i32[] aborted selects (sampled misses) so far —
                             # the §16 ignored-count accounting; stays 0 under
                             # policy="hybrid"
    store: Any = None      # klsm level store (§15); None under storage="flat"
                           # (an empty pytree subtree, so flat programs are
                           # byte-identical to the pre-klsm ones)


class StepEvents(NamedTuple):
    """Per-step device→host event record (stacked over a chunk) — the only
    readback of a fused chunk; the host reconstructs admission order, token
    streams, preemptions, and completions from it. ``pre_*`` leaves are
    ``[rounds]`` per step (``rounds`` = 0 with preemption off)."""

    admit: jnp.ndarray   # i32[S] pool slot admitted into decode slot s; -1
    token: jnp.ndarray   # i32[S] decode-step token (valid where ``active``)
    active: jnp.ndarray  # bool[S] slot held a request this step
    done: jnp.ndarray    # bool[S] request finished this step
    live: jnp.ndarray    # bool[] step did decode/preempt work (False = the
                         # masked no-op tail of a short chunk)
    pre_slot: jnp.ndarray  # i32[rounds] preempted decode slot; -1 no fire
    pre_vps: jnp.ndarray   # i32[rounds] victim's pool slot (re-pushed)
    pre_ps: jnp.ndarray    # i32[rounds] challenger's pool slot (admitted)


class StepRecord(NamedTuple):
    """Host-side view of one fused step, in engine event order. ``admitted``
    holds FRESH admissions only (their first token rides along);
    ``resumed``/``preempted`` are the §11 preemption events; ``order`` is
    the step's full admission sequence — phase-1 fills in slot order, then
    preemption rounds in round order — with ``tok0`` None on resumes."""

    admitted: List[Tuple[int, Any, int, int]]  # (decode_slot, item, tok0, pool_slot)
    tokens: List[Tuple[int, Any, int]]         # (decode_slot, item, token)
    finished: List[Tuple[int, Any]]            # (decode_slot, item)
    order: Any = ()                            # (slot, item, tok0|None, pool_slot)
    resumed: Any = ()                          # (decode_slot, item, pool_slot)
    preempted: Any = ()                        # (decode_slot, item, pool_slot)


def _new_record() -> StepRecord:
    return StepRecord([], [], [], [], [], [])


class _Arrival(NamedTuple):
    step: int       # absolute engine step at which this push becomes foldable
    place: int
    pool_slot: int
    prio: float     # f32-exact
    uid: int        # global arrival index


def splice_in(caches, staged_caches, rows, mask):
    """Write staged row ``rows[s]`` into decode-slot column ``s`` of every
    cache leaf, for each slot ``s`` where ``mask[s]`` (DESIGN.md §10.1).

    One in-place write per admitted slot: a loop over the admitted slots
    alone, so a step pays O(admitted × one slot's cache) and a step that
    admits nothing moves no cache bytes. Bit-identical to gathering every
    slot's row and selecting it over the decode caches by ``mask``: the same
    values, cast to the decode dtype the same way, and untouched columns
    unchanged. (A ``lax.cond`` per slot is no cheaper: XLA then gives the
    last cond's result the decode loop's layout, a copy of the whole
    cache.)"""
    admitted = jnp.flatnonzero(mask, size=mask.shape[0], fill_value=0)

    def write(i, caches):
        s = admitted[i]

        def one(full, stage):
            row = jax.lax.dynamic_slice_in_dim(stage, rows[s], 1, axis=1)
            return jax.lax.dynamic_update_slice_in_dim(
                full, row.astype(full.dtype), s, axis=1)

        return jax.tree.map(one, caches, staged_caches)

    return jax.lax.fori_loop(0, jnp.sum(mask, dtype=jnp.int32), write,
                             caches)


def build_chunk_fn(decode_fn: Callable, *, k: int, frontends: int,
                   slots: int, max_len: int, n: int,
                   preempt: bool = False, margin: float = 0.0,
                   rounds: int = 0, continuous: bool = False,
                   slo_margin: bool = False, margin_scale: float = 0.0,
                   margin_floor: float = 0.0, margin_cap: float = 0.0,
                   victim_cost: bool = False, storage: str = "flat",
                   policy: str = "hybrid"):
    """Build THE fused program: n steps of fold → ``stream_pop_fill`` →
    splice → [preempt ×``rounds``] → decode → complete as one jitted
    ``lax.scan`` over per-step AdmissionBuffer rows — one dispatch per chunk
    (DESIGN.md §10/§11). Signature:
    ``(params, carry, bufs[n]) -> (carry, events)`` with ``carry`` donated.

    ``policy="multiqueue"`` swaps the admit phase for the miss-tolerant
    sampled fill (:func:`repro.core.kpriority.stream_pop_fill_mq`,
    DESIGN.md §16): per empty slot, up to ``1 + MQ_POP_RETRIES``
    select→commit/abort attempts against the carry's pop-attempt counter,
    then CONTINUE to the next slot — a sampled miss says nothing about
    global emptiness, so stop-at-first-miss would under-admit vs the eager
    planes. Aborted selects accumulate in ``carry.pop_aborts``.

    The compiled program is shared across live loop instances with the same
    static config through :func:`streaming.shared_jit` — weakly, so
    dropping every loop frees the executable (callers keep the returned
    holder alive). Two refinements over the PR-4 program:

    * **dead-step masking** — a step with no occupied decode slot and no
      successful pop runs neither the preempt-round arbitration scan nor
      the decode step (one ``lax.cond``): a 1-step tail of an 8-step chunk
      pays 1 step of decode/arbitration, not 8. Fold + pops still run, so
      pool state (publish-on-k counters, spy refs) stays bit-identical to
      the unmasked program's.
    * **``continuous=True``** — before the scan, fold whatever the host has
      published into device plan slot ``carry.plan_sel``, clear it, and
      flip ``plan_sel``: the chunk-boundary half of the double-buffered
      arrival-plan protocol (§12). Plan entries behave exactly like
      arrivals scheduled at the chunk's first step.
    """
    key = ("chunk_fn", decode_fn, k, frontends, slots, max_len, n,
           preempt, margin, rounds, continuous,
           slo_margin, margin_scale, margin_floor, margin_cap, victim_cost,
           storage, policy)
    return streaming.shared_jit(
        key,
        lambda: _build_chunk_impl(
            decode_fn, k=k, frontends=frontends, slots=slots,
            max_len=max_len, n=n, preempt=preempt, margin=margin,
            rounds=rounds, continuous=continuous, slo_margin=slo_margin,
            margin_scale=margin_scale, margin_floor=margin_floor,
            margin_cap=margin_cap, victim_cost=victim_cost,
            storage=storage, policy=policy))


def _build_chunk_impl(decode_fn: Callable, *, k: int, frontends: int,
                      slots: int, max_len: int, n: int, preempt: bool,
                      margin: float, rounds: int, continuous: bool,
                      slo_margin: bool = False, margin_scale: float = 0.0,
                      margin_floor: float = 0.0, margin_cap: float = 0.0,
                      victim_cost: bool = False, storage: str = "flat",
                      policy: str = "hybrid"):
    places_vec = jnp.arange(slots, dtype=jnp.int32) % frontends
    n_rounds = rounds if (preempt and rounds > 0) else 0
    # storage="klsm" under the preempt rounds threads the level store
    # through the round scan: the peek probes the level fronts
    # (kp.preempt_plan_klsm), and the fire branch re-syncs the store right
    # after the victim's re-push — ≤ max(k, 1) newly published entries for
    # one place — before popping the challenger through the heads, exactly
    # the eager plane's peek → repush(+sync) → pop sequence (DESIGN.md §16).

    def preempt_round(st, _):
        # under storage="klsm" the level store rides the round carry as a
        # 16th element (appended, so the flat program stays byte-identical)
        if storage == "klsm":
            st, store = st[:-1], st[-1]
        else:
            store = None
        (pool, caches, staging, staged_caches, cur_tok, pos, out_len,
         budget, slot_req, slot_prio, slot_uid, slot_creator, slot_deadline,
         clock, protected) = st
        eligible = (slot_req >= 0) & ~protected
        if slo_margin:
            # per-slot deadline-derived margins (§13): slack in steps at
            # this round — deadline − clock − remaining budget — f32-exact
            # (ints ≤ 2^24), identical op order to the host mirror
            slack = slot_deadline - (clock + budget - out_len).astype(
                jnp.float32)
            margins = kp.slack_margin_traced(
                slack, scale=margin_scale, floor=margin_floor,
                cap=margin_cap)
        else:
            margins = None
        if storage == "klsm":
            # klsm peek mutates the STORE (spy-run acquisition), not the pool
            store, victim, fire = kp.preempt_plan_klsm(
                pool, store, slot_prio, slot_uid, eligible, places_vec,
                margin=margin, margins=margins,
                restage_cost=pos if victim_cost else None)
        else:
            pool, victim, fire = kp.preempt_plan(
                pool, slot_prio, slot_uid, eligible, places_vec,
                margin=margin, margins=margins,
                restage_cost=pos if victim_cost else None)

        def fire_branch(op):
            if storage == "klsm":
                op, store = op[:-1], op[-1]
            else:
                store = None
            (pool, caches, staging, staged_caches, cur_tok, pos, out_len,
             budget, slot_req, slot_prio, slot_uid, slot_creator,
             slot_deadline, clock, protected) = op
            m = pool.prio.shape[0]
            vps = slot_req[victim]
            vrow = staging.row[vps]
            # write the victim's resumable cursor + KV back to its row
            staging = staging._replace(
                tok=staging.tok.at[vrow].set(cur_tok[victim]),
                pos=staging.pos.at[vrow].set(pos[victim]),
                out_len=staging.out_len.at[vrow].set(out_len[victim]),
                budget=staging.budget.at[vrow].set(budget[victim]),
                deadline=staging.deadline.at[vrow].set(
                    slot_deadline[victim]),
            )
            staged_caches = jax.tree.map(
                lambda stg, full: stg.at[:, vrow].set(
                    full[:, victim].astype(stg.dtype)),
                staged_caches, caches)
            # re-queue through the ordinary push/publish path: fresh seq,
            # original (priority, creator) — exactly HybridKQueue.push
            pool = kp.push(
                pool, jnp.arange(m) == vps,
                jnp.full((m,), slot_prio[victim]),
                jnp.full((m,), slot_creator[victim], jnp.int32),
                k=k, policy=kp.Policy.HYBRID)
            if storage == "klsm":
                # the re-push may publish (publish-on-k): re-sync the level
                # store — ≤ max(k, 1) newly published entries for one place
                # (k-1 carried + the re-push; k=0 publishes just the one) —
                # then pop the challenger through the level heads, exactly
                # the eager _jitted_klsm_repush → klsm_pop sequence
                store = kp.klsm_sync(pool, store, batch_cap=max(k, 1))
                pool, store, cps, cprio, _cvalid = kp.klsm_pop(
                    pool, store, places_vec[victim])
            else:
                # the challenger (strictly better than the victim, so the
                # pop can never return the just-re-pushed slot) takes the
                # seat
                pool, cps, cprio, _cvalid = kp.stream_pop(
                    pool, places_vec[victim])
            crow = staging.row[cps]
            cur_tok = cur_tok.at[victim].set(staging.tok[crow])
            pos = pos.at[victim].set(staging.pos[crow])
            out_len = out_len.at[victim].set(staging.out_len[crow])
            budget = budget.at[victim].set(staging.budget[crow])
            caches = jax.tree.map(
                lambda full, stg: full.at[:, victim].set(
                    stg[:, crow].astype(full.dtype)),
                caches, staged_caches)
            slot_req = slot_req.at[victim].set(cps)
            slot_prio = slot_prio.at[victim].set(cprio)
            slot_uid = slot_uid.at[victim].set(pool.seq[cps])
            slot_creator = slot_creator.at[victim].set(pool.creator[cps])
            slot_deadline = slot_deadline.at[victim].set(
                staging.deadline[crow])
            protected = protected.at[victim].set(True)
            new = (pool, caches, staging, staged_caches, cur_tok, pos,
                   out_len, budget, slot_req, slot_prio, slot_uid,
                   slot_creator, slot_deadline, clock, protected)
            if storage == "klsm":
                new = new + (store,)
            return new, (victim, vps, cps)

        def skip_branch(op):
            return op, (jnp.int32(-1), jnp.int32(-1), jnp.int32(-1))

        st2 = (pool, caches, staging, staged_caches, cur_tok, pos, out_len,
               budget, slot_req, slot_prio, slot_uid, slot_creator,
               slot_deadline, clock, protected)
        if storage == "klsm":
            st2 = st2 + (store,)
        return jax.lax.cond(fire, fire_branch, skip_branch, st2)

    def run(params, carry, bufs):
        def one_step(c, buf):
            # fold + pops always run (cheap, and they keep pool state —
            # publish-on-k counters, spy refs — bit-identical to the
            # unmasked program); only decode + preempt arbitration are
            # gated on the step having any work
            with jax.named_scope("fold"):
                pool, _ = fold(c.pool, buf, k=k)
            if storage == "klsm":
                # re-derive the level store from the freshly folded pool,
                # then pop through the level-front probe (§15): one fold
                # publishes ≤ per-step buffer width + K entries per place
                bc = buf.prio.shape[-1] + max(k, 1)
                with jax.named_scope("klsm_sync"):
                    store = kp.klsm_sync(pool, c.store, batch_cap=bc)
                with jax.named_scope("pop_fill"):
                    pool, store, res = kp.klsm_pop_fill(
                        pool, store, c.slot_req < 0, places_vec)
                mq_pops, pop_aborts = c.mq_pops, c.pop_aborts
            elif policy == "multiqueue":
                # miss-tolerant sampled fill (§16): attempts — hits AND
                # misses — advance the carried counter exactly like the
                # eager planes' per-attempt counters, dead steps included,
                # which is what keeps the c=2 draws (hence admission order)
                # bit-identical across all four planes
                store = c.store
                with jax.named_scope("pop_fill"):
                    pool, mq_pops, res, ab = kp.stream_pop_fill_mq(
                        pool, c.slot_req < 0, c.mq_pops)
                    pop_aborts = c.pop_aborts + ab
            else:
                store = c.store
                with jax.named_scope("pop_fill"):
                    pool, res = kp.stream_pop_fill(
                        pool, c.slot_req < 0, places_vec)
                mq_pops, pop_aborts = c.mq_pops, c.pop_aborts
            got = res.valid                              # bool[S]
            live = jnp.any(got) | jnp.any(c.slot_req >= 0)
            # the engine increments its clock at the top of EVERY step
            # (dead-masked ones included) — the §13 slack math reads it
            clock = c.clock + 1

            def live_step(c):
                with jax.named_scope("splice_in"):
                    ps = jnp.where(got, res.slot, 0)         # i32[S]
                    rows = c.staging.row[ps]                 # i32[S]
                    cur_tok = jnp.where(got, c.staging.tok[rows], c.cur_tok)
                    pos = jnp.where(got, c.staging.pos[rows], c.pos)
                    out_len = jnp.where(got, c.staging.out_len[rows],
                                        c.out_len)
                    budget = jnp.where(got, c.staging.budget[rows], c.budget)
                    slot_req = jnp.where(got, ps, c.slot_req)
                    slot_prio = jnp.where(got, res.prio, c.slot_prio)
                    slot_uid = jnp.where(got, pool.seq[ps], c.slot_uid)
                    slot_creator = jnp.where(got, pool.creator[ps],
                                             c.slot_creator)
                    slot_deadline = jnp.where(got, c.staging.deadline[rows],
                                              c.slot_deadline)
                    caches = splice_in(c.caches, c.staged_caches, rows, got)
                staging, staged_caches = c.staging, c.staged_caches

                store_out = store
                if n_rounds > 0:
                    st = (pool, caches, staging, staged_caches, cur_tok,
                          pos, out_len, budget, slot_req, slot_prio,
                          slot_uid, slot_creator, slot_deadline, clock, got)
                    if storage == "klsm":
                        st = st + (store,)
                    with jax.named_scope("preempt"):
                        st, (pre_slot, pre_vps, pre_ps) = jax.lax.scan(
                            preempt_round, st, None, length=n_rounds)
                    if storage == "klsm":
                        st, store_out = st[:-1], st[-1]
                    (pool_out, caches, staging, staged_caches, cur_tok,
                     pos, out_len, budget, slot_req, slot_prio, slot_uid,
                     slot_creator, slot_deadline, _clock, _protected) = st
                else:
                    pool_out = pool
                    empty = jnp.zeros((0,), jnp.int32)
                    pre_slot = pre_vps = pre_ps = empty

                with jax.named_scope("decode"):
                    logits, caches = decode_fn(params, caches, cur_tok, pos)
                    nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                active = slot_req >= 0
                pos = jnp.where(active, pos + 1, pos)
                cur_tok = jnp.where(active, nxt, cur_tok)
                out_len = jnp.where(active, out_len + 1, out_len)
                done = active & ((out_len >= budget) | (pos >= max_len - 1))
                slot_req = jnp.where(done, -1, slot_req)
                new_c = c._replace(
                    pool=pool_out, caches=caches, cur_tok=cur_tok, pos=pos,
                    slot_req=slot_req, out_len=out_len, budget=budget,
                    slot_prio=slot_prio, slot_uid=slot_uid,
                    slot_creator=slot_creator, slot_deadline=slot_deadline,
                    clock=clock, staging=staging,
                    staged_caches=staged_caches, mq_pops=mq_pops,
                    pop_aborts=pop_aborts, store=store_out)
                ev = StepEvents(admit=jnp.where(got, res.slot, -1),
                                token=nxt, active=active, done=done,
                                live=jnp.bool_(True),
                                pre_slot=pre_slot, pre_vps=pre_vps,
                                pre_ps=pre_ps)
                return new_c, ev

            def dead_step(c):
                rfill = jnp.full((n_rounds,), -1, jnp.int32)
                ev = StepEvents(
                    admit=jnp.full((slots,), -1, jnp.int32),
                    token=c.cur_tok,
                    active=jnp.zeros((slots,), bool),
                    done=jnp.zeros((slots,), bool),
                    live=jnp.bool_(False),
                    pre_slot=rfill, pre_vps=rfill, pre_ps=rfill)
                # the sampled-fill counters advance on dead steps too (the
                # eager planes attempt pops whenever slots are free)
                return c._replace(pool=pool, clock=clock, mq_pops=mq_pops,
                                  pop_aborts=pop_aborts, store=store), ev

            return jax.lax.cond(live, live_step, dead_step, c)

        if continuous:
            # chunk-boundary half of the double-buffered plan protocol
            # (DESIGN.md §12): fold whatever the host has published into
            # plan slot ``plan_sel`` — equivalent to those arrivals landing
            # at this chunk's first step — then clear it and flip, so the
            # host packs the next plan into the other slot while this
            # chunk runs
            with jax.named_scope("plan_fold"):
                sel = carry.plan_sel
                plan = carry.plan
                ready = AdmissionBuffer(
                    prio=plan.prio[sel], slot=plan.slot[sel],
                    arrival=plan.arrival[sel], count=plan.count[sel])
                pool, _ = fold(carry.pool, ready, k=k)
                if storage == "klsm":
                    # sync HERE, not at the scan's first step: the boundary
                    # fold can publish a full plan row (+ carried
                    # unpublished) per place, more than the per-step
                    # batch_cap budgets for
                    with jax.named_scope("klsm_sync"):
                        carry = carry._replace(store=kp.klsm_sync(
                            pool, carry.store,
                            batch_cap=ready.prio.shape[-1] + max(k, 1)))
                cleared = AdmissionBuffer(
                    prio=plan.prio.at[sel].set(jnp.inf),
                    slot=plan.slot.at[sel].set(-1),
                    arrival=plan.arrival.at[sel].set(0),
                    count=plan.count.at[sel].set(0))
            carry = carry._replace(pool=pool, plan=cleared,
                                   plan_sel=1 - sel)
        return jax.lax.scan(one_step, carry, bufs)

    return jax.jit(run, donate_argnums=(1,))


def _stage_update_impl(staging, staged_caches, ps, row, tok, pos, out_len,
                       budget, deadline, cache1):
    staging = Staging(
        tok=staging.tok.at[row].set(tok),
        pos=staging.pos.at[row].set(pos),
        out_len=staging.out_len.at[row].set(out_len),
        budget=staging.budget.at[row].set(budget),
        deadline=staging.deadline.at[row].set(deadline),
        row=staging.row.at[ps].set(row),
    )
    staged_caches = jax.tree.map(
        lambda full, one: full.at[:, row].set(one[:, 0].astype(full.dtype)),
        staged_caches, cache1,
    )
    return staging, staged_caches


_stage_update = jax.jit(_stage_update_impl, donate_argnums=(0, 1))


def _stage_batch_fn(r: int):
    """Batched staging: scatter ``r`` requests' resume state (cursors + the
    per-request prefill cache1s, concatenated in-program) in ONE device
    program — the continuous plane's replacement for ``r`` per-request
    ``_stage_update`` dispatches. ``r`` is bucketed (next power of two) and
    callers pad by repeating the last entry: duplicate-index scatters with
    identical values are deterministic, so padding is free."""

    def f(staging, staged_caches, ps, row, tok, pos, out_len, budget,
          deadline, *cache1s):
        staging = Staging(
            tok=staging.tok.at[row].set(tok),
            pos=staging.pos.at[row].set(pos),
            out_len=staging.out_len.at[row].set(out_len),
            budget=staging.budget.at[row].set(budget),
            deadline=staging.deadline.at[row].set(deadline),
            row=staging.row.at[ps].set(row),
        )
        batch = jax.tree.map(
            lambda *xs: jnp.concatenate(xs, axis=1), *cache1s)
        staged_caches = jax.tree.map(
            lambda full, b: full.at[:, row].set(b.astype(full.dtype)),
            staged_caches, batch)
        return staging, staged_caches

    return streaming.shared_jit(
        ("stage_batch", r), lambda: jax.jit(f, donate_argnums=(0, 1)))


def _plan_upload_impl(plan, sel, prio, slot, arrival, count):
    """Write one host-packed plan into device plan slot ``sel`` (the slot
    the next chunk folds) — one scatter per plan, however many requests it
    carries."""
    return AdmissionBuffer(
        prio=plan.prio.at[sel].set(prio),
        slot=plan.slot.at[sel].set(slot),
        arrival=plan.arrival.at[sel].set(arrival),
        count=plan.count.at[sel].set(count),
    )


_plan_upload = jax.jit(_plan_upload_impl, donate_argnums=(0,))


class FusedServeLoop:
    """Device-resident serving loop: admission + pop + splice + preempt +
    decode as one dispatch per chunk (DESIGN.md §10/§11).

    Queue-like on the submission side (``submit``/``flush``/``__len__``/
    ``pending`` mirror :class:`~repro.serve.streaming.StreamingAdmitter` —
    identical pool-slot allocation, so popped-slot sequences are comparable
    bit-for-bit) and engine-like on the decode side (``run_steps(n)``
    advances n steps in ⌈n/chunk⌉ dispatches and returns per-step
    :class:`StepRecord`\\ s).

    ``decode_fn(params, caches, tok, pos) -> (logits [S, V], caches)`` and
    ``prefill_fn(params, tokens [1, L]) -> (logits [1, V], cache1)`` supply
    the model; tests drive a toy pair, ``ServeEngine(step="fused")`` the
    real one — admission semantics are model-independent.

    ``preemption="margin"`` arms the in-trace preempt phase (§11): per step,
    up to ``slots`` rounds evict the worst running slot whenever the queue's
    visible front beats it by ``margin`` — the victim's cursor and KV are
    written back to its staging row and it re-enters the queue with its
    original priority; its pool slot and staging row stay reserved until it
    finishes, so ``capacity`` then bounds submitted-plus-running requests.
    With ``"off"`` (default) behaviour is exactly the PR-4 loop.

    ``staging_rows`` sizes the staged-KV area: one row per concurrently
    in-flight request (submitted-but-not-admitted, plus running when
    preemption is on) via the pool-slot → row indirection — O(staging_rows ×
    per-slot cache) device bytes instead of O(capacity × …). Defaults to
    ``capacity`` (never raises); size it to the real in-flight budget on
    memory-tight deployments.

    ``mesh``: place the carry on a composed serving mesh
    (``launch.mesh.make_production_batch_mesh``) via
    ``sharded_batch.fused_carry_shardings`` — pool and cache slot leaves
    shard over ``batch``, bookkeeping replicates; the fused program is an
    ordinary jit, so GSPMD supplies the collectives and semantics are
    unchanged on any mesh (the §9.4 placement argument).
    """

    #: aggregating ledger over per-instance dispatch counters (the
    #: StreamingAdmitter counterpart) — benchmarks/run.py snapshot-deltas
    #: :meth:`dispatch_total` per section; ``self.dispatches`` itself is
    #: instance-scoped.
    dispatch_ledger = streaming.DispatchLedger()

    def __init__(
        self,
        *,
        slots: int,
        frontends: int,
        k: int,
        max_len: int,
        capacity: int = 256,
        buffer_cap: int = 64,
        params: Any = None,
        caches: Any,
        decode_fn: Callable,
        prefill_fn: Callable,
        mesh=None,
        preemption: str = "off",
        margin: float = 0.0,
        staging_rows: Optional[int] = None,
        continuous: bool = False,
        slo=None,
        storage: str = "flat",
        policy: str = "hybrid",
    ):
        if preemption not in ("off", "margin"):
            raise ValueError(f"unknown preemption mode: {preemption!r}")
        if margin < 0:
            raise ValueError("preemption margin must be >= 0")
        if storage not in ("flat", "klsm"):
            raise ValueError(f"unknown admission storage: {storage!r}")
        if policy not in ("hybrid", "multiqueue"):
            raise ValueError(f"unknown admission policy: {policy!r}")
        if policy == "multiqueue" and preemption != "off":
            raise ValueError(
                "policy='multiqueue' has no peek-then-pop front contract "
                "for the preempt rounds to rely on (HYBRID-only)")
        if policy == "multiqueue" and storage == "klsm":
            raise ValueError(
                "storage='klsm' indexes the HYBRID published set; the "
                "MULTIQUEUE sampled pop has nothing for it to index")
        self.slots, self.frontends, self.k = slots, frontends, k
        self.storage = storage
        self.policy = policy
        self.max_len, self.capacity = max_len, capacity
        self.buffer_cap = buffer_cap
        self.params = params
        self.decode_fn = decode_fn
        self._prefill = jax.jit(prefill_fn)
        self.mesh = mesh
        self.preemption = preemption
        self.margin = float(margin)
        # §13 SLO policy: slack-derived per-slot margins and/or the
        # cheapest-restage victim tie-break inside the preempt rounds
        # (aging happens at the SUBMIT boundary — callers feed aged keys)
        self.slo = slo
        self._slo_margin = slo is not None and slo.slack_margins
        self._victim_cost = slo is not None and slo.victim == "cheapest"
        self.rounds = slots if preemption == "margin" else 0
        self.staging_rows = capacity if staging_rows is None else staging_rows
        self.continuous = continuous
        self.clock = 0
        self.work_steps = 0            # steps that did decode/preempt work
        self.noop_steps = 0            # dead-masked steps (ev.live False)
        # slot splices the step programs performed: phase-1 admissions
        # only (a preempt round's challenger is written in place too, but
        # is not counted); summed from the events, no device cost
        self.splices = 0
        r = self.staging_rows
        staging = Staging(
            tok=jnp.zeros((r,), jnp.int32),
            pos=jnp.zeros((r,), jnp.int32),
            out_len=jnp.ones((r,), jnp.int32),
            budget=jnp.ones((r,), jnp.int32),
            deadline=jnp.full((r,), jnp.inf, jnp.float32),
            row=jnp.zeros((capacity,), jnp.int32),
        )
        staged_caches = jax.tree.map(
            lambda x: jnp.zeros(x.shape[:1] + (r,) + x.shape[2:], x.dtype),
            caches,
        )
        self.carry = FusedCarry(
            pool=kp.init_pool(capacity, frontends),
            caches=caches,
            cur_tok=jnp.zeros((slots,), jnp.int32),
            pos=jnp.zeros((slots,), jnp.int32),
            slot_req=jnp.full((slots,), -1, jnp.int32),
            out_len=jnp.zeros((slots,), jnp.int32),
            budget=jnp.ones((slots,), jnp.int32),
            slot_prio=jnp.full((slots,), jnp.inf, jnp.float32),
            slot_uid=jnp.zeros((slots,), jnp.int32),
            slot_creator=jnp.zeros((slots,), jnp.int32),
            slot_deadline=jnp.full((slots,), jnp.inf, jnp.float32),
            clock=jnp.zeros((), jnp.int32),
            staging=staging,
            staged_caches=staged_caches,
            plan=AdmissionBuffer(
                prio=jnp.full((2, frontends, buffer_cap), jnp.inf,
                              jnp.float32),
                slot=jnp.full((2, frontends, buffer_cap), -1, jnp.int32),
                arrival=jnp.zeros((2, frontends, buffer_cap), jnp.int32),
                count=jnp.zeros((2, frontends), jnp.int32),
            ),
            plan_sel=jnp.zeros((), jnp.int32),
            mq_pops=jnp.zeros((), jnp.uint32),
            pop_aborts=jnp.zeros((), jnp.int32),
            store=(kp.klsm_init(capacity, frontends, k=k)
                   if storage == "klsm" else None),
        )
        if mesh is not None:
            from repro.core.sharded_batch import fused_carry_shardings

            self.carry = jax.device_put(
                self.carry, fused_carry_shardings(mesh, self.carry))
        # host-side bookkeeping (never on the step path)
        self._by_slot = {}                     # pool slot -> item, in flight
        self._tok0 = {}                        # pool slot -> first token
        self._row_of = {}                      # pool slot -> staging row
        self._place_of = {}                    # pool slot -> submit place
        self._free_rows = list(range(r))
        heapq.heapify(self._free_rows)
        self._preempted = set()                # pool slots awaiting resume
        self._slot_ps = [-1] * slots           # decode slot -> pool slot
        self._pending: List[_Arrival] = []     # not-yet-dispatched arrivals
        self._next_slot = 0
        self._arrival = 0
        self._unpub = [0] * frontends          # pool unpub_pushes host mirror
        self._active_items: List[Optional[Any]] = [None] * slots
        self.admission_log: List[Any] = []     # items, admission order
        self.preempt_log: List[Any] = []       # items, eviction order
        # continuous-plane state: packer-thread-shared bookkeeping is
        # guarded by _lock (submit_planned runs off-thread; everything
        # else is the consumer thread's)
        self._lock = threading.Lock()
        self._hsel = 0                         # device plan_sel host mirror
        self._staged_meta = {}                 # pool slot -> deferred staging
        self._plan_pending = None              # uploaded-not-folded counts
        # weakly-shared compiled programs: holding them HERE is what keeps
        # them alive/shared while this loop exists (streaming.shared_jit)
        if storage == "klsm":
            self._flush_fold = streaming._jitted_klsm_fold_dyn(k, True)
            self._flush_fold_places = streaming._jitted_klsm_fold_places_dyn(k)
        else:
            self._flush_fold = streaming._jitted_fold(k, True)
            self._flush_fold_places = streaming._jitted_fold_places(k)
        self._chunk_holders = {}
        self._stage_batch_holders = {}
        self._dispatch_cell = type(self).dispatch_ledger.attach(self)

    @property
    def dispatches(self) -> int:
        """Device programs launched by THIS loop (instance-scoped)."""
        return self._dispatch_cell.n

    def _count(self, n: int = 1):
        self._dispatch_cell.n += n

    @classmethod
    def dispatch_total(cls) -> int:
        """Monotone aggregate of every instance's dispatches since import,
        dead instances included (benchmarks/run.py snapshot-deltas this
        per section)."""
        return cls.dispatch_ledger.total()

    @property
    def pop_aborts(self) -> int:
        """Aborted in-trace selects so far (§16) — sampled MULTIQUEUE
        misses whose attempt was counter-bumped and abandoned. Reads the
        device carry scalar (one scalar readback; 0 under HYBRID)."""
        return int(self.carry.pop_aborts)

    def place_of(self, pool_slot: int) -> int:
        """Buffer place this pool slot's push folds into: the submit
        ``place`` under HYBRID, the hashed home place under MULTIQUEUE —
        the PlanSlot row a continuous-plane publisher must target."""
        with self._lock:
            return self._place_of[pool_slot]

    # ------------------------------------------------------------ submission
    def _alloc_slot(self) -> int:
        s, self._next_slot = streaming.alloc_pool_slot(
            self._by_slot, self._next_slot, self.capacity)
        return s

    def _alloc_row(self) -> int:
        if not self._free_rows:
            raise RuntimeError(
                f"prefill staging full ({self.staging_rows} rows in "
                "flight); raise staging_rows= or pop before pushing")
        return heapq.heappop(self._free_rows)

    def _free_row(self, pool_slot: int):
        heapq.heappush(self._free_rows, self._row_of.pop(pool_slot))

    def submit(self, place: int, priority: float, item: Any, tokens,
               max_new: int, *, at_step: Optional[int] = None,
               deadline: Optional[int] = None) -> int:
        """Stream one request in: run its prefill (one dispatch, submit-time
        — deterministic in the prompt, so admission-time and submit-time
        prefill produce identical tokens), stage the result device-side by
        staging row (pool-slot indirection), and schedule the push's fold at
        ``at_step`` (default: the next unexecuted step, matching the eager
        engine's fold-before-admit of everything submitted before the step).
        Feed f32-exact priorities when comparing against a host oracle
        (``ServeEngine.submit`` quantizes at the boundary). ``deadline`` is
        the request's absolute deadline step (§13; None = best-effort) —
        it rides the staging row into the decode slot, where the slack→
        margin preempt rounds read it. Returns the reserved pool slot."""
        step = self.clock + 1 if at_step is None else at_step
        if step <= self.clock:
            raise ValueError(
                f"at_step={step} already executed (clock={self.clock})")
        if self.policy == "multiqueue":
            # MQ routing (§14.2): ignore the caller's place — the home
            # place is the (f32 priority, uid) hash, computed host-side
            # exactly like StreamingAdmitter/MultiQueue. The fold assigns
            # pool seq in arrival order, so the arrival uid here IS the
            # uid the traced hash would see.
            place = kp.mq_place_host(
                float(np.float32(priority)), self._arrival, self.frontends)
        pool_slot = self._alloc_slot()
        row = self._alloc_row()
        self._by_slot[pool_slot] = item
        self._row_of[pool_slot] = row
        self._place_of[pool_slot] = place
        toks = jnp.asarray(np.asarray(tokens)[None, :], jnp.int32)
        logits, cache1 = self._prefill(self.params, toks)
        tok0 = int(jnp.argmax(logits[0]))
        dl = np.inf if deadline is None else float(deadline)
        staging, staged_caches = _stage_update(
            self.carry.staging, self.carry.staged_caches,
            jnp.int32(pool_slot), jnp.int32(row), jnp.int32(tok0),
            jnp.int32(len(np.asarray(tokens))), jnp.int32(1),
            jnp.int32(max_new), jnp.float32(dl), cache1,
        )
        self.carry = self.carry._replace(
            staging=staging, staged_caches=staged_caches)
        self._tok0[pool_slot] = tok0
        self._pending.append(_Arrival(
            step, place, pool_slot, float(priority), self._arrival))
        self._arrival += 1
        self._count(2)                         # prefill + staging scatter
        return pool_slot

    # ------------------------------------------- continuous submission path
    def submit_planned(self, place: int, priority: float, item: Any,
                       tokens, max_new: int,
                       deadline: Optional[int] = None) -> Tuple[int, int]:
        """Packer half of a continuous submission (DESIGN.md §12): reserve
        a pool slot + staging row, run the prefill (one dispatch), and
        record the resume state host-side — WITHOUT touching the carry, so
        it is safe to call from the packer thread while a chunk is in
        flight. The caller publishes the returned ``(pool_slot, uid)`` into
        a :class:`~repro.serve.streaming.PlanSlot`; the deferred staging is
        applied in one batched program at :meth:`publish_plan` /
        :meth:`adopt_plan` time (consumer thread)."""
        toks = jnp.asarray(np.asarray(tokens)[None, :], jnp.int32)
        plen = int(toks.shape[1])
        with self._lock:
            pool_slot = self._alloc_slot()
            row = self._alloc_row()
            self._by_slot[pool_slot] = item
            self._row_of[pool_slot] = row
            uid = self._arrival
            self._arrival += 1
            if self.policy == "multiqueue":
                # same host-side hash as submit(); callers fetch the home
                # place via place_of() when publishing the plan row
                place = kp.mq_place_host(
                    float(np.float32(priority)), uid, self.frontends)
            self._place_of[pool_slot] = place
        logits, cache1 = self._prefill(self.params, toks)
        tok0 = int(jnp.argmax(logits[0]))
        dl = np.inf if deadline is None else float(deadline)
        with self._lock:
            self._tok0[pool_slot] = tok0
            self._staged_meta[pool_slot] = (row, tok0, plen, max_new, dl,
                                            cache1)
            self._count()                      # prefill only — staging is
        return pool_slot, uid                  # batched per plan

    def _stage_batch(self, r: int):
        h = self._stage_batch_holders.get(r)
        if h is None:
            h = _stage_batch_fn(r)
            self._stage_batch_holders[r] = h
        return h

    def _apply_staging(self, entries):
        """Apply the deferred staging of ``entries`` (a sealed plan's
        publish-order (place, pool_slot, prio, uid) rows) in ONE batched
        device program, padding to the next power-of-two bucket."""
        if not entries:
            return
        with self._lock:
            metas = [self._staged_meta.pop(ps) for (_pl, ps, _pr, _u)
                     in entries]
        r = 1 << (len(entries) - 1).bit_length()
        idx = list(range(len(entries)))
        idx += [len(entries) - 1] * (r - len(entries))
        ps_a = jnp.asarray(
            np.asarray([entries[i][1] for i in idx], np.int32))
        row_a = jnp.asarray(np.asarray([metas[i][0] for i in idx], np.int32))
        tok_a = jnp.asarray(np.asarray([metas[i][1] for i in idx], np.int32))
        pos_a = jnp.asarray(np.asarray([metas[i][2] for i in idx], np.int32))
        out_a = jnp.ones((r,), jnp.int32)
        bud_a = jnp.asarray(np.asarray([metas[i][3] for i in idx], np.int32))
        dl_a = jnp.asarray(np.asarray([metas[i][4] for i in idx],
                                      np.float32))
        cache1s = [metas[i][5] for i in idx]
        staging, staged_caches = self._stage_batch(r)(
            self.carry.staging, self.carry.staged_caches,
            ps_a, row_a, tok_a, pos_a, out_a, bud_a, dl_a, *cache1s)
        self.carry = self.carry._replace(
            staging=staging, staged_caches=staged_caches)
        self._count()

    def publish_plan(self, sealed: PlanSlot):
        """Consumer half of the plan handoff: apply the sealed plan's
        deferred staging (one batched program) and upload its arrival
        arrays into the device plan slot the NEXT chunk folds (one
        scatter) — ~2 dispatches per plan regardless of how many requests
        it carries, vs 2 per request on the fused submit path. Clears the
        sealed slot so the ping-pong can hand it back. Must be paired with
        a following :meth:`run_steps` before the next publish (the device
        slot holds ONE plan)."""
        if sealed.total() == 0:
            sealed.clear()
            return
        if self._plan_pending is not None:
            raise RuntimeError(
                "publish_plan called twice without an intervening "
                "run_steps: the device plan slot still holds an unfolded "
                "plan (would overwrite and drop submissions)")
        self._apply_staging(sealed.entries)
        plan = _plan_upload(
            self.carry.plan, jnp.int32(self._hsel),
            jnp.asarray(sealed.prio), jnp.asarray(sealed.slot),
            jnp.asarray(sealed.arrival), jnp.asarray(sealed.count))
        self.carry = self.carry._replace(plan=plan)
        self._plan_pending = sealed.count.copy()
        self._count()
        sealed.clear()

    def adopt_plan(self, sealed: PlanSlot):
        """Drain-path adoption of a sealed plan: apply its deferred staging
        and schedule its entries as ordinary next-step arrivals instead of
        a device plan upload — the exact :meth:`flush` companion (used when
        the engine drains rather than running another chunk)."""
        self._apply_staging(sealed.entries)
        step = self.clock + 1
        for (place, ps, pr, u) in sealed.entries:
            self._pending.append(_Arrival(step, place, ps, pr, u))
        sealed.clear()

    # --------------------------------------------------------------- packing
    def _pack_bufs(self, n: int):
        """Pack pending arrivals into per-step AdmissionBuffer rows
        [n, P, C] (the scan's xs): entry → its scheduled step's buffer, in
        arrival order (the fold replays publish-on-k from exactly this
        order). Arrivals beyond the chunk stay pending."""
        first = self.clock + 1
        p, c = self.frontends, self.buffer_cap
        prio = np.full((n, p, c), np.inf, np.float32)
        slot = np.full((n, p, c), -1, np.int32)
        arrival = np.zeros((n, p, c), np.int32)
        count = np.zeros((n, p), np.int32)
        remaining = []
        for a in self._pending:
            if a.step >= first + n:
                remaining.append(a)
                continue
            t = a.step - first
            i = count[t, a.place]
            if i >= c:
                raise ValueError(
                    f"fused-step arrival burst overflow: > buffer_cap="
                    f"{c} arrivals for place {a.place} at step {a.step}; "
                    "raise buffer_cap=")
            prio[t, a.place, i] = a.prio
            slot[t, a.place, i] = a.pool_slot
            arrival[t, a.place, i] = a.uid
            count[t, a.place] += 1
        self._pending = remaining
        bufs = AdmissionBuffer(
            prio=jnp.asarray(prio), slot=jnp.asarray(slot),
            arrival=jnp.asarray(arrival), count=jnp.asarray(count),
        )
        return bufs, count

    # ------------------------------------------------------------- chunk fn
    def _chunk_fn(self, n: int):
        h = self._chunk_holders.get(n)
        if h is None:
            slo = self.slo
            h = build_chunk_fn(
                self.decode_fn, k=self.k, frontends=self.frontends,
                slots=self.slots, max_len=self.max_len, n=n,
                preempt=self.preemption == "margin", margin=self.margin,
                rounds=self.rounds, continuous=self.continuous,
                slo_margin=self._slo_margin,
                margin_scale=slo.margin_scale if self._slo_margin else 0.0,
                margin_floor=slo.margin_floor if self._slo_margin else 0.0,
                margin_cap=slo.margin_cap if self._slo_margin else 0.0,
                victim_cost=self._victim_cost, storage=self.storage,
                policy=self.policy)
            self._chunk_holders[n] = h
        return h

    # ----------------------------------------------------------- bookkeeping
    def _mirror_repush(self, place: int):
        u = self._unpub[place] + 1
        self._unpub[place] = 0 if (self.k == 0 or u >= self.k) else u

    def _admit_event(self, rec: StepRecord, s: int, pool_slot: int):
        """Replay one admission event (phase-1 fill or preempt-round
        challenger) into the host mirrors; fresh vs resumed is decided by
        whether the pool slot sits in the preempted set."""
        retain = self.preemption == "margin"
        if retain:
            item = self._by_slot[pool_slot]
        else:
            item = self._by_slot.pop(pool_slot)
            self._place_of.pop(pool_slot, None)
            self._free_row(pool_slot)
        if pool_slot in self._preempted:
            self._preempted.discard(pool_slot)
            rec.resumed.append((s, item, pool_slot))
            rec.order.append((s, item, None, pool_slot))
        else:
            tok0 = self._tok0.pop(pool_slot)
            rec.admitted.append((s, item, tok0, pool_slot))
            rec.order.append((s, item, tok0, pool_slot))
        self._slot_ps[s] = pool_slot
        self._active_items[s] = item
        self.admission_log.append(item)

    # ---------------------------------------------------------------- steps
    def run_steps(self, n: int) -> List[StepRecord]:
        """Advance n engine steps in ONE dispatch; returns one
        :class:`StepRecord` per step, in engine event order (admissions in
        decode-slot order, then preemption rounds, then decode tokens, then
        completions — exactly the eager ``ServeEngine.step`` sequence)."""
        with span("serve.dispatch"):
            bufs, counts = self._pack_bufs(n)
            fn = self._chunk_fn(n)
            self.carry, ev = fn(self.params, self.carry, bufs)
            self._count()
        with span("serve.readback"):
            ev = StepEvents(*(np.asarray(leaf) for leaf in ev))
        with span("serve.replay"):
            return self._replay(n, counts, ev)

    def _replay(self, n: int, counts: np.ndarray,
                ev: StepEvents) -> List[StepRecord]:
        """Replay a chunk's events, read back as numpy arrays, into the host
        mirrors and one :class:`StepRecord` per step."""
        admit, token, active, done, live, pre_slot, pre_vps, pre_ps = ev
        if self.continuous:
            # the chunk folded (and cleared) device plan slot _hsel and
            # flipped plan_sel — mirror both host-side: publish-on-k
            # counters advance by the folded plan's per-place counts,
            # before the per-step buffer counts below
            self._hsel ^= 1
            pc, self._plan_pending = self._plan_pending, None
            if pc is not None:
                for pl in range(self.frontends):
                    u = self._unpub[pl] + int(pc[pl])
                    self._unpub[pl] = 0 if self.k == 0 else u % self.k
        self.work_steps += int(live.sum())
        self.noop_steps += n - int(live.sum())
        self.splices += int((admit >= 0).sum())
        retain = self.preemption == "margin"
        records: List[StepRecord] = []
        for t in range(n):
            self.clock += 1
            for pl in range(self.frontends):                 # unpub mirror
                u = self._unpub[pl] + int(counts[t, pl])
                self._unpub[pl] = 0 if self.k == 0 else u % self.k
            rec = _new_record()
            for s in range(self.slots):
                pslot = int(admit[t, s])
                if pslot >= 0:
                    self._admit_event(rec, s, pslot)
            for r in range(self.rounds):
                v = int(pre_slot[t, r])
                if v < 0:
                    continue
                vps = int(pre_vps[t, r])
                item = self._by_slot[vps]
                self._mirror_repush(self._place_of[vps])
                self._preempted.add(vps)
                self._active_items[v] = None
                self._slot_ps[v] = -1
                rec.preempted.append((v, item, vps))
                self.preempt_log.append(item)
                self._admit_event(rec, v, int(pre_ps[t, r]))
            for s in range(self.slots):
                if active[t, s]:
                    rec.tokens.append(
                        (s, self._active_items[s], int(token[t, s])))
                if done[t, s]:
                    rec.finished.append((s, self._active_items[s]))
                    self._active_items[s] = None
                    if retain:
                        ps = self._slot_ps[s]
                        self._by_slot.pop(ps)
                        self._place_of.pop(ps, None)
                        self._free_row(ps)
                    self._slot_ps[s] = -1
            records.append(rec)
        return records

    # ---------------------------------------------------------------- flush
    def flush(self, place: Optional[int] = None):
        """Exact drain at a chunk boundary: every pending arrival (even ones
        scheduled for future steps) folds into the pool NOW, force-publishing
        every place (``place=None``) or exactly one (the per-place
        ``HybridKQueue.flush(p)`` analogue; the others keep stream-accurate
        publish-on-k, which fold timing cannot perturb — DESIGN.md §10).
        Partially-drained chunks are safe: arrivals already folded live in
        the pool, the rest are packed here — nothing is dropped or double-
        folded (regression-pinned by tests/test_fused_step.py)."""
        if self._plan_pending is not None:
            raise RuntimeError(
                "flush with an uploaded-but-unfolded plan: run_steps the "
                "published chunk first (or adopt_plan instead of "
                "publish_plan when draining)")
        p = self.frontends
        need = max(
            (sum(1 for a in self._pending if a.place == pl)
             for pl in range(p)), default=1)
        # pad the one-shot buffer width to buffer_cap buckets: repeated
        # flushes with varying pending counts hit a handful of compiled fold
        # shapes instead of one XLA specialization per distinct width
        c = self.buffer_cap * max(1, -(-max(need, 1) // self.buffer_cap))
        prio = np.full((p, c), np.inf, np.float32)
        slot = np.full((p, c), -1, np.int32)
        arrival = np.zeros((p, c), np.int32)
        count = np.zeros((p,), np.int32)
        for a in self._pending:
            i = count[a.place]
            prio[a.place, i] = a.prio
            slot[a.place, i] = a.pool_slot
            arrival[a.place, i] = a.uid
            count[a.place] += 1
        self._pending = []
        buf = AdmissionBuffer(
            prio=jnp.asarray(prio), slot=jnp.asarray(slot),
            arrival=jnp.asarray(arrival), count=jnp.asarray(count),
        )
        store = self.carry.store
        if place is None:
            if self.storage == "klsm":
                pool, store = self._flush_fold(
                    self.carry.pool, buf, store)
            else:
                pool, _ = self._flush_fold(self.carry.pool, buf)
            self._unpub = [0] * p
        else:
            mask = jnp.zeros((p,), bool).at[place].set(True)
            if self.storage == "klsm":
                pool, store = self._flush_fold_places(
                    self.carry.pool, buf, mask, store)
            else:
                pool, _ = self._flush_fold_places(
                    self.carry.pool, buf, mask)
            for pl in range(p):
                u = self._unpub[pl] + int(count[pl])
                self._unpub[pl] = (
                    0 if (pl == place or self.k == 0) else u % self.k)
        self.carry = self.carry._replace(pool=pool, store=store)
        self._count()

    # --------------------------------------------------------------- queries
    def __len__(self) -> int:
        """In-flight requests: submitted but not yet admitted (plus running
        ones under ``preemption="margin"``, whose pool slots stay reserved
        for the re-queue path — the ``StreamingAdmitter`` retain-mode
        analogue, at chunk granularity)."""
        return len(self._by_slot)

    def pending(self, place: int) -> int:
        """Unpublished + still-scheduled pushes of ``place`` (host queue's
        ``len(local)`` analogue — no device readback)."""
        return self._unpub[place] + sum(
            1 for a in self._pending if a.place == place)

    @property
    def idle(self) -> bool:
        return (not any(i is not None for i in self._active_items)
                and len(self._by_slot) == 0)


# ---------------------------------------------------------------------------
# toy model: admission semantics are model-independent — the differential
# harness (tests/test_fused_step.py) and the mesh selftest drive this pair
# ---------------------------------------------------------------------------

TOY_VOCAB = 13


def toy_decode_fn(params, caches, tok, pos):
    """Trivial deterministic decode (token stream is a pure function of the
    first token and position — host-simulable, so the randomized harness
    checks token routing without paying for a transformer)."""
    logits = jax.nn.one_hot(
        (tok * 7 + pos) % TOY_VOCAB, TOY_VOCAB, dtype=jnp.float32)
    return logits, caches


def toy_prefill_fn(params, toks):
    first = (jnp.sum(toks) * 3 + toks.shape[1]) % TOY_VOCAB
    logits = jax.nn.one_hot(first, TOY_VOCAB, dtype=jnp.float32)[None]
    return logits, {"kv": jnp.ones((1, 1, 2), jnp.float32)}


def toy_loop(*, slots, frontends, k, max_len=10_000, capacity=128,
             buffer_cap=32, mesh=None, preemption="off", margin=0.0,
             staging_rows=None, continuous=False, slo=None,
             storage="flat", policy="hybrid") -> FusedServeLoop:
    """A :class:`FusedServeLoop` over the toy model, with the engine's cache
    convention (slot dim = axis 1 of every leaf) — splice/staging machinery
    is exercised end-to-end, compiles are shared across LIVE instances (the
    toy fns are module-level, so ``build_chunk_fn``'s weak cache hits while
    any loop of the same config is alive)."""
    caches = {"kv": jnp.zeros((1, slots, 2), jnp.float32)}
    return FusedServeLoop(
        slots=slots, frontends=frontends, k=k, max_len=max_len,
        capacity=capacity, buffer_cap=buffer_cap, params=None,
        caches=caches, decode_fn=toy_decode_fn, prefill_fn=toy_prefill_fn,
        mesh=mesh, preemption=preemption, margin=margin,
        staging_rows=staging_rows, continuous=continuous, slo=slo,
        storage=storage, policy=policy)


# ---------------------------------------------------------------------------
# selftest (subprocess: run under XLA_FLAGS=--xla_force_host_platform_device_count=8)
# ---------------------------------------------------------------------------

def _oracle_drive(trace, *, slots, frontends, k, max_len, queue, fold_fn):
    """Drive the eager slot state machine (the exact ServeEngine.step
    sequence) over ``trace`` against a queue-like admission plane; returns
    (admission uids, (step, slot, uid) fills)."""  # pragma: no cover
    active = [None] * slots   # uid -> dict(out, pos, max_new)
    meta = {}
    admission, fills = [], []
    for step, burst in enumerate(trace, start=1):
        for (place, pr, uid, max_new, plen) in burst:
            queue.push(place, pr, uid)
            meta[uid] = (max_new, plen)
        fold_fn()
        for s in range(slots):
            if active[s] is not None:
                continue
            got = queue.pop(s % frontends)
            if got is None:
                break
            uid = got[1]
            admission.append(uid)
            fills.append((step, s, uid))
            max_new, plen = meta[uid]
            active[s] = {"out": 1, "pos": plen, "max_new": max_new}
        for s in range(slots):
            a = active[s]
            if a is None:
                continue
            a["pos"] += 1
            a["out"] += 1
            if a["out"] >= a["max_new"] or a["pos"] >= max_len - 1:
                active[s] = None
    return admission, fills


def _fused_drive(trace, *, slots, frontends, k, max_len, chunk,
                 mesh=None):  # pragma: no cover
    loop = toy_loop(slots=slots, frontends=frontends, k=k, max_len=max_len,
                    mesh=mesh)
    for step, burst in enumerate(trace, start=1):
        for (place, pr, uid, max_new, plen) in burst:
            loop.submit(place, pr, uid, np.arange(plen) + uid, max_new,
                        at_step=step)
    admission, fills = [], []
    t = 0
    while t < len(trace):
        n = min(chunk, len(trace) - t)
        for i, rec in enumerate(loop.run_steps(n)):
            for (s, item, _tok0, _ps) in rec.admitted:
                admission.append(item)
                fills.append((t + i + 1, s, item))
        t += n
    return admission, fills


def _selftest_toy_differential(mesh=None, chunk=4):  # pragma: no cover
    from repro.core.host_queue import HybridKQueue

    slots, frontends, k, max_len = 4, 2, 3, 64
    rng = np.random.default_rng(17)
    trace, uid = [], 0
    for _ in range(40):
        burst = []
        for _ in range(int(rng.integers(0, 4))):
            burst.append((int(rng.integers(frontends)),
                          float(rng.integers(0, 8)) / 4.0, uid,
                          int(rng.integers(1, 5)), int(rng.integers(1, 4))))
            uid += 1
        trace.append(burst)

    host = HybridKQueue(frontends, k, spy="min_index")
    ref = _oracle_drive(trace, slots=slots, frontends=frontends, k=k,
                        max_len=max_len, queue=host, fold_fn=lambda: None)
    dev_q = streaming.StreamingAdmitter(frontends, k, capacity=128)
    dev = _oracle_drive(trace, slots=slots, frontends=frontends, k=k,
                        max_len=max_len, queue=dev_q, fold_fn=dev_q.fold)
    fused1 = _fused_drive(trace, slots=slots, frontends=frontends, k=k,
                          max_len=max_len, chunk=1, mesh=mesh)
    fusedN = _fused_drive(trace, slots=slots, frontends=frontends, k=k,
                          max_len=max_len, chunk=chunk, mesh=mesh)
    assert fused1 == ref, (fused1, ref)
    assert fused1 == dev, (fused1, dev)
    assert fusedN == ref, (fusedN, ref)
    tag = "mesh" if mesh is not None else "local"
    print(f"FUSED_TRACE_OK {tag} uid={uid} admitted={len(ref[0])}")


def _preempt_oracle_drive(trace, *, slots, frontends, k, max_len, margin,
                          queue):  # pragma: no cover
    """Eager slot state machine WITH §11 preemption over the host queue:
    the python truth the fused preemptive plane must reproduce (the full
    version, with token streams, lives in tests/test_fused_step.py)."""
    active = [None] * slots
    meta, stash = {}, {}
    push_seq = [0]
    uid_of = {}
    admission, evictions = [], []

    def push(place, pr, uid):
        queue.push(place, pr, uid)
        push_seq[0] += 1
        uid_of[uid] = push_seq[0]

    def admit(s, got, step):
        pr, uid = got
        admission.append(uid)
        if uid in stash:
            active[s] = stash.pop(uid)
        else:
            max_new, plen, place = meta[uid]
            active[s] = {"uid": uid, "pr": pr, "out": 1, "pos": plen,
                         "max_new": max_new, "place": place}

    for step, burst in enumerate(trace, start=1):
        for (place, pr, uid, max_new, plen) in burst:
            meta[uid] = (max_new, plen, place)
            push(place, pr, uid)
        filled = set()
        for s in range(slots):
            if active[s] is not None:
                continue
            got = queue.pop(s % frontends)
            if got is None:
                break
            admit(s, got, step)
            filled.add(s)
        for _ in range(slots):
            elig = [s for s in range(slots)
                    if active[s] is not None and s not in filled]
            if not elig:
                break
            v = max(elig, key=lambda s: (active[s]["pr"],
                                         uid_of[active[s]["uid"]]))
            top = queue.peek(v % frontends)
            if top is None or not kp.preempt_beats(top, margin,
                                                   active[v]["pr"]):
                break
            victim = active[v]
            evictions.append(victim["uid"])
            stash[victim["uid"]] = victim
            active[v] = None
            push(victim["place"], victim["pr"], victim["uid"])
            got = queue.pop(v % frontends)
            admit(v, got, step)
            filled.add(v)
        for s in range(slots):
            a = active[s]
            if a is None:
                continue
            a["pos"] += 1
            a["out"] += 1
            if a["out"] >= a["max_new"] or a["pos"] >= max_len - 1:
                active[s] = None
    return admission, evictions


def _selftest_preempt_differential(mesh=None, chunk=4):  # pragma: no cover
    """Fused preemptive plane == host HybridKQueue preemption oracle on a
    randomized inversion-heavy trace (admission order AND victim order),
    for chunk 1 and ``chunk`` (the ISSUE 5 acceptance criterion)."""
    from repro.core.host_queue import HybridKQueue

    slots, frontends, k, max_len, margin = 3, 2, 2, 64, 0.5
    rng = np.random.default_rng(23)
    trace, uid = [], 0
    for _ in range(30):
        burst = []
        for _ in range(int(rng.integers(0, 3))):
            burst.append((uid % frontends,
                          float(rng.integers(0, 8)), uid,
                          int(rng.integers(2, 7)), int(rng.integers(1, 4))))
            uid += 1
        trace.append(burst)

    host = HybridKQueue(frontends, k, spy="min_index")
    ref = _preempt_oracle_drive(
        trace, slots=slots, frontends=frontends, k=k, max_len=max_len,
        margin=margin, queue=host)

    def fused(chunk_):
        loop = toy_loop(slots=slots, frontends=frontends, k=k,
                        max_len=max_len, preemption="margin", margin=margin)
        for step, burst in enumerate(trace, start=1):
            for (place, pr, u, max_new, plen) in burst:
                loop.submit(place, pr, u, np.arange(plen) + u, max_new,
                            at_step=step)
        t = 0
        while t < len(trace):
            n = min(chunk_, len(trace) - t)
            loop.run_steps(n)
            t += n
        return loop.admission_log, loop.preempt_log

    f1, fn = fused(1), fused(chunk)
    assert f1 == ref, (f1, ref)
    assert fn == ref, (fn, ref)
    tag = "mesh" if mesh is not None else "local"
    print(f"PREEMPT_TRACE_OK {tag} uid={uid} evicted={len(ref[1])}")


def _selftest_engine_fused(mesh):  # pragma: no cover
    """ServeEngine(step="fused", mesh=composed) admits in exactly the host
    oracle's order, with identical token streams (the ISSUE 4 acceptance
    criterion under the 8-device batch × data × model mesh)."""
    from repro.configs import get_reduced
    from repro.models import materialize, model_p
    from repro.serve.config import ServeConfig
    from repro.serve.engine import Request, ServeEngine

    cfg = get_reduced("qwen3_1_7b")
    params = materialize(jax.random.PRNGKey(0), model_p(cfg))
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, cfg.vocab_size, 6).astype(np.int32)
               for _ in range(8)]
    prios = [float(v) for v in rng.permutation(len(prompts))]

    def run(mode, mesh_):
        eng = ServeEngine(cfg, params, slots=4, max_len=32, frontends=2, k=2,
                          config=ServeConfig(step=mode, step_chunk=3,
                                             mesh=mesh_))
        for i, toks in enumerate(prompts):
            eng.submit(Request(rid=i, tokens=toks, max_new=4,
                               priority=prios[i]), frontend=i % 2)
        done = eng.run()
        return eng.admission_log, {r.rid: r.out for r in done}

    ref_log, ref_out = run("host", None)
    fus_log, fus_out = run("fused", mesh)
    assert ref_log == fus_log, (ref_log, fus_log)
    assert ref_out == fus_out, (ref_out, fus_out)
    print(f"FUSED_ENGINE_OK order={ref_log}")


def selftest() -> None:  # pragma: no cover - exercised via subprocess
    from repro.launch.mesh import make_test_production_batch_mesh

    d = len(jax.devices())
    _selftest_toy_differential()
    _selftest_preempt_differential()
    if d >= 8:
        mesh = make_test_production_batch_mesh()
        _selftest_toy_differential(mesh=mesh)
        _selftest_preempt_differential(mesh=mesh)
        _selftest_engine_fused(mesh)
    print(f"FUSED_OK devices={d}")


if __name__ == "__main__":
    import sys

    if "--selftest" in sys.argv:
        selftest()
