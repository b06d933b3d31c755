"""Differential + protocol harness for continuous serving (ISSUE 6 tentpole,
DESIGN.md §12) and the fused-plane accounting sweep that rides along:

  * continuous-plane admission order, fills, token streams, and popped-pool-
    slot sequence are bit-identical to the PR-4 fused plane AND the host
    ``HybridKQueue(spy="min_index")`` oracle over randomized assignments of
    submissions to chunk boundaries — empty-plan boundaries, priority ties,
    and k = 0 (the strict plane) included,
  * a submission landing in a LATER chunk than its submit boundary (the
    packer-behind case) is just a late push: bit-identical to the oracle
    replayed at the observed landing boundaries, and within ρ = P·k there,
  * chunk-boundary races: exactly-once landing across plan flips and
    slot-starved chunks; empty-plan chunks dispatch nothing extra and keep
    the ping-pong parity; the PlanBook publish/seal protocol backpressures
    (spill-to-next-plan) and raises on dirty hand-back,
  * the async packer thread drains submissions into plans ahead of the
    device and is liveness-safe under forced spills; a dropped engine stops
    its packer (weakref-finalized),
  * dead-step masking (satellite 1): padded/trailing/gap no-op steps run no
    decode or preempt work — ``work_steps``/``noop_steps`` pin the budget —
    while staying bit-identical to chunk=1 execution,
  * dispatch counters are instance-scoped (satellite 2) with a monotone
    aggregating classmethod that retains retired instances' counts,
  * the jitted-helper caches are weakly keyed (satellite 3): live same-config
    loops share compiles, the last owner's death frees the cache entry, and
    no device buffers survive loop/engine teardown.
"""
import gc
import threading
import time
import weakref
from collections import deque

import jax
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.serve import streaming
from repro.serve.config import ServeConfig
from repro.serve.fused_step import FusedServeLoop, toy_loop
from repro.serve.streaming import PlanBook, StreamingAdmitter
from test_fused_step import PRIO_GRID, _prompt, drive_fused, drive_oracle

# keep recent loops (and thus their weakly-cached compiles) alive across
# hypothesis examples — purely a test-speed device, the cache itself is weak
_KEEP = deque(maxlen=8)


def gen_boundary_trace(seed, n_chunks, frontends, *, burst_max=4):
    """Per-chunk-boundary arrival bursts — the continuous plane's native
    granularity. Every interleaving of submit vs chunk boundary is one
    assignment of submissions to boundaries, including boundaries reached
    only after several chunks have already run (and empty boundaries)."""
    rng = np.random.default_rng(seed)
    bursts, uid = [], 0
    for _ in range(n_chunks):
        burst = []
        for _ in range(int(rng.integers(0, burst_max + 1))):
            pr = float(np.float32(PRIO_GRID[rng.integers(len(PRIO_GRID))]))
            burst.append((int(rng.integers(frontends)), pr, uid,
                          int(rng.integers(1, 5)),
                          int(rng.integers(1, 4))))
            uid += 1
        bursts.append(burst)
    return bursts


def boundary_step_trace(bursts, chunk):
    """The per-step trace equivalent: each boundary's burst arrives at the
    first step of its chunk (where the device plan fold lands it)."""
    trace = [[] for _ in range(len(bursts) * chunk)]
    for b, burst in enumerate(bursts):
        trace[b * chunk] = list(burst)
    return trace


def drive_continuous(bursts, *, slots, frontends, k, max_len, chunk,
                     capacity=128, publish_at=None):
    """Drive the continuous plane with a synchronous packer: each boundary
    packs its burst into the open PlanSlot, seals, publishes to the device
    plan slot, and runs one chunk. ``publish_at`` optionally maps a uid to a
    LATER boundary: the submission is prefilled at its submit boundary but
    held out of the plan until then (the packer-behind case)."""
    loop = toy_loop(slots=slots, frontends=frontends, k=k, max_len=max_len,
                    capacity=capacity, continuous=True)
    book = PlanBook(frontends, loop.buffer_cap)
    held = []
    admission, fills, tokens, pop_slots, records = [], [], {}, [], []
    for b, burst in enumerate(bursts):
        for (_lb, place, ps, pr, u) in [h for h in held if h[0] == b]:
            assert book.publish(place, ps, pr, u)
        held = [h for h in held if h[0] != b]
        for (place, pr, uid, max_new, plen) in burst:
            ps, u = loop.submit_planned(place, pr, uid, _prompt(uid, plen),
                                        max_new)
            land = b if publish_at is None else publish_at.get(uid, b)
            if land > b:
                held.append((land, place, ps, pr, u))
            else:
                assert book.publish(place, ps, pr, u)
        loop.publish_plan(book.seal())
        recs = loop.run_steps(chunk)
        records.extend(recs)
        for i, rec in enumerate(recs):
            for (s, uid, tok0, ps) in rec.admitted:
                admission.append(uid)
                fills.append((b * chunk + i + 1, s, uid))
                pop_slots.append(ps)
                tokens[uid] = [tok0]
            for (_s, uid, tok) in rec.tokens:
                tokens[uid].append(tok)
    assert not held, "publish_at boundary beyond the trace"
    _KEEP.append(loop)
    return admission, fills, tokens, pop_slots, records, loop


# ---------------------------------------------------------------------------
# the tentpole differential
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("frontends,slots,k", [(2, 4, 3), (3, 5, 1), (2, 3, 0)])
@settings(max_examples=3, deadline=None)
@given(seed=st.integers(0, 10_000))
def test_continuous_matches_fused_and_host(frontends, slots, k, seed):
    """Tentpole acceptance: continuous == fused == host oracle — admission
    order, fills, token streams (and popped pool slots vs the fused plane)
    — for every randomized interleaving of submit vs chunk boundary.
    Covers empty-plan boundaries, priority ties, and k = 0 (with k = 0 the
    plan still defers to the boundary, but admission is priority-strict)."""
    max_len, chunk = 64, 3
    bursts = gen_boundary_trace(seed, 5, frontends)
    trace = boundary_step_trace(bursts, chunk)
    host = drive_oracle(trace, slots=slots, frontends=frontends, k=k,
                        max_len=max_len, plane="host")
    f_adm, f_fills, f_toks, f_pops, _, f_loop = drive_fused(
        trace, slots=slots, frontends=frontends, k=k, max_len=max_len,
        chunk=chunk)
    _KEEP.append(f_loop)
    assert (f_adm, f_fills, f_toks) == host.results()
    c_adm, c_fills, c_toks, c_pops, _, _ = drive_continuous(
        bursts, slots=slots, frontends=frontends, k=k, max_len=max_len,
        chunk=chunk)
    assert (c_adm, c_fills, c_toks) == host.results()
    assert c_pops == f_pops


def test_continuous_deferred_landing_matches_oracle_within_rho():
    """The ISSUE 6 ρ claim: a submission landing in a LATER chunk than its
    submit boundary is just a late push — the plane stays bit-identical to
    the host oracle replayed at the OBSERVED landing boundaries, and every
    admission ignores at most ρ = P·k strictly-better landed-but-unadmitted
    requests (deferral consumes no extra relaxation budget)."""
    frontends, slots, k, max_len, chunk, n_chunks = 3, 4, 2, 64, 3, 8
    rng = np.random.default_rng(17)
    bursts, uid = [], 0
    for _ in range(n_chunks):
        burst = []
        for _ in range(int(rng.integers(0, 5))):
            # distinct priorities: deferral reorders pushes across
            # boundaries, so f32-tie arrival-order semantics would differ
            # between a publish-order host replay and the uid-keyed plan
            pr = float(np.float32((uid * 37 % 101) / 13.0))
            burst.append((int(rng.integers(frontends)), pr, uid,
                          int(rng.integers(1, 4)),
                          int(rng.integers(1, 4))))
            uid += 1
        bursts.append(burst)
    publish_at, land = {}, [[] for _ in range(n_chunks)]
    for b, burst in enumerate(bursts):
        for e in burst:
            d = 1 if (e[2] % 3 == 0 and b + 1 < n_chunks) else 0
            publish_at[e[2]] = b + d
            land[b + d].append(e)
    assert any(publish_at[u] > b for b, burst in enumerate(bursts)
               for (_p, _pr, u, _mn, _pl) in burst), "no deferral exercised"
    for row in land:
        row.sort(key=lambda e: e[2])
    adm, fills, toks, _pops, _, _ = drive_continuous(
        bursts, slots=slots, frontends=frontends, k=k, max_len=max_len,
        chunk=chunk, publish_at=publish_at)
    trace = [[] for _ in range(n_chunks * chunk)]
    for b, row in enumerate(land):
        trace[b * chunk] = row
    host = drive_oracle(trace, slots=slots, frontends=frontends, k=k,
                        max_len=max_len, plane="host")
    assert (adm, fills, toks) == host.results()
    landing_step = {e[2]: b * chunk + 1
                    for b, row in enumerate(land) for e in row}
    prio_of = {e[2]: e[1] for burst in bursts for e in burst}
    admitted, worst = set(), 0
    for (step, _s, u) in fills:
        better = sum(1 for v, ls in landing_step.items()
                     if v != u and v not in admitted and ls <= step
                     and prio_of[v] < prio_of[u])
        worst = max(worst, better)
        admitted.add(u)
    assert worst <= frontends * k, worst


# ---------------------------------------------------------------------------
# chunk-boundary races: exactly-once, empty plans, PlanBook protocol
# ---------------------------------------------------------------------------

def test_continuous_exactly_once_across_boundaries():
    """Exactly-once landing: with more submissions than decode slots the
    pool backs up across chunks and plan flips — every submission is
    admitted exactly once, never dropped, never double-admitted."""
    slots, frontends, chunk = 2, 2, 2
    loop = toy_loop(slots=slots, frontends=frontends, k=1, capacity=64,
                    continuous=True)
    book = PlanBook(frontends, loop.buffer_cap)
    admitted, uid = [], 0
    for _b in range(3):
        for _ in range(3):
            ps, u = loop.submit_planned(uid % frontends, float(uid % 2), uid,
                                        _prompt(uid, 2), 4)
            assert book.publish(uid % frontends, ps, float(uid % 2), u)
            uid += 1
        loop.publish_plan(book.seal())
        for rec in loop.run_steps(chunk):
            admitted.extend(u for (_s, u, _t, _p) in rec.admitted)
    for _ in range(40):
        if loop.idle:
            break
        loop.publish_plan(book.seal())
        for rec in loop.run_steps(chunk):
            admitted.extend(u for (_s, u, _t, _p) in rec.admitted)
    assert loop.idle
    assert len(admitted) == len(set(admitted)), "double admission"
    assert sorted(admitted) == list(range(uid)), "dropped submission"


def test_continuous_empty_plan_chunks():
    """Empty-plan boundaries upload nothing (one chunk dispatch only) and
    keep the ping-pong parity: a real plan published after a run of empty
    boundaries still lands exactly at its own boundary's first step."""
    loop = toy_loop(slots=2, frontends=2, k=1, continuous=True)
    book = PlanBook(2, loop.buffer_cap)
    d0 = loop.dispatches
    loop.publish_plan(book.seal())
    recs = loop.run_steps(2)
    assert loop.dispatches - d0 == 1          # the chunk program, nothing else
    assert (loop.work_steps, loop.noop_steps) == (0, 2)
    assert all(not r.admitted and not r.tokens for r in recs)
    loop.publish_plan(book.seal())            # second empty flip (odd parity)
    loop.run_steps(2)
    d1 = loop.dispatches
    ps, u = loop.submit_planned(0, 1.0, 7, _prompt(7, 2), 2)
    assert book.publish(0, ps, 1.0, u)
    loop.publish_plan(book.seal())
    recs = loop.run_steps(2)
    # prefill + batched staging + plan upload + chunk
    assert loop.dispatches - d1 == 4
    assert [u for r in recs for (_s, u, _t, _p) in r.admitted] == [7]
    assert len(recs[0].admitted) == 1         # landed at the boundary step


def test_plan_book_backpressure_and_protocol():
    """PlanBook unit contract: per-place row capacity backpressures
    (non-blocking publish returns False; publish_wait times out with no
    sealer, spills into the next plan after a seal), rows are independent
    across places, and handing a sealed slot back dirty raises."""
    book = PlanBook(2, 2)
    assert book.publish(0, 10, 1.0, 0)
    assert book.publish(0, 11, 1.5, 1)
    assert not book.publish(0, 12, 2.0, 2)          # place-0 row full
    assert book.publish(1, 13, 0.5, 3)              # place-1 row independent
    assert book.publish_wait(0, 12, 2.0, 2, timeout=0.05) is False
    assert book.pending() == 3
    sealed = book.seal()
    assert sealed.total() == 3 and book.pending() == 0
    assert [e[1] for e in sealed.entries] == [10, 11, 13]  # publish order
    assert book.publish(0, 12, 2.0, 2)              # spill into the next plan
    with pytest.raises(RuntimeError, match="ping-pong"):
        book.seal()                                 # sealed not yet cleared
    sealed.clear()
    # a sealing consumer unblocks a producer blocked on a full row
    book2 = PlanBook(1, 1)
    assert book2.publish(0, 1, 0.5, 0)
    got = []
    t = threading.Thread(target=lambda: got.append(
        book2.publish_wait(0, 2, 0.5, 1, timeout=10.0)))
    t.start()
    time.sleep(0.05)
    s = book2.seal()
    t.join(10.0)
    assert got == [True] and book2.pending() == 1
    s.clear()


def test_plan_slot_clear_keeps_uploaded_arrays():
    """A sealed plan's arrays go to an asynchronous device upload that may
    still read them after ``clear``: clearing starts fresh arrays and never
    refills the handed-off ones in place (an in-place refill let the upload
    read slot -1, which the pool scatter wrapped to ``capacity - 1``)."""
    book = PlanBook(2, 4)
    assert book.publish(0, 10, 1.0, 0)
    assert book.publish(1, 11, 0.5, 1)
    sealed = book.seal()
    handed = (sealed.prio, sealed.slot, sealed.arrival, sealed.count)
    before = [a.copy() for a in handed]
    sealed.clear()
    for a, b in zip(handed, before):
        np.testing.assert_array_equal(a, b)
    fresh = (sealed.prio, sealed.slot, sealed.arrival, sealed.count)
    assert all(a is not b for a, b in zip(handed, fresh))
    assert sealed.total() == 0 and not sealed.entries
    assert (sealed.slot == -1).all() and (sealed.prio == np.inf).all()


def test_threaded_packer_backpressure_and_liveness():
    """The async packer under forced spills: plan rows sized below the
    burst, so publish_wait blocks until the consumer seals and entries
    spill across plans — every submission still lands exactly once."""
    from repro.serve.engine import Request, _PlanPacker

    loop = toy_loop(slots=2, frontends=2, k=1, capacity=64, buffer_cap=2,
                    continuous=True)
    book = PlanBook(2, 2)                     # 2 entries/place/plan: spills
    packer = _PlanPacker(loop, book)
    try:
        n = 10
        for uid in range(n):
            packer.submit(uid % 2, float(uid % 3), Request(
                rid=uid, tokens=_prompt(uid, 2), max_new=2,
                priority=float(uid % 3)))
        admitted, deadline = [], time.monotonic() + 120
        while len(admitted) < n:
            assert time.monotonic() < deadline, (admitted, packer.backlog())
            packer.check()
            loop.publish_plan(book.seal())
            for rec in loop.run_steps(2):
                admitted.extend(r.rid for (_s, r, _t, _p) in rec.admitted)
            packer.wait_progress()
        assert sorted(admitted) == list(range(n))
    finally:
        packer.stop()


# ---------------------------------------------------------------------------
# satellite 1: dead-step masking
# ---------------------------------------------------------------------------

def test_dead_step_masking_counts_and_identity():
    """Padded trailing and mid-trace-gap steps are masked: no decode or
    preempt work runs (``work_steps``/``noop_steps`` pin the per-chunk flop
    budget — the dispatch count per chunk is 1 either way), while fold/pop
    bookkeeping still runs so masked chunks stay bit-identical to chunk=1."""
    loop = toy_loop(slots=2, frontends=2, k=2)
    loop.submit(0, 1.0, 0, _prompt(0, 2), 3, at_step=1)
    recs = loop.run_steps(8)                  # work on steps 1-2; 6 trailing
    assert (loop.work_steps, loop.noop_steps) == (2, 6)
    assert all(not r.admitted and not r.tokens and not r.finished
               for r in recs[2:])
    # mid-trace gap, one 12-step chunk vs twelve 1-step chunks
    trace = [[] for _ in range(12)]
    trace[0] = [(0, 1.0, 0, 3, 2), (1, 0.5, 1, 2, 1)]
    trace[9] = [(1, 2.0, 2, 2, 2)]
    outs, counters = {}, {}
    for chunk in (1, 12):
        adm, fills, toks, pops, _, gl = drive_fused(
            trace, slots=2, frontends=2, k=2, max_len=64, chunk=chunk)
        outs[chunk] = (adm, fills, toks, pops)
        counters[chunk] = (gl.work_steps, gl.noop_steps)
        _KEEP.append(gl)
    assert outs[1] == outs[12]
    assert counters[1] == counters[12] == (3, 9)
    # the preemptive plane masks its preempt rounds on dead steps too
    ploop = toy_loop(slots=2, frontends=2, k=1, preemption="margin",
                     margin=0.0)
    ploop.submit(0, 1.0, 0, _prompt(0, 2), 3, at_step=1)
    ploop.run_steps(8)
    assert (ploop.work_steps, ploop.noop_steps) == (2, 6)
    assert ploop.preempt_log == []


# ---------------------------------------------------------------------------
# satellite 2: instance-scoped dispatch counters
# ---------------------------------------------------------------------------

def test_dispatch_counters_instance_scoped():
    """Counters are per-instance (two live planes don't bleed into each
    other) and the classmethod aggregate is monotone, retaining retired
    instances' counts — the benchmarks' snapshot-delta contract."""
    base = StreamingAdmitter.dispatch_total()
    a = StreamingAdmitter(2, 1, capacity=8)
    b = StreamingAdmitter(2, 1, capacity=8)
    a.push(0, 1.0, 0)
    a.fold()
    assert a.dispatches > 0 and b.dispatches == 0
    da = a.dispatches
    assert StreamingAdmitter.dispatch_total() - base == da
    del a
    gc.collect()
    assert StreamingAdmitter.dispatch_total() - base == da  # retired kept
    assert b.dispatches == 0

    base = FusedServeLoop.dispatch_total()
    l1 = toy_loop(slots=2, frontends=2, k=1)
    l2 = toy_loop(slots=2, frontends=2, k=1)
    l1.submit(0, 1.0, 0, _prompt(0, 2), 2)
    assert l1.dispatches == 2 and l2.dispatches == 0  # prefill + staging
    l1.run_steps(1)
    d1 = l1.dispatches
    assert d1 == 3 and l2.dispatches == 0
    del l1
    gc.collect()
    assert FusedServeLoop.dispatch_total() - base == d1
    _KEEP.append(l2)


# ---------------------------------------------------------------------------
# satellite 3: weak jit caches + teardown
# ---------------------------------------------------------------------------

def test_weak_jit_cache_shares_and_tears_down():
    """Live same-config loops share one compiled chunk program; the last
    owner's death frees the weak cache entry; and a full submit/run/flush
    session leaves NO device buffers behind (the lru_cache regression this
    PR removes: compiled closures used to pin mesh + buffers forever)."""
    cfg = dict(slots=2, frontends=2, k=1)
    l1, l2 = toy_loop(**cfg), toy_loop(**cfg)
    h = l1._chunk_fn(2)
    assert l2._chunk_fn(2) is h               # shared while both live
    ref = weakref.ref(h)
    del h, l1, l2
    gc.collect()
    assert ref() is None                      # weak: freed with last owner

    def session():
        loop = toy_loop(**cfg)
        loop.submit(0, 1.0, 0, _prompt(0, 2), 2)
        loop.run_steps(2)
        loop.flush()
        loop.run_steps(1)

    _KEEP.clear()
    session()                                 # warm: populate global jits
    gc.collect()
    before = len(jax.live_arrays())
    session()
    gc.collect()
    assert len(jax.live_arrays()) <= before


# ---------------------------------------------------------------------------
# engine level: ServeEngine(step="continuous") on the real reduced model
# ---------------------------------------------------------------------------

def test_engine_continuous_matches_host():
    """ServeEngine(step="continuous"): admission order and token streams
    identical to the host oracle for sync packing at chunk 1 and 3, and for
    the threaded packer once its backlog has drained into the open plan;
    the flush_frontends drain path (adopt_plan) completes everything; a
    dropped engine stops its packer thread and leaks no device buffers."""
    from repro.configs import get_reduced
    from repro.models import materialize, model_p
    from repro.serve.engine import Request, ServeEngine

    cfg = get_reduced("qwen3_1_7b")
    params = materialize(jax.random.PRNGKey(0), model_p(cfg))
    rng = np.random.default_rng(2)
    prompts = [rng.integers(0, cfg.vocab_size, 6).astype(np.int32)
               for _ in range(8)]
    prios = [float(v) for v in rng.permutation(8)]

    def run(mode, chunk=1, packer="sync"):
        eng = ServeEngine(cfg, params, slots=3, max_len=32, frontends=2, k=2,
                          config=ServeConfig(step=mode, step_chunk=chunk,
                                             packer=packer))
        for i, toks in enumerate(prompts):
            eng.submit(Request(rid=i, tokens=toks, max_new=4,
                               priority=prios[i]), frontend=i % 2)
        eng.wait_packed()
        done = eng.run()
        return eng.admission_log, {r.rid: r.out for r in done}

    ref = run("host")
    assert run("continuous", chunk=1) == ref
    assert run("continuous", chunk=3) == ref
    assert run("continuous", chunk=2, packer="thread") == ref

    # flush_frontends drains planned-but-unfolded submissions (adopt_plan)
    eng = ServeEngine(cfg, params, slots=2, max_len=32, frontends=2, k=1,
                      config=ServeConfig(step="continuous", step_chunk=3,
                                         packer="sync"))
    for i in range(4):
        eng.submit(Request(rid=i, tokens=prompts[i], max_new=3,
                           priority=prios[i]), frontend=i % 2)
    eng.flush_frontends()
    done = eng.run()
    assert sorted(r.rid for r in done) == [0, 1, 2, 3]

    # dropping a threaded engine stops its packer (weakref-finalized)
    eng = ServeEngine(cfg, params, slots=2, max_len=32, frontends=2, k=1,
                      config=ServeConfig(step="continuous", step_chunk=2,
                                         packer="thread"))
    t = eng._packer._thread
    del eng
    gc.collect()
    t.join(10.0)
    assert not t.is_alive()

    # teardown: a full continuous engine session leaves no device buffers
    # (params/prompts held by the test are in the baseline on both sides)
    gc.collect()
    before = len(jax.live_arrays())
    run("continuous", chunk=2)
    gc.collect()
    assert len(jax.live_arrays()) <= before
