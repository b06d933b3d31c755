"""Randomized differential harness for the single-dispatch fused decode step
(ISSUE 4 tentpole contract, DESIGN.md §10):

  * fused-step admission order, popped-pool-slot sequence, decode-slot
    fills, AND token streams are bit-identical to the host
    ``HybridKQueue(spy="min_index")`` oracle and to the eager
    ``admission="device"`` plane on randomized traces — arrival bursts,
    priority ties (incl. f32-quantization collisions), k = 0, empty-pool
    steps — for chunk sizes 1, 3, and whole-trace,
  * step-chunk identity: the chunked scan equals step-by-step execution
    bit-for-bit, events and final carry alike,
  * the ρ/ignored-work bound holds through the fused chunked program for
    EVERY policy (``list(kp.Policy)`` — the enum is the table), and
    chunked == step-by-step for the generic ``queue_phase_chunk`` program,
  * ``stream_pop_fill`` replicates the engine's stop-at-first-miss admit
    loop exactly (single and batched),
  * capacity-full raises like the eager plane; flush-after-chunk-boundary
    (full and per-place) drains exactly (the StreamingAdmitter per-place
    flush fix rides the same contract),
  * engine-level: ``ServeEngine(step="fused")`` == host == device on the
    real reduced model; the 8-device composed-mesh subprocess selftest.
"""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import batched, kpriority as kp
from repro.core.host_queue import HostPodQueues, HybridKQueue, MultiQueue
from repro.serve.config import ServeConfig
from repro.serve.fused_step import TOY_VOCAB, toy_loop
from repro.serve.streaming import StreamingAdmitter

# priorities drawn from this grid: repeated values + f64-distinct pairs that
# collide after f32 quantization, so the (priority, uid) tie-break carries
# real weight on every plane (quantized at the harness boundary, as
# ServeEngine.submit does)
PRIO_GRID = [0.0, 0.5, 1.0, 1.5, 0.1, 0.1 + 1e-12, 7.5, 7.5 + 1e-12]


def _prompt(uid, plen):
    return ((np.arange(plen) + uid) % 11).astype(np.int32)


def _tok0(uid, plen):
    return int((_prompt(uid, plen).sum() * 3 + plen) % TOY_VOCAB)


def gen_trace(seed, steps, frontends, *, lead_empty=2, burst_max=4):
    """Per-step arrival bursts: (place, f32-quantized prio, uid, max_new,
    plen). The first ``lead_empty`` steps are arrival-free (empty-pool
    steps); later steps may draw empty bursts too."""
    rng = np.random.default_rng(seed)
    trace, uid = [], 0
    for t in range(steps):
        burst = []
        if t >= lead_empty:
            for _ in range(int(rng.integers(0, burst_max + 1))):
                pr = float(np.float32(PRIO_GRID[rng.integers(len(PRIO_GRID))]))
                burst.append((int(rng.integers(frontends)), pr, uid,
                              int(rng.integers(1, 5)),
                              int(rng.integers(1, 4))))
                uid += 1
        trace.append(burst)
    return trace


class OracleEngine:
    """The eager ServeEngine.step state machine over a queue-like admission
    plane, with the toy decode simulated host-side: the python-level truth
    the fused program must reproduce event-for-event."""

    def __init__(self, queue, *, slots, frontends, max_len, fold=False):
        self.q = queue
        self.slots, self.frontends, self.max_len = slots, frontends, max_len
        self.do_fold = fold
        self.active = [None] * slots
        self.meta = {}
        self.clock = 0
        self.admission, self.fills, self.tokens = [], [], {}
        self.pop_slots = []      # popped pool slots (device planes only)

    def push(self, place, prio, uid, max_new, plen):
        self.meta[uid] = (max_new, plen)
        self.q.push(place, prio, uid)

    def _pop(self, place):
        if not isinstance(self.q, StreamingAdmitter):
            return self.q.pop(place)
        before = set(self.q._items)
        got = self.q.pop(place)
        if got is not None:
            self.pop_slots.append((before - set(self.q._items)).pop())
        return got

    def step(self):
        self.clock += 1
        if self.do_fold:
            self.q.fold()
        for s in range(self.slots):
            if self.active[s] is not None:
                continue
            got = self._pop(s % self.frontends)
            if got is None:
                break
            uid = got[1]
            self.admission.append(uid)
            self.fills.append((self.clock, s, uid))
            max_new, plen = self.meta[uid]
            t0 = _tok0(uid, plen)
            self.tokens[uid] = [t0]
            self.active[s] = {"uid": uid, "cur": t0, "pos": plen,
                              "out": 1, "max_new": max_new}
        for s in range(self.slots):
            a = self.active[s]
            if a is None:
                continue
            tok = (a["cur"] * 7 + a["pos"]) % TOY_VOCAB
            self.tokens[a["uid"]].append(tok)
            a["pos"] += 1
            a["cur"] = tok
            a["out"] += 1
            if a["out"] >= a["max_new"] or a["pos"] >= self.max_len - 1:
                self.active[s] = None

    def flush(self, place=None):
        if isinstance(self.q, HybridKQueue):
            for p in ([place] if place is not None
                      else range(self.frontends)):
                self.q.flush(p)
        else:
            self.q.flush(place)

    def results(self):
        return self.admission, self.fills, self.tokens


def drive_oracle(trace, *, slots, frontends, k, max_len, plane,
                 capacity=128):
    if plane == "host":
        q, fold = HybridKQueue(frontends, k, spy="min_index"), False
    else:
        q, fold = StreamingAdmitter(frontends, k, capacity=capacity), True
    eng = OracleEngine(q, slots=slots, frontends=frontends, max_len=max_len,
                       fold=fold)
    for burst in trace:
        for (place, pr, uid, max_new, plen) in burst:
            eng.push(place, pr, uid, max_new, plen)
        eng.step()
    return eng


def drive_fused(trace, *, slots, frontends, k, max_len, chunk, capacity=128,
                policy="hybrid"):
    loop = toy_loop(slots=slots, frontends=frontends, k=k, max_len=max_len,
                    capacity=capacity, policy=policy)
    for step, burst in enumerate(trace, start=1):
        for (place, pr, uid, max_new, plen) in burst:
            loop.submit(place, pr, uid, _prompt(uid, plen), max_new,
                        at_step=step)
    admission, fills, tokens, pop_slots = [], [], {}, []
    records = []
    t = 0
    while t < len(trace):
        n = min(chunk, len(trace) - t)
        recs = loop.run_steps(n)
        records.extend(recs)
        for i, rec in enumerate(recs):
            for (s, uid, tok0, ps) in rec.admitted:
                admission.append(uid)
                fills.append((t + i + 1, s, uid))
                pop_slots.append(ps)
                tokens[uid] = [tok0]
            for (_s, uid, tok) in rec.tokens:
                tokens[uid].append(tok)
        t += n
    return admission, fills, tokens, pop_slots, records, loop


# ---------------------------------------------------------------------------
# the differential harness
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("frontends,slots,k", [(2, 4, 3), (3, 5, 1), (2, 3, 0)])
@settings(max_examples=4, deadline=None)
@given(seed=st.integers(0, 10_000))
def test_fused_matches_host_and_device_oracles(frontends, slots, k, seed):
    """Admission order, fills, token streams == host oracle == eager device
    plane; popped pool slots == eager device plane; for chunk 1 and 3.
    Covers k = 0 (fully centralized), empty-pool steps, priority ties."""
    max_len = 64
    trace = gen_trace(seed, 18, frontends)
    host = drive_oracle(trace, slots=slots, frontends=frontends, k=k,
                        max_len=max_len, plane="host")
    dev = drive_oracle(trace, slots=slots, frontends=frontends, k=k,
                       max_len=max_len, plane="device")
    assert host.results() == dev.results()
    for chunk in (1, 3):
        adm, fills, toks, pop_slots, _, _ = drive_fused(
            trace, slots=slots, frontends=frontends, k=k, max_len=max_len,
            chunk=chunk)
        assert (adm, fills, toks) == host.results(), f"chunk={chunk}"
        assert pop_slots == dev.pop_slots, f"chunk={chunk}"


def test_fused_chunk_identity():
    """Step-chunk identity: whole-trace chunk == chunk 1, events AND final
    carry bit-for-bit (the fused analogue of the §8 phase_chunk pin)."""
    trace = gen_trace(5, 16, 2)
    outs = {}
    for chunk in (1, 16):
        adm, fills, toks, pops, records, loop = drive_fused(
            trace, slots=4, frontends=2, k=2, max_len=64, chunk=chunk)
        outs[chunk] = (adm, fills, toks, pops, records)
        if chunk == 1:
            ref_carry = loop.carry
        else:
            for name, a, b in zip(loop.carry._fields, ref_carry, loop.carry):
                for la, lb in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
                    np.testing.assert_array_equal(
                        np.asarray(la), np.asarray(lb), err_msg=name)
    assert outs[1] == outs[16]


# ---------------------------------------------------------------------------
# fuzz soaks (slow marker: deselected by make test-fast; the nightly CI job
# raises the seed budget via SOAK_SEEDS and uploads tests/out/ on failure)
# ---------------------------------------------------------------------------

def _soak_seeds(default: int):
    """Seed budget for the slow fuzz soaks: ``SOAK_SEEDS`` many consecutive
    seeds from ``SOAK_SEED_BASE`` (the nightly CI job raises the budget and
    rotates the base by run number; a failure's repro seed is dumped to
    tests/out/soak_repro.json and uploaded as an artifact)."""
    n = int(os.environ.get("SOAK_SEEDS", str(default)))
    base = int(os.environ.get("SOAK_SEED_BASE", "0"))
    return range(base, base + n)


def _dump_soak_repro(test: str, seed: int, err: Exception):
    out = os.path.join(os.path.dirname(__file__), "out")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, "soak_repro.json"), "w") as f:
        json.dump({"test": test, "seed": seed,
                   "repro": f"SOAK_SEEDS=1 SOAK_SEED_BASE={seed} pytest "
                            f"-m slow tests/test_fused_step.py -k {test}",
                   "error": f"{type(err).__name__}: {err}"[:2000]}, f,
                  indent=1)


@pytest.mark.slow
def test_fused_fuzz_soak():
    """Long-trace fuzz soak — same triple-differential as above at 60 steps
    and denser bursts, over the SOAK_SEEDS budget."""
    frontends, slots, k, max_len = 3, 6, 2, 48
    for seed in _soak_seeds(8):
        try:
            trace = gen_trace(seed, 60, frontends, burst_max=5)
            host = drive_oracle(trace, slots=slots, frontends=frontends,
                                k=k, max_len=max_len, plane="host")
            dev = drive_oracle(trace, slots=slots, frontends=frontends, k=k,
                               max_len=max_len, plane="device", capacity=512)
            adm, fills, toks, pops, _, _ = drive_fused(
                trace, slots=slots, frontends=frontends, k=k,
                max_len=max_len, chunk=8, capacity=512)
            assert (adm, fills, toks) == host.results()
            assert (adm, fills, toks) == dev.results()
            assert pops == dev.pop_slots
        except Exception as e:
            _dump_soak_repro("test_fused_fuzz_soak", seed, e)
            raise AssertionError(f"fused soak failed at seed={seed}") from e


# ---------------------------------------------------------------------------
# stream_pop_fill: the traced admit loop
# ---------------------------------------------------------------------------

def _fill_oracle(state, want, places):
    """Python replay of the engine's admit loop over single stream_pops."""
    slots, prios, valids = [], [], []
    stopped = False
    for w, pl in zip(want, places):
        if w and not stopped:
            state, slot, prio, valid = kp.stream_pop(state, jnp.int32(pl))
            if not bool(valid):
                stopped = True
            slots.append(int(slot) if bool(valid) else 0)
            valids.append(bool(valid))
        else:
            slots.append(0)
            valids.append(False)
    return state, slots, valids


@pytest.mark.parametrize("want_pattern", ["all", "holes", "none"])
def test_stream_pop_fill_matches_loop(want_pattern):
    m, places, s = 32, 2, 5
    rng = np.random.default_rng(4)
    st_ = kp.init_pool(m, places)
    mask = jnp.asarray(rng.random(m) < 0.25)
    st_ = kp.push_batch(st_, mask,
                        jnp.asarray(rng.random(m).astype(np.float32)),
                        jnp.asarray(rng.integers(0, places, m), jnp.int32))
    st_ = kp.publish(st_, k=1)
    want = {"all": [True] * s, "holes": [True, False, True, True, False],
            "none": [False] * s}[want_pattern]
    pl = [i % places for i in range(s)]
    ref_state, ref_slots, ref_valids = _fill_oracle(st_, want, pl)
    new_state, res = kp.stream_pop_fill(
        st_, jnp.asarray(want), jnp.asarray(pl, jnp.int32))
    assert [bool(v) for v in res.valid] == ref_valids
    got = [int(x) for x, v in zip(res.slot, res.valid) if bool(v)]
    ref = [x for x, v in zip(ref_slots, ref_valids) if v]
    assert got == ref
    for name, la, lb in zip(kp.PoolState._fields, new_state, ref_state):
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb),
                                      err_msg=name)


def test_stream_pop_fill_stops_at_first_miss():
    """An empty pool with several wanted slots: no pops, and the pool is
    untouched (the eager loop's early return)."""
    st_ = kp.init_pool(16, 2)
    new_state, res = kp.stream_pop_fill(
        st_, jnp.ones((4,), bool), jnp.asarray([0, 1, 0, 1], jnp.int32))
    assert not bool(res.valid.any())
    for la, lb in zip(new_state, st_):
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))


def test_batched_stream_pop_fill_matches_loop():
    b, m, places, s = 3, 24, 2, 4
    rng = np.random.default_rng(9)
    bstate = batched.init_pool(m, places, batch=b)
    mask = jnp.asarray(rng.random((b, m)) < 0.3)
    prios = jnp.asarray(rng.random((b, m)).astype(np.float32))
    creators = jnp.asarray(rng.integers(0, places, (b, m)), jnp.int32)
    bstate = batched.publish(
        batched.push_batch(bstate, mask, prios, creators), k=1)
    want = jnp.asarray(rng.random((b, s)) < 0.8)
    pl = jnp.asarray(rng.integers(0, places, (b, s)), jnp.int32)
    bnew, bres = batched.stream_pop_fill(bstate, want, pl)
    for i in range(b):
        single = jax.tree.map(lambda x: x[i], bstate)
        snew, sres = kp.stream_pop_fill(single, want[i], pl[i])
        for name, la, lb in zip(kp.PoolState._fields, bnew, snew):
            np.testing.assert_array_equal(
                np.asarray(la[i]), np.asarray(lb), err_msg=f"{name} b={i}")
        for name, la, lb in zip(kp.PopResult._fields, bres, sres):
            np.testing.assert_array_equal(
                np.asarray(la[i]), np.asarray(lb), err_msg=f"{name} b={i}")


# ---------------------------------------------------------------------------
# invariants: ρ bound + chunk identity for the generic fused queue program
# ---------------------------------------------------------------------------

# ONE table for the policy-generic differentials: the enum itself, so a new
# Policy member is parametrized into the chunk identity / ρ harness for free
ALL_POLICIES = list(kp.Policy)


def _chunk_inputs(seed, t, m, places):
    rng = np.random.default_rng(seed)
    masks = np.zeros((t, m), bool)
    used = set()
    for i in range(t):
        for _ in range(int(rng.integers(0, 6))):
            slot = int(rng.integers(m))
            if slot not in used:
                used.add(slot)
                masks[i, slot] = True
    prios = rng.random((t, m)).astype(np.float32)
    creators = rng.integers(0, places, (t, m)).astype(np.int32)
    push_keys = jax.random.split(jax.random.PRNGKey(seed), t)
    pop_keys = jax.random.split(jax.random.PRNGKey(seed + 1), t)
    return (jnp.asarray(masks), jnp.asarray(prios), jnp.asarray(creators),
            push_keys, pop_keys)


@pytest.mark.parametrize("policy", ALL_POLICIES)
def test_queue_phase_chunk_rho_bound(policy):
    """ignored ≤ rho at EVERY step of the fused chunked program, every
    policy (the in-trace ignored counter of queue_phase_chunk)."""
    t, m, places, k = 10, 48, 4, 3
    state = kp.init_pool(m, places)
    xs = _chunk_inputs(3, t, m, places)
    state, results, ignored = jax.jit(
        lambda s, *a: kp.queue_phase_chunk(
            s, *a, num_places=places, k=k, policy=policy)
    )(state, *xs)
    rho = kp.rho_bound(policy, k, places)
    assert int(jnp.max(ignored)) <= rho or rho == float("inf")
    if policy not in (kp.Policy.WORK_STEALING, kp.Policy.MULTIQUEUE):
        assert float(rho) < float("inf")
        np.testing.assert_array_less(np.asarray(ignored), rho + 1)


@pytest.mark.parametrize("policy", ALL_POLICIES)
def test_queue_phase_chunk_identity(policy):
    """Chunked scan == step-by-step push/phase_pop, bit-for-bit: state,
    per-step results, AND per-step ignored counts, for every policy."""
    t, m, places, k = 8, 40, 3, 2
    xs = _chunk_inputs(7, t, m, places)
    st_c = kp.init_pool(m, places)
    st_c, res_c, ign_c = kp.queue_phase_chunk(
        st_c, *xs, num_places=places, k=k, policy=policy)
    st_s = kp.init_pool(m, places)
    masks, prios, creators, push_keys, pop_keys = xs
    for i in range(t):
        st_s = kp.push(st_s, masks[i], prios[i], creators[i], k=k,
                       policy=policy, key=push_keys[i])
        before = st_s
        st_s, res = kp.phase_pop(st_s, pop_keys[i], num_places=places, k=k,
                                 policy=policy)
        for name, lc, ls in zip(kp.PopResult._fields, res_c, res):
            np.testing.assert_array_equal(
                np.asarray(lc[i]), np.asarray(ls), err_msg=f"{name} step {i}")
        assert int(ign_c[i]) == int(kp.ignored_count(before, res)), i
    for name, lc, ls in zip(kp.PoolState._fields, st_c, st_s):
        np.testing.assert_array_equal(np.asarray(lc), np.asarray(ls),
                                      err_msg=name)


def test_fused_admission_rho_bound():
    """The fused serving path inherits ρ = frontends·k: a popped request is
    worse than at most ρ live better requests (live = submitted, foldable by
    the pop's step, not yet admitted)."""
    frontends, slots, k, max_len = 3, 4, 2, 64
    trace = gen_trace(21, 30, frontends, burst_max=5)
    arrivals = {}
    for step, burst in enumerate(trace, start=1):
        for (place, pr, uid, max_new, plen) in burst:
            arrivals[uid] = (step, pr)
    adm, fills, _, _, _, _ = drive_fused(
        trace, slots=slots, frontends=frontends, k=k, max_len=max_len,
        chunk=5)
    admitted_before = set()
    worst = 0
    for (step, _s, uid) in fills:
        _, my_pr = arrivals[uid]
        better = sum(
            1 for u, (st_, pr) in arrivals.items()
            if u != uid and u not in admitted_before and st_ <= step
            and pr < my_pr)
        worst = max(worst, better)
        admitted_before.add(uid)
    assert worst <= frontends * k, worst


# ---------------------------------------------------------------------------
# capacity, flush-after-chunk-boundary, per-place flush
# ---------------------------------------------------------------------------

def test_fused_capacity_full_raises():
    loop = toy_loop(slots=2, frontends=2, k=2, capacity=3)
    for i in range(3):
        loop.submit(0, float(i), i, _prompt(i, 2), 2)
    with pytest.raises(RuntimeError, match="admission pool full"):
        loop.submit(0, 9.0, 9, _prompt(9, 2), 2)
    # admitting frees pool slots: after a step the 4th submit fits
    loop.run_steps(1)
    loop.submit(1, 9.0, 9, _prompt(9, 2), 2)
    assert len(loop) >= 1


@pytest.mark.parametrize("place", [None, 0])
def test_fused_flush_after_chunk_boundary(place):
    """Regression (ISSUE 4 satellite): flush at a chunk boundary — buffers
    partially drained mid-stream, arrivals still scheduled for future steps
    — must drain exactly: fused admission order equals the host oracle that
    received the same pushes before its flush."""
    frontends, slots, k, max_len = 2, 2, 4, 64
    loop = toy_loop(slots=slots, frontends=frontends, k=k, max_len=max_len)
    host = OracleEngine(HybridKQueue(frontends, k, spy="min_index"),
                        slots=slots, frontends=frontends, max_len=max_len)
    burst_a = [(i % frontends, float(i % 3), i, 2, 2) for i in range(5)]
    burst_b = [(i % frontends, float((i + 1) % 3), i, 3, 1)
               for i in range(5, 9)]
    for (pl, pr, uid, mn, plen) in burst_a:
        loop.submit(pl, pr, uid, _prompt(uid, plen), mn, at_step=1)
        host.push(pl, pr, uid, mn, plen)
    recs = loop.run_steps(2)                  # partial drain: mid-stream
    host.step()
    host.step()
    # burst B lands beyond the executed steps, then the flush publishes it
    for (pl, pr, uid, mn, plen) in burst_b:
        loop.submit(pl, pr, uid, _prompt(uid, plen), mn, at_step=6)
        host.push(pl, pr, uid, mn, plen)
    loop.flush(place)
    host.flush(place)
    recs += loop.run_steps(6)
    for _ in range(6):
        host.step()
    adm = [uid for rec in recs for (_s, uid, _t, _p) in rec.admitted]
    assert adm == host.admission, (adm, host.admission)
    assert loop.idle and not any(host.active)


def test_streaming_per_place_flush_matches_host():
    """StreamingAdmitter.flush(place) is now the exact per-place
    HybridKQueue.flush(p): randomized trace with per-place flushes mixed in
    agrees pop-for-pop (regression for the old loud-raise behaviour)."""
    places, k = 3, 4
    rng = np.random.default_rng(13)
    dev = StreamingAdmitter(places, k, capacity=128, buffer_cap=32)
    host = HybridKQueue(places, k, spy="min_index")
    uid = 0
    for _ in range(40):
        for _ in range(int(rng.integers(0, 5))):
            p = int(rng.integers(places))
            pr = float(rng.integers(0, 6)) / 2.0
            dev.push(p, pr, uid)
            host.push(p, pr, uid)
            uid += 1
        dev.fold()
        if rng.random() < 0.3:
            p = int(rng.integers(places))
            dev.flush(p)
            host.flush(p)
        for _ in range(int(rng.integers(0, 4))):
            p = int(rng.integers(places))
            a, b = dev.pop(p), host.pop(p)
            assert (a is None) == (b is None), (uid, a, b)
            if a is not None:
                assert a == b, (uid, a, b)
        for p in range(places):
            assert dev.pending(p) == host.pending(p), (p, uid)
    dev.flush()
    for p in range(places):
        host.flush(p)
    drained = 0
    p = 0
    while len(host) or len(dev):
        a, b = dev.pop(p % places), host.pop(p % places)
        p += 1
        assert (a is None) == (b is None), (a, b)
        if a is not None:
            assert a == b, (a, b)
            drained += 1
    assert drained > 0


# ---------------------------------------------------------------------------
# dispatch-count contract + engine level + composed mesh
# ---------------------------------------------------------------------------

def test_fused_dispatch_count_below_eager():
    """The point of the fusion: one dispatch per chunk vs the eager device
    plane's fold + per-slot pops every step (submission-path dispatches are
    identical by construction, so total counts compare fairly)."""
    frontends, slots, k, max_len = 2, 4, 2, 64
    trace = gen_trace(2, 16, frontends)
    dev = drive_oracle(trace, slots=slots, frontends=frontends, k=k,
                       max_len=max_len, plane="device")
    *_, loop = drive_fused(trace, slots=slots, frontends=frontends, k=k,
                           max_len=max_len, chunk=8)
    n_req = sum(len(b) for b in trace)
    # eager: ≥ 1 fold + ≥ 1 pop per step, + 1 buffer push per request
    eager_step_dispatches = dev.q.dispatches - n_req
    fused_step_dispatches = loop.dispatches - 2 * n_req   # prefill + staging
    assert fused_step_dispatches == 2                     # 16 steps, chunk 8
    assert fused_step_dispatches < eager_step_dispatches


def test_engine_fused_matches_host_and_device():
    """ServeEngine(step="fused") on the real reduced model: admission order
    and token streams identical to both eager oracles, for chunk 1 and 3."""
    from repro.configs import get_reduced
    from repro.models import materialize, model_p
    from repro.serve.engine import Request, ServeEngine

    cfg = get_reduced("qwen3_1_7b")
    params = materialize(jax.random.PRNGKey(0), model_p(cfg))
    rng = np.random.default_rng(2)
    prompts = [rng.integers(0, cfg.vocab_size, 6).astype(np.int32)
               for _ in range(8)]
    prios = [float(v) for v in rng.permutation(8)]

    def run(mode, chunk=1):
        eng = ServeEngine(cfg, params, slots=3, max_len=32, frontends=2, k=2,
                          config=ServeConfig(step=mode, step_chunk=chunk))
        for i, toks in enumerate(prompts):
            eng.submit(Request(rid=i, tokens=toks, max_new=4,
                               priority=prios[i]), frontend=i % 2)
        done = eng.run()
        return eng.admission_log, {r.rid: r.out for r in done}

    ref = run("host")
    assert run("device") == ref
    assert run("fused", chunk=1) == ref
    assert run("fused", chunk=3) == ref


def test_engine_fused_caches_stay_live():
    """Regression: the fused carry's buffers are donated every chunk, so
    ``engine.caches`` must read the LIVE carry — not alias deleted arrays."""
    from repro.configs import get_reduced
    from repro.models import materialize, model_p
    from repro.serve.engine import Request, ServeEngine

    cfg = get_reduced("qwen3_1_7b")
    params = materialize(jax.random.PRNGKey(0), model_p(cfg))
    eng = ServeEngine(cfg, params, slots=2, max_len=24, frontends=2, k=1,
                      config=ServeConfig(step="fused", step_chunk=2))
    eng.submit(Request(rid=0, tokens=np.arange(4, dtype=np.int32),
                       max_new=3, priority=0.0), frontend=0)
    eng.run()
    leaves = jax.tree.leaves(eng.caches)
    assert leaves and np.asarray(leaves[0]) is not None


# ---------------------------------------------------------------------------
# splice_in: one in-place write per admitted slot
# ---------------------------------------------------------------------------

def _splice_gather_where(caches, staged_caches, rows, mask):
    """The masked-gather splice ``splice_in`` replaced, kept as its oracle:
    gather every slot's staged row, select it over the decode caches."""
    def one(full, stage):
        g = jnp.take(stage, rows, axis=1)
        m = mask.reshape((1, -1) + (1,) * (full.ndim - 2))
        return jnp.where(m, g.astype(full.dtype), full)

    return jax.tree.map(one, caches, staged_caches)


def _bits(tree):
    return [np.asarray(x).tobytes() for x in jax.tree.leaves(tree)]


SPLICE_MASKS = {
    "none": [False] * 6,
    "all": [True] * 6,
    "alternate": [True, False] * 3,
    "random": list(np.random.default_rng(5).random(6) < 0.5),
}


@pytest.mark.parametrize("mask", sorted(SPLICE_MASKS))
@pytest.mark.parametrize("seed", [0, 1])
def test_splice_in_matches_masked_gather(mask, seed):
    """``splice_in`` leaves caches bit-identical to the masked gather, with
    staged leaves wider than the decode ones (the cast) and rows repeated,
    masked off or not."""
    from repro.serve.fused_step import splice_in

    slots, rows_n = 6, 5
    rng = np.random.default_rng(seed)

    def arr(shape, dtype):
        return jnp.asarray(rng.standard_normal(shape) * 3.7, dtype)

    caches = {"k": arr((3, slots, 2, 7), jnp.bfloat16),
              "v": (arr((3, slots, 4), jnp.float32),)}
    staged = {"k": arr((3, rows_n, 2, 7), jnp.float32),
              "v": (arr((3, rows_n, 4), jnp.float32),)}
    rows = jnp.asarray(rng.integers(0, rows_n, slots), jnp.int32)
    rows = rows.at[1].set(rows[0])                  # a repeated row
    m = jnp.asarray(SPLICE_MASKS[mask])
    want = jax.jit(_splice_gather_where)(caches, staged, rows, m)
    got = jax.jit(splice_in)(caches, staged, rows, m)
    assert _bits(got) == _bits(want)
    if mask == "none":
        assert _bits(got) == _bits(caches)


def _eqns(jaxpr, in_scope=False, scope="splice_in"):
    """Every equation of ``jaxpr`` and its sub-jaxprs, with whether it was
    traced under the named scope ``scope`` (inherited by a loop's body)."""
    for eqn in jaxpr.eqns:
        inside = in_scope or scope in str(eqn.source_info.name_stack).split(
            "/")
        yield eqn, inside
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _eqns(sub, inside, scope)


@pytest.mark.parametrize("preemption", ["off", "margin"])
def test_splice_moves_no_whole_cache(preemption):
    """Structure guard: nothing traced under ``splice_in`` gathers or selects
    an array of a whole decode-cache leaf's shape — only the in-place
    update (and the loop that carries it) has that shape."""
    from repro.serve.fused_step import (FusedServeLoop, _build_chunk_impl,
                                        toy_decode_fn, toy_prefill_fn)

    slots = 3
    leaf = (2, slots, 3, 4)
    loop = FusedServeLoop(
        slots=slots, frontends=2, k=1, max_len=64, capacity=16,
        caches={"kv": jnp.zeros(leaf, jnp.bfloat16)},
        decode_fn=toy_decode_fn, prefill_fn=toy_prefill_fn,
        preemption=preemption, margin=0.5, continuous=True)
    fn = _build_chunk_impl(
        loop.decode_fn, k=loop.k, frontends=loop.frontends, slots=slots,
        max_len=loop.max_len, n=2, preempt=preemption == "margin",
        margin=loop.margin, rounds=loop.rounds, continuous=True)
    bufs, _ = loop._pack_bufs(2)
    jaxpr = jax.make_jaxpr(fn)(loop.params, loop.carry, bufs).jaxpr
    spliced = [(e.primitive.name, tuple(v.aval.shape))
               for e, inside in _eqns(jaxpr) if inside for v in e.outvars]
    assert spliced, "no equation traced under splice_in"
    whole = {p for p, shape in spliced if shape == leaf}
    assert whole == {"dynamic_update_slice", "while"}, whole


@pytest.mark.parametrize("step,preemption", [
    ("fused", "off"), ("continuous", "off"), ("fused", "margin")])
def test_engine_splices_count_admissions(step, preemption):
    """``ServeEngine.splices`` counts one splice per admission from the
    queue's fill; a preempt round's challenger is seated without one."""
    from repro.configs import get_reduced
    from repro.models import materialize, model_p
    from repro.serve.engine import Request, ServeEngine

    cfg = get_reduced("qwen3_1_7b")
    params = materialize(jax.random.PRNGKey(0), model_p(cfg))
    rng = np.random.default_rng(4)
    eng = ServeEngine(cfg, params, slots=2, max_len=48, frontends=2, k=1,
                      config=ServeConfig(step=step, step_chunk=2,
                                         preemption=preemption,
                                         preempt_margin=0.5))
    for rid in range(2):                      # long, low priority
        eng.submit(Request(rid=rid, tokens=rng.integers(
            0, cfg.vocab_size, 5).astype(np.int32), max_new=7,
            priority=9.0), frontend=rid % 2)
    eng.wait_packed()
    eng.step()
    eng.step()
    for rid in range(2, 5):                   # short, high priority
        eng.submit(Request(rid=rid, tokens=rng.integers(
            0, cfg.vocab_size, 4).astype(np.int32), max_new=3,
            priority=float(rid)), frontend=rid % 2)
    eng.wait_packed()
    assert len(eng.run()) == 5
    assert len(eng.admission_log) >= 5
    if preemption == "off":
        assert eng.preempt_log == []
    else:
        assert eng.preempt_log, "no preemption fired; strengthen the trace"
    assert eng.splices == len(eng.admission_log) - len(eng.preempt_log)


def test_fused_selftest_8_devices():
    """Acceptance pin: fused step == host oracle == eager device plane under
    the 8-device composed (batch × data × model) production-style mesh —
    toy differential (preemptive AND non-preemptive) plus the real-model
    engine, via subprocess."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    out = subprocess.run(
        [sys.executable, "-m", "repro.serve.fused_step", "--selftest"],
        capture_output=True, text=True, env=env, timeout=600,
    )
    assert "FUSED_OK devices=8" in out.stdout, (
        out.stdout[-500:], out.stderr[-2000:])
    assert "FUSED_TRACE_OK mesh" in out.stdout, out.stdout[-500:]
    assert "PREEMPT_TRACE_OK mesh" in out.stdout, out.stdout[-500:]
    assert "FUSED_ENGINE_OK" in out.stdout, out.stdout[-500:]


# ---------------------------------------------------------------------------
# §11 preemption: the three-plane differential harness
# ---------------------------------------------------------------------------

class PreemptOracle:
    """The eager preemptive ``ServeEngine.step`` state machine (fold →
    admission fill → preemption rounds → decode → completion) over a host
    ``HybridKQueue`` or a retain-mode ``StreamingAdmitter``, with the toy
    decode simulated host-side — the python truth the fused preemptive
    plane must reproduce event-for-event (DESIGN.md §11)."""

    def __init__(self, plane, *, slots, frontends, k, max_len, margin,
                 capacity=128):
        self.is_dev = plane == "device"
        if self.is_dev:
            self.q = StreamingAdmitter(frontends, k, capacity=capacity,
                                       retain=True)
        else:
            self.q = HybridKQueue(frontends, k, spy="min_index")
        self.slots, self.frontends, self.max_len = slots, frontends, max_len
        self.margin = margin
        self.active = [None] * slots
        self.meta, self.stash = {}, {}
        self.seq = 0                 # queue-uid mirror (latest push order)
        self.uid_seq, self.slot_of = {}, {}
        self.clock = 0
        self.admission, self.fills, self.evictions = [], [], []
        self.tokens, self.pop_slots = {}, []

    def push(self, place, pr, uid, max_new, plen):
        self.meta[uid] = (max_new, plen, place)
        self.seq += 1
        self.uid_seq[uid] = self.seq
        self.q.push(place, pr, uid)

    def _pop(self, place):
        if not self.is_dev:
            return self.q.pop(place)
        got = self.q.pop_ex(place)
        if got is None:
            return None
        pr, uid, slot = got
        self.slot_of[uid] = slot
        return pr, uid

    def _seat(self, s, got):
        pr, uid = got
        self.admission.append(uid)
        self.fills.append((self.clock, s, uid))
        if self.is_dev:
            self.pop_slots.append(self.slot_of[uid])
        if uid in self.stash:
            self.active[s] = self.stash.pop(uid)
        else:
            mn, plen, place = self.meta[uid]
            t0 = _tok0(uid, plen)
            self.tokens[uid] = [t0]
            self.active[s] = {"uid": uid, "pr": pr, "cur": t0, "pos": plen,
                              "out": 1, "max_new": mn, "place": place}

    def step(self):
        self.clock += 1
        if self.is_dev:
            self.q.fold()
        filled = set()
        for s in range(self.slots):
            if self.active[s] is not None:
                continue
            got = self._pop(s % self.frontends)
            if got is None:
                break
            self._seat(s, got)
            filled.add(s)
        for _ in range(self.slots):
            elig = [s for s in range(self.slots)
                    if self.active[s] is not None and s not in filled]
            if not elig:
                break
            v = max(elig, key=lambda s: (self.active[s]["pr"],
                                         self.uid_seq[self.active[s]["uid"]]))
            top = self.q.peek(v % self.frontends)
            if top is None or not kp.preempt_beats(
                    top, self.margin, self.active[v]["pr"]):
                break
            vic = self.active[v]
            self.evictions.append((self.clock, v, vic["uid"]))
            self.stash[vic["uid"]] = vic
            self.active[v] = None
            self.seq += 1
            self.uid_seq[vic["uid"]] = self.seq
            if self.is_dev:
                self.q.repush(self.slot_of[vic["uid"]], vic["place"],
                              vic["pr"])
            else:
                self.q.push(vic["place"], vic["pr"], vic["uid"])
            got = self._pop(v % self.frontends)
            assert got is not None
            self._seat(v, got)
            filled.add(v)
        for s in range(self.slots):
            a = self.active[s]
            if a is None:
                continue
            tok = (a["cur"] * 7 + a["pos"]) % TOY_VOCAB
            self.tokens[a["uid"]].append(tok)
            a["pos"] += 1
            a["cur"] = tok
            a["out"] += 1
            if a["out"] >= a["max_new"] or a["pos"] >= self.max_len - 1:
                if self.is_dev:
                    self.q.release(self.slot_of[a["uid"]])
                self.active[s] = None

    def results(self):
        return self.admission, self.fills, self.evictions, self.tokens


def drive_preempt_oracle(trace, plane, *, slots, frontends, k, max_len,
                         margin, capacity=128):
    eng = PreemptOracle(plane, slots=slots, frontends=frontends, k=k,
                        max_len=max_len, margin=margin, capacity=capacity)
    for burst in trace:
        for (place, pr, uid, max_new, plen) in burst:
            eng.push(place, pr, uid, max_new, plen)
        eng.step()
    return eng


def drive_fused_preempt(trace, *, slots, frontends, k, max_len, chunk,
                        margin, capacity=128, staging_rows=None):
    loop = toy_loop(slots=slots, frontends=frontends, k=k, max_len=max_len,
                    capacity=capacity, preemption="margin", margin=margin,
                    staging_rows=staging_rows)
    for step, burst in enumerate(trace, start=1):
        for (place, pr, uid, max_new, plen) in burst:
            loop.submit(place, pr, uid, _prompt(uid, plen), max_new,
                        at_step=step)
    admission, fills, evictions, tokens, pop_slots = [], [], [], {}, []
    t = 0
    while t < len(trace):
        n = min(chunk, len(trace) - t)
        for i, rec in enumerate(loop.run_steps(n)):
            step = t + i + 1
            for (s, uid, _ps) in rec.preempted:
                evictions.append((step, s, uid))
            for (s, uid, tok0, ps) in rec.order:
                admission.append(uid)
                fills.append((step, s, uid))
                pop_slots.append(ps)
                if tok0 is not None:
                    tokens[uid] = [tok0]
            for (_s, uid, tok) in rec.tokens:
                tokens[uid].append(tok)
        t += n
    return admission, fills, evictions, tokens, pop_slots, loop


def gen_preempt_trace(seed, steps, frontends, *, burst_max=3, long_max=9):
    """Inversion-heavy arrival bursts: longer token budgets (so victims are
    mid-flight when better requests land) and priorities from the collision
    grid (victim AND challenger ties carry weight)."""
    rng = np.random.default_rng(seed)
    trace, uid = [], 0
    for _ in range(steps):
        burst = []
        for _ in range(int(rng.integers(0, burst_max + 1))):
            pr = float(np.float32(PRIO_GRID[rng.integers(len(PRIO_GRID))]))
            burst.append((int(rng.integers(frontends)), pr, uid,
                          int(rng.integers(2, long_max)),
                          int(rng.integers(1, 4))))
            uid += 1
        trace.append(burst)
    return trace


@pytest.mark.parametrize("frontends,slots,k,margin", [
    (2, 3, 2, 0.0), (3, 4, 1, 0.5), (2, 2, 0, 0.0)])
@settings(max_examples=3, deadline=None)
@given(seed=st.integers(0, 10_000))
def test_preempt_matches_host_and_device_oracles(frontends, slots, k, margin,
                                                 seed):
    """The ISSUE 5 acceptance core: the fused preemptive plane is
    bit-identical to the host HybridKQueue preemption oracle AND the eager
    retain-mode device plane — admission order, fills, victim choice
    (eviction events), token streams (resume-exactly semantics), and the
    popped-pool-slot sequence — for chunk 1 and 4, incl. k = 0 and
    margin = 0 tie edges."""
    max_len = 64
    trace = gen_preempt_trace(seed, 20, frontends)
    host = drive_preempt_oracle(trace, "host", slots=slots,
                                frontends=frontends, k=k, max_len=max_len,
                                margin=margin)
    dev = drive_preempt_oracle(trace, "device", slots=slots,
                               frontends=frontends, k=k, max_len=max_len,
                               margin=margin)
    assert host.results() == dev.results()
    for chunk in (1, 4):
        adm, fills, ev, toks, pops, _ = drive_fused_preempt(
            trace, slots=slots, frontends=frontends, k=k, max_len=max_len,
            chunk=chunk, margin=margin)
        assert (adm, fills, ev, toks) == host.results(), f"chunk={chunk}"
        assert pops == dev.pop_slots, f"chunk={chunk}"


def test_preempt_chunk_identity():
    """Whole-trace chunk == chunk 1 under preemption: events AND final carry
    (incl. the staging now living in the carry) bit-for-bit."""
    trace = gen_preempt_trace(11, 14, 2)
    outs = {}
    ref_carry = None
    for chunk in (1, 14):
        adm, fills, ev, toks, pops, loop = drive_fused_preempt(
            trace, slots=3, frontends=2, k=2, max_len=64, chunk=chunk,
            margin=0.25)
        outs[chunk] = (adm, fills, ev, toks, pops)
        if chunk == 1:
            ref_carry = loop.carry
        else:
            for name, a, b in zip(loop.carry._fields, ref_carry, loop.carry):
                for la, lb in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
                    np.testing.assert_array_equal(
                        np.asarray(la), np.asarray(lb), err_msg=name)
    assert outs[1] == outs[14]


def test_preempt_never_fires_matches_off_plane():
    """A margin no challenger can clear ⇒ the preemptive program emits
    exactly the non-preemptive plane's events (the preempt phase is
    observationally inert when it never fires)."""
    trace = gen_trace(9, 16, 2)
    host = drive_oracle(trace, slots=4, frontends=2, k=2, max_len=64,
                        plane="host")
    adm, fills, ev, toks, _pops, loop = drive_fused_preempt(
        trace, slots=4, frontends=2, k=2, max_len=64, chunk=4, margin=1e9)
    assert ev == [] and loop.preempt_log == []
    h_adm, h_fills, h_toks = host.results()
    assert (adm, fills, toks) == (h_adm, h_fills, h_toks)


def test_preempt_admission_rho_bound():
    """ρ = P·k survives preemption: at every admission event (fresh or
    resumed), at most P·k strictly-better requests are waiting — with
    re-pushed victims counted as waiting at their ORIGINAL priority (the
    §11 claim that re-queueing through the push path preserves the
    bound)."""
    frontends, slots, k, max_len, margin = 3, 3, 2, 64, 0.0
    trace = gen_preempt_trace(33, 30, frontends, burst_max=4)
    arrivals = {}
    for step, burst in enumerate(trace, start=1):
        for (place, pr, uid, max_new, plen) in burst:
            arrivals[uid] = (step, pr)
    adm, fills, ev, _toks, _pops, _ = drive_fused_preempt(
        trace, slots=slots, frontends=frontends, k=k, max_len=max_len,
        chunk=5, margin=margin)
    assert len(ev) > 0, "trace produced no preemptions; weaken it"
    # replay: waiting = submitted (foldable) or evicted, not seated. Within
    # a step the recorded orders interleave as: phase-1 fills, then (evict,
    # refill) pairs — an eviction always directly precedes its seat's fill,
    # so applying the next eviction when its (step, seat) matches the fill
    # being processed reconstructs exact event order.
    waiting = {}
    worst = 0
    fi = ei = 0
    for step in range(1, len(trace) + 1):
        for (place, pr, uid, mn, plen) in trace[step - 1]:
            waiting[uid] = pr
        while fi < len(fills) and fills[fi][0] == step:
            _, s, uid = fills[fi]
            if ei < len(ev) and ev[ei][0] == step and ev[ei][1] == s:
                _, _, vuid = ev[ei]
                ei += 1
                waiting[vuid] = arrivals[vuid][1]
            my_pr = arrivals[uid][1]
            better = sum(1 for u, pr in waiting.items()
                         if u != uid and pr < my_pr)
            worst = max(worst, better)
            waiting.pop(uid, None)
            fi += 1
    assert worst <= frontends * k, worst


def test_preempt_k0_degenerates_to_strict():
    """k = 0 (everything published immediately) + margin 0: every admission
    takes the globally best waiting request — zero strictly-better requests
    are ever waiting at an admission, i.e. the preemptive serving plane is
    priority-strict."""
    frontends, slots, max_len = 2, 2, 64
    trace = gen_preempt_trace(7, 24, frontends)
    adm, fills, ev, _toks, _pops, _ = drive_fused_preempt(
        trace, slots=slots, frontends=frontends, k=0, max_len=max_len,
        chunk=4, margin=0.0)
    arrivals = {}
    for step, burst in enumerate(trace, start=1):
        for (place, pr, uid, max_new, plen) in burst:
            arrivals[uid] = (step, pr)
    waiting = {}
    fi = ei = 0
    for step in range(1, len(trace) + 1):
        for (place, pr, uid, mn, plen) in trace[step - 1]:
            waiting[uid] = pr
        while fi < len(fills) and fills[fi][0] == step:
            _, s, uid = fills[fi]
            if ei < len(ev) and ev[ei][0] == step and ev[ei][1] == s:
                _, _, vuid = ev[ei]
                ei += 1
                waiting[vuid] = arrivals[vuid][1]
            my_pr = arrivals[uid][1]
            assert not any(pr < my_pr for u, pr in waiting.items()
                           if u != uid), (step, uid)
            waiting.pop(uid, None)
            fi += 1


def test_fused_staging_rows_bound():
    """The §11 staging indirection: rows are bounded by in-flight requests,
    not pool capacity — a tight ``staging_rows`` serves a roomy pool, frees
    rows as requests leave flight, and raises loudly when oversubscribed."""
    loop = toy_loop(slots=2, frontends=2, k=1, capacity=64, staging_rows=3)
    for i in range(3):
        loop.submit(0, float(i), i, _prompt(i, 2), 2)
    with pytest.raises(RuntimeError, match="staging full"):
        loop.submit(0, 9.0, 9, _prompt(9, 2), 2)
    loop.run_steps(1)            # admits 2 -> frees their rows (no preempt)
    loop.submit(1, 9.0, 9, _prompt(9, 2), 2)
    loop.submit(1, 9.5, 10, _prompt(10, 2), 2)
    # and a tight-rows preemptive loop stays bit-identical to the oracle
    trace = gen_preempt_trace(3, 12, 2, burst_max=2)
    host = drive_preempt_oracle(trace, "host", slots=2, frontends=2, k=1,
                                max_len=64, margin=0.0)
    adm, fills, ev, toks, _pops, _ = drive_fused_preempt(
        trace, slots=2, frontends=2, k=1, max_len=64, chunk=3, margin=0.0,
        capacity=128, staging_rows=32)
    assert (adm, fills, ev, toks) == host.results()


def test_streaming_retain_slots_reserved_until_release():
    """Retain mode: a popped slot stays occupied (capacity accounting and
    allocator) until release — the §11 reservation the in-trace re-push
    relies on."""
    adm = StreamingAdmitter(2, 1, capacity=3, retain=True)
    for i in range(3):
        adm.push(i % 2, float(i), i)
    adm.fold()
    got = adm.pop_ex(0)
    assert got is not None
    _pr, _item, slot = got
    with pytest.raises(RuntimeError, match="admission pool full"):
        adm.push(0, 9.0, 9)
    adm.release(slot)
    adm.push(0, 9.0, 9)         # freed slot is allocatable again
    assert len(adm) == 3


@pytest.mark.slow
def test_preemption_fuzz_soak():
    """Preemption fuzz soak (slow; nightly CI raises SOAK_SEEDS): the
    three-plane differential over long inversion-heavy traces with random
    (frontends, slots, k, margin) per seed."""
    for seed in _soak_seeds(6):
        try:
            rng = np.random.default_rng(seed * 31 + 7)
            frontends = int(rng.integers(2, 4))
            slots = int(rng.integers(2, 6))
            k = int(rng.integers(0, 4))
            margin = float(np.float32(
                [0.0, 0.0, 0.25, 0.5, 1.0][rng.integers(5)]))
            max_len = 48
            trace = gen_preempt_trace(seed, 50, frontends, burst_max=4)
            host = drive_preempt_oracle(
                trace, "host", slots=slots, frontends=frontends, k=k,
                max_len=max_len, margin=margin, capacity=512)
            dev = drive_preempt_oracle(
                trace, "device", slots=slots, frontends=frontends, k=k,
                max_len=max_len, margin=margin, capacity=512)
            assert host.results() == dev.results()
            adm, fills, ev, toks, pops, _ = drive_fused_preempt(
                trace, slots=slots, frontends=frontends, k=k,
                max_len=max_len, chunk=7, margin=margin, capacity=512)
            assert (adm, fills, ev, toks) == host.results()
            assert pops == dev.pop_slots
        except Exception as e:
            _dump_soak_repro("test_preemption_fuzz_soak", seed, e)
            raise AssertionError(
                f"preemption soak failed at seed={seed}") from e


def test_engine_preemption_matches_across_planes():
    """ServeEngine(preemption="margin") on the real reduced model: admission
    order, victim order, AND token streams identical across host, device,
    and fused planes — the resumed KV cache path is exact (an inexact
    resume diverges the post-resume tokens immediately)."""
    from repro.configs import get_reduced
    from repro.models import materialize, model_p
    from repro.serve.engine import Request, ServeEngine

    cfg = get_reduced("qwen3_1_7b")
    params = materialize(jax.random.PRNGKey(0), model_p(cfg))
    rng = np.random.default_rng(4)
    low = [(i, rng.integers(0, cfg.vocab_size, 5).astype(np.int32), 7, 9.0)
           for i in range(2)]
    high = [(i, rng.integers(0, cfg.vocab_size, 4).astype(np.int32), 3,
             float(i)) for i in range(2, 5)]

    def run(mode, chunk=1):
        eng = ServeEngine(cfg, params, slots=2, max_len=48, frontends=2,
                          k=1, config=ServeConfig(
                              step=mode, step_chunk=chunk,
                              preemption="margin", preempt_margin=0.5))
        for (rid, toks, mn, pr) in low:
            eng.submit(Request(rid=rid, tokens=toks, max_new=mn,
                               priority=pr), frontend=rid % 2)
        eng.step()
        eng.step()
        for (rid, toks, mn, pr) in high:
            eng.submit(Request(rid=rid, tokens=toks, max_new=mn,
                               priority=pr), frontend=rid % 2)
        done = eng.run()
        return (eng.admission_log, eng.preempt_log,
                {r.rid: r.out for r in done})

    ref = run("host")
    assert len(ref[1]) > 0, "no preemptions fired; strengthen the trace"
    assert run("device") == ref
    assert run("fused", 1) == ref
    assert run("fused", 3) == ref


# ---------------------------------------------------------------------------
# §14: pod-scale cross-pod block stealing — device plane vs HostPodQueues
# ---------------------------------------------------------------------------

def drive_pod_steal(seed, *, npods, k=3, n_push=4, margin=0.25,
                    push_phases=10, max_phases=600):
    """Single-process replay of the pod-steal plane (DESIGN.md §14.1): the
    ``make_pod_engine`` all-gather becomes a manual stack over a list of
    per-pod ``PodState``\\ s, the claim scan is ``kp.pod_steal_plan``
    verbatim, and EVERY phase is compared against the ``HostPodQueues``
    twin — fire/victim decisions, popped (prio, uid) streams, and full
    sorted (prio, uid, block) state records. Ends with exactly-once drain.
    Returns the number of fired steals (for trace-strength asserts)."""
    block_cap = k + n_push
    m = npods * n_push * push_phases + block_cap  # no pod can ever overflow
    rng = np.random.default_rng(seed)
    states = [kp.init_pod(m) for _ in range(npods)]
    host = HostPodQueues(npods, k=k, block_cap=block_cap, margin=margin)
    uid = 0
    popped_uids, steals = [], 0
    for phase in range(max_phases):
        if phase < push_phases:
            # uneven pushes across pods, collision-grid priorities: fronts
            # diverge, so the margin test and the (prio, uid) tie-break on
            # victim choice both carry weight
            for p in range(npods):
                n = int(rng.integers(0, n_push + 1))
                prios = np.full(n_push, np.inf, np.float32)
                uids = np.full(n_push, -1, np.int32)
                items = []
                for i in range(n):
                    pr = float(np.float32(
                        PRIO_GRID[rng.integers(len(PRIO_GRID))]))
                    prios[i], uids[i] = pr, uid
                    items.append((pr, uid))
                    uid += 1
                states[p] = kp.pod_push(
                    states[p], jnp.asarray(prios), jnp.asarray(uids), k=k)
                host.push(p, items)
        # steal phase: the manual all-gather (headers, fronts, payloads are
        # ALL pre-phase snapshots, exactly like the shard_map engine)
        heads = [kp.pod_best_block(s) for s in states]
        fronts = [kp.pod_front(s) for s in states]
        pays = [kp.pod_extract_block(states[p], heads[p][3], block_cap)
                for p in range(npods)]
        fire, victim = kp.pod_steal_plan(
            jnp.stack([h[0] for h in heads]),
            jnp.stack([h[1] for h in heads]),
            jnp.stack([h[2] for h in heads]),
            jnp.stack([f[1] for f in fronts]),
            jnp.stack([f[3] for f in fronts]),
            margin=margin)
        host_plan = {t: (v, pay) for (t, v, pay) in host.steal_phase()}
        for p in range(npods):
            assert bool(fire[p]) == (p in host_plan), (phase, p)
            if bool(fire[p]):
                assert int(victim[p]) == host_plan[p][0], (phase, p)
        for p in range(npods):                      # victims lose their block
            if any(bool(fire[t]) and int(victim[t]) == p
                   for t in range(npods)):
                states[p] = kp.pod_remove_block(states[p], heads[p][3])
        for p in range(npods):                      # thieves splice payloads
            if bool(fire[p]):
                v = int(victim[p])
                states[p] = kp.pod_insert_block(states[p], *pays[v])
                steals += 1
        for p in range(npods):                      # one pop per pod
            states[p], pr, u, valid = kp.pod_pop(states[p])
            got = (float(pr), int(u)) if bool(valid) else None
            assert got == host.pop(p), (phase, p)
            if got is not None:
                popped_uids.append(got[1])
        for p in range(npods):                      # full state records
            su = np.asarray(states[p].uid)
            live = su >= 0
            recs = sorted(zip(
                np.asarray(states[p].prio)[live].tolist(),
                su[live].tolist(),
                np.asarray(states[p].block)[live].tolist()))
            assert recs == host.snapshot(p), (phase, p)
        if phase >= push_phases and len(host) == 0:
            break
    assert len(host) == 0, "pods failed to drain"
    assert sorted(popped_uids) == list(range(uid)), "not exactly-once"
    return steals


@pytest.mark.parametrize("npods,k,margin", [
    (2, 3, 0.25), (3, 2, 0.0), (4, 1, 0.5)])
@settings(max_examples=3, deadline=None)
@given(seed=st.integers(0, 10_000))
def test_pod_steal_matches_host_twin(npods, k, margin, seed):
    """ISSUE 8 acceptance core, host half: the pod-steal plane is
    bit-identical to HostPodQueues on random traces — decisions, pop
    streams, records, exactly-once — incl. margin = 0 tie edges and k = 1
    single-item blocks. (The shard_map half is the 8-device selftest in
    tests/test_sharded_batch.py.)"""
    drive_pod_steal(seed, npods=npods, k=k, margin=margin)


def test_pod_steal_fires_and_is_block_granular():
    """Deterministic scenario: an empty pod steals the victim's best
    published block WHOLE (arXiv 1305.6474 — block, not item, granularity),
    the host twin fires identically, and the spliced block is re-published
    under the thief (stealable onward as a unit)."""
    k, cap, margin = 2, 4, 0.0
    states = [kp.init_pod(16), kp.init_pod(16)]
    host = HostPodQueues(2, k=k, block_cap=cap, margin=margin)
    p = jnp.asarray([0.5, 0.25], jnp.float32)
    u = jnp.asarray([0, 1], jnp.int32)
    states[1] = kp.pod_push(states[1], p, u, k=k)   # publishes block 0
    host.push(1, [(0.5, 0), (0.25, 1)])
    heads = [kp.pod_best_block(s) for s in states]
    fronts = [kp.pod_front(s) for s in states]
    fire, victim = kp.pod_steal_plan(
        jnp.stack([h[0] for h in heads]), jnp.stack([h[1] for h in heads]),
        jnp.stack([h[2] for h in heads]),
        jnp.stack([f[1] for f in fronts]), jnp.stack([f[3] for f in fronts]),
        margin=margin)
    assert [bool(x) for x in fire] == [True, False]
    assert int(victim[0]) == 1
    assert host.steal_phase() == [(0, 1, [(0.25, 1), (0.5, 0)])]
    pay = kp.pod_extract_block(states[1], heads[1][3], cap)
    states[1] = kp.pod_remove_block(states[1], heads[1][3])
    states[0] = kp.pod_insert_block(states[0], *pay)
    assert int(jnp.sum(states[0].uid >= 0)) == 2    # whole block moved
    assert int(jnp.sum(states[1].uid >= 0)) == 0
    hp, hu, has, _ = kp.pod_best_block(states[0])
    assert bool(has) and float(hp) == 0.25 and int(hu) == 1
    for pod in (0, 1):
        su = np.asarray(states[pod].uid)
        live = su >= 0
        recs = sorted(zip(np.asarray(states[pod].prio)[live].tolist(),
                          su[live].tolist(),
                          np.asarray(states[pod].block)[live].tolist()))
        assert recs == host.snapshot(pod), pod


@pytest.mark.slow
def test_pod_steal_fuzz_soak():
    """Pod-steal fuzz soak (slow; nightly CI raises SOAK_SEEDS): the full
    phase-by-phase differential with randomized (npods, k, n_push, margin,
    push_phases) per seed."""
    for seed in _soak_seeds(6):
        try:
            rng = np.random.default_rng(seed * 101 + 13)
            drive_pod_steal(
                seed,
                npods=int(rng.integers(2, 6)),
                k=int(rng.integers(1, 5)),
                n_push=int(rng.integers(1, 6)),
                margin=float(np.float32(
                    [0.0, 0.25, 0.5, 1.0][rng.integers(4)])),
                push_phases=int(rng.integers(6, 13)))
        except Exception as e:
            _dump_soak_repro("test_pod_steal_fuzz_soak", seed, e)
            raise AssertionError(
                f"pod-steal soak failed at seed={seed}") from e


@pytest.mark.slow
def test_multiqueue_fuzz_soak():
    """MULTIQUEUE fuzz soak: StreamingAdmitter(policy="multiqueue") vs the
    host MultiQueue over long interleaved push/pop traces with randomized
    (places, k) per seed — every pop (hits AND misses), the pop-attempt
    counters, and the final drain compared bit-for-bit. places = 1 pins the
    degenerate both-samples-same-queue edge."""
    for seed in _soak_seeds(6):
        try:
            rng = np.random.default_rng(seed * 77 + 5)
            places = int(rng.integers(1, 7))
            k = int(rng.integers(0, 4))
            dev = StreamingAdmitter(places, k, capacity=512,
                                    policy="multiqueue")
            host = MultiQueue(places, k)
            uid = 0
            for _phase in range(40):
                for _ in range(int(rng.integers(0, 6))):
                    place = int(rng.integers(places))
                    pr = float(np.float32(
                        PRIO_GRID[rng.integers(len(PRIO_GRID))]))
                    dev.push(place, pr, uid)
                    host.push(place, pr, uid)
                    uid += 1
                dev.flush()                 # MQ visibility is fold-granular
                for _ in range(int(rng.integers(0, 4))):
                    assert dev.pop(0) == host.pop(0)
            budget = 200 * places + 1000    # sampled drain: misses are legal
            while len(host) and budget:
                assert dev.pop(0) == host.pop(0)
                budget -= 1
            assert len(host) == 0 and len(dev) == 0, "failed to drain"
            assert dev._pops == host.pop_attempts
        except Exception as e:
            _dump_soak_repro("test_multiqueue_fuzz_soak", seed, e)
            raise AssertionError(
                f"multiqueue soak failed at seed={seed}") from e
