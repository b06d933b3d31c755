"""Serving cells: ``ServeEngine.submit``/``step`` under open-loop traffic.

The configuration is the model (its published config.json keys) and the
engine (``serve``: slots, max_len, frontends, k and the ``ServeConfig``
fields); the mix may set further ``ServeConfig`` fields under
``serve_config`` (preemption). Its ``model_type`` picks the model's files
(``chipbench/spec.py``): the program's ``ModelConfig``, the reference, the
weights and the FLOP counts; nothing here names a model. Weights are
random, made on the device from the seed in their serving dtypes.

The client side is the harness: each request is due at a wall-clock time
and is submitted once due and once a prefill staging row is free (a submit
beyond ``staging_rows`` would stop the packer); a request kept waiting for
a row waits on the client side, and that wait counts in its latency. One
``step()`` runs one engine step; a token is delivered when the ``step()``
after which it is visible in ``Request.out`` returns.
"""
from __future__ import annotations

import collections
import dataclasses
import gc
import time
from typing import Dict, List, Optional

import jax
import numpy as np

from chipbench import spec, traffic
from chipbench.common import Check, Outcome, percentile, seed_words
from chipbench.tracing import span

DRAIN_S = 60.0            # how long past the window a due request may take
SAMPLE = 6                # requests compared with the reference per run
# largest arrival plan warmed up: each pending prefill holds a whole
# max_len cache (117 MB at full width) until its plan is staged, so a plan
# of 32 would not fit beside the staged rows
WARM_PLAN = 16


@dataclasses.dataclass
class Rec:
    due: float                      # absolute, perf_counter seconds
    arrival: traffic.Arrival
    tokens: np.ndarray
    submit_t: Optional[float] = None
    seat_t: Optional[float] = None      # first step that admitted it
    first_t: Optional[float] = None
    last_t: Optional[float] = None
    done_t: Optional[float] = None
    n: int = 0
    req: object = None


class State:
    pass


def _engine(st: State):
    from repro.serve.config import ServeConfig
    from repro.serve.engine import ServeEngine

    sv = st.serve
    config = ServeConfig(step="continuous", packer="thread",
                         admission_capacity=sv["admission_capacity"],
                         staging_rows=sv["staging_rows"],
                         **st.serve_overrides)
    return ServeEngine(st.mc, st.params, slots=sv["slots"],
                       max_len=sv["max_len"], frontends=sv["frontends"],
                       k=sv["k"], config=config)


def setup(ctx) -> State:
    from repro.models import model_p
    from repro.models.module import P

    cfg, mix = ctx.config, ctx.traffic
    st = State()
    st.cfg, st.serve = cfg, cfg["serve"]
    st.serve_overrides = mix.get("serve_config", {})
    st.retain = st.serve_overrides.get("preemption", "off") == "margin"
    st.model = spec.model_files(cfg["model_type"], cfg[spec.ROOT_KEY])
    st.mc = st.model.adapter.model_config(cfg)
    want = jax.tree.structure(model_p(st.mc), is_leaf=lambda x: isinstance(x, P))
    t0 = time.perf_counter()
    with span("make_weights"):
        st.params = st.model.reference.make_weights(
            int(seed_words(ctx.seed, 2)[0]), cfg)
        jax.block_until_ready(st.params)
    if jax.tree.structure(st.params) != want:
        raise ValueError("the weight layout differs from the model's")
    ctx.log(f"weights made in {time.perf_counter() - t0:.3f} s")
    st.eng = _engine(st)
    t0 = time.perf_counter()
    with span("warm_up"):
        _warm_up(st, traffic.prompt_buckets(mix))
    ctx.log(f"warm-up in {time.perf_counter() - t0:.3f} s")
    st.arrivals = traffic.generate(mix, ctx.seconds, st.serve["frontends"])
    rng = np.random.default_rng(seed_words(ctx.seed, 3, 2))
    st.prompts = [rng.integers(0, cfg["vocab_size"], a.prompt_len,
                               dtype=np.int32) for a in st.arrivals]
    return st


def _warm_up(st: State, buckets: List[int]):
    """Every prompt length, and every power-of-two size of a staging batch
    up to ``WARM_PLAN`` (a plan folds what the packer finished since the
    last step: one or two requests at these rates, 16 in a burst), through
    the same engine the window drives. A larger plan would compile inside
    the window, and the harness reports that as an error."""
    from repro.serve.engine import Request

    rows = st.serve["staging_rows"]
    sizes = sorted({min(1 << i, rows) for i in range(WARM_PLAN.bit_length())})
    rid = -1
    for size in sizes:
        for j in range(size):
            plen = buckets[j % len(buckets)]
            st.eng.submit(Request(rid=rid, tokens=np.zeros(plen, np.int32),
                                  max_new=2, priority=float(j % 4)),
                          frontend=j % st.serve["frontends"])
            rid -= 1
        st.eng.wait_packed()
        live = size
        for _ in range(64 * size):
            live -= len(st.eng.step())
            if not live:
                break
        else:
            raise RuntimeError(f"warm-up: {live} of {size} requests never "
                               "finished")
    st.warm_admitted = len(st.eng.admission_log)


def _rows_in_use(st: State, recs: Dict[int, Rec]) -> int:
    """Staging rows the submitted requests hold: until admitted, or, with
    preemption on, until finished."""
    if st.retain:
        return sum(1 for r in recs.values() if r.done_t is None)
    return sum(1 for r in recs.values() if r.first_t is None)


def window(ctx, st: State) -> Outcome:
    from repro.serve.engine import Request

    eng, arr = st.eng, st.arrivals
    prefill_flops = st.model.reference.prefill_flops
    flops_per_token = st.model.reference.flops_per_token
    t0 = time.perf_counter()
    t_end = st.t_end = t0 + ctx.seconds
    recs = [Rec(due=t0 + a.due_s, arrival=a, tokens=st.prompts[i])
            for i, a in enumerate(arr)]
    client = collections.deque()
    inflight: Dict[int, Rec] = {}
    nxt, steps, tokens_in, flop_in = 0, 0, 0, 0.0
    max_client_queue, dispatch0 = 0, eng.dispatches
    steps_in = dispatch_in = None
    adm0 = st.warm_admitted
    while True:
        now = time.perf_counter()
        if steps_in is None and now >= t_end:
            steps_in, dispatch_in = steps, eng.dispatches - dispatch0
        while nxt < len(arr) and recs[nxt].due <= now:
            client.append(nxt)
            nxt += 1
        max_client_queue = max(max_client_queue, len(client))
        while client and _rows_in_use(st, inflight) < st.serve["staging_rows"]:
            i = client.popleft()
            r, a = recs[i], arr[i]
            r.req = Request(rid=i, tokens=r.tokens, max_new=a.max_new,
                            priority=a.priority)
            with span("submit"):
                eng.submit(r.req, frontend=a.frontend)
            r.submit_t = time.perf_counter()
            inflight[i] = r
            if r.submit_t < t_end:
                flop_in += prefill_flops(st.cfg, a.prompt_len)
        if not inflight:
            if nxt >= len(arr) and not client:
                break
            wait = recs[nxt].due - time.perf_counter() if nxt < len(arr) else 0
            time.sleep(min(max(wait, 0.0), 0.005))
            ctx.poll()
            continue
        with span("step"):
            done = eng.step()
        t = time.perf_counter()
        steps += 1
        for rid in eng.admission_log[adm0:]:
            if recs[rid].seat_t is None:
                recs[rid].seat_t = t
        adm0 = len(eng.admission_log)
        for r in inflight.values():
            n = len(r.req.out)
            if n > r.n:
                if r.n == 0:
                    r.first_t = t
                if t < t_end:
                    tokens_in += n - r.n
                    # token i >= 1 was decoded at context prompt_len + i
                    for j in range(max(r.n, 1), n):
                        flop_in += flops_per_token(
                            st.cfg, r.arrival.prompt_len + j)
                r.n, r.last_t = n, t
        for req in done:
            inflight.pop(req.rid).done_t = t
        ctx.poll(t)
        if t > t_end + DRAIN_S:
            break
    if steps_in is None:
        steps_in, dispatch_in = steps, eng.dispatches - dispatch0
    st.recs = recs
    return _outcome(ctx, recs, tokens_in, flop_in, steps_in, dispatch_in,
                    max_client_queue, t_end)


def _outcome(ctx, recs, tokens_in, flop_in, steps_in, dispatch_in,
             max_client_queue, t_end) -> Outcome:
    ttft = [1e3 * (r.first_t - r.due) for r in recs if r.first_t is not None]
    tpot = [1e3 * (r.last_t - r.first_t) / (r.n - 1) for r in recs
            if r.done_t is not None and r.done_t < t_end and r.n > 1]
    wait = [1e3 * (r.seat_t - r.submit_t) for r in recs
            if r.seat_t is not None and r.seat_t < t_end]
    late = [1e3 * (r.submit_t - r.due) for r in recs if r.submit_t is not None]
    undelivered = sum(1 for r in recs
                      if r.done_t is None or r.n != r.arrival.max_new)
    ctx.log(f"{len(recs)} requests due, {undelivered} undelivered; client "
            f"wait before submit p50 {percentile(late or [0], 50):.3f} ms, max "
            f"{max(late, default=0):.3f} ms; longest client queue "
            f"{max_client_queue}; {steps_in} steps in the window; TTFT "
            f"p50 {percentile(ttft or [0], 50):.1f} ms, largest "
            f"{[round(x, 1) for x in sorted(ttft)[-6:]]} ms")
    return Outcome(
        end_to_end={
            "serve_ttft_mean_ms": float(np.mean(ttft)) if ttft else None,
            "serve_tpot_p95_ms": percentile(tpot, 95) if tpot else None,
        },
        counters={"queue_wait_p95_ms": percentile(wait, 95) if wait else None,
                  "dispatches": dispatch_in, "steps": steps_in,
                  "model_flop": flop_in, "window_s": ctx.seconds,
                  "tokens": tokens_in, "tokens_per_s": tokens_in / ctx.seconds,
                  "requests": len(recs), "undelivered": undelivered,
                  "ttft_p50_ms": percentile(ttft, 50) if ttft else None,
                  "ttft_p95_ms": percentile(ttft, 95) if ttft else None,
                  "preempted": sum(r.req.preemptions for r in recs
                                   if r.req is not None),
                  "client_wait_max_ms": max(late, default=0.0)},
        attempted=len(recs), failed=undelivered)


def release(st: State):
    st.eng = None
    gc.collect()


# ---------------------------------------------------------------------------
# the comparison
# ---------------------------------------------------------------------------

def sample(ctx, st: State, count: int = SAMPLE) -> List[Rec]:
    """Finished requests drawn from the seed: the longest, a preempted
    one where there is one, and the rest at random."""
    done = [r for r in st.recs if r.done_t is not None and r.n >= 1]
    if not done:
        return []
    rng = np.random.default_rng(seed_words(ctx.seed, 4, 2))
    pick = {max(range(len(done)), key=lambda i: (done[i].n, -i))}
    pre = [i for i, r in enumerate(done) if r.req.preemptions > 0]
    if pre:
        pick.add(int(rng.choice(pre)))
    for i in rng.permutation(len(done)):
        if len(pick) >= min(count, len(done)):
            break
        pick.add(int(i))
    return [done[i] for i in sorted(pick)]


def gaps(st: State, recs: List[Rec], control: bool = False):
    """Widest gap by which a served token's reference logit lies below the
    reference's best, over ``recs`` (and the same for the token that the
    control puts first at each of those positions); and the token count."""
    ref = st.model.reference
    items = ref.cfg_items(st.cfg)
    served_worst, control_worst, count = 0.0, 0.0, 0
    for r in recs:
        seq = np.concatenate([r.tokens, np.asarray(r.req.out, np.int32)])
        pad = np.zeros(st.serve["max_len"], np.int32)
        pad[: len(seq)] = seq
        s, c = ref.token_gaps(st.params, pad, items, control)
        # served token i sits at prompt_len + i, predicted at one before
        lo, hi = len(r.tokens) - 1, len(seq) - 1
        served_worst = max(served_worst, float(np.asarray(s)[lo:hi].max()))
        control_worst = max(control_worst, float(np.asarray(c)[lo:hi].max()))
        count += hi - lo
    return served_worst, control_worst, count


def check(ctx, st: State, out: Outcome) -> list:
    """The model (served tokens against the float32 reference) and
    delivery (every request due in the window finished with its tokens)."""
    limits = ctx.config["limits"]
    recs = sample(ctx, st)
    t0 = time.perf_counter()
    gap, _, count = gaps(st, recs)
    ctx.log(f"reference over {len(recs)} requests, {count} served tokens, "
            f"in {time.perf_counter() - t0:.3f} s")
    return [Check("logit_gap", gap if recs else float("inf"),
                  limits["logit_gap"]),
            Check("undelivered", out.counters["undelivered"], 0)]


def control(ctx, st: State) -> dict:
    """The control's reading of ``logit_gap`` on the run's own sample: the
    float8 reference's first choice at each served position, measured
    against the float32 reference."""
    _, ctl, count = gaps(st, sample(ctx, st), control=True)
    return {"logit_gap": ctl, "tokens": count}
