"""Host spans (``repro.obs``) and device scopes of the program: what a
profiler trace of the SSSP drivers and the serving engine shows, and that
the scopes change the compiled programs' metadata only (DESIGN.md §17)."""
import contextlib
import glob
import os
import re
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.core import Policy, run_sssp, run_sssp_batched
from repro.core import sssp as ss

SSSP_SCOPES = ("pop", "relax", "push", "stats")
CHUNK_SCOPES = ("plan_fold", "fold", "pop_fill", "splice_in", "preempt",
                "decode")


class Span(NamedTuple):
    thread: str
    name: str
    start: float
    end: float
    args: dict

    def holds(self, other: "Span") -> bool:
        return (other is not self and other.thread == self.thread
                and self.start <= other.start and other.end <= self.end)


def traced(fn, trace_dir):
    """Run ``fn`` under a profiler trace; return its result and the
    ``repro:`` spans of every host thread."""
    from jax.profiler import ProfileData

    jax.profiler.start_trace(str(trace_dir))
    try:
        out = fn()
    finally:
        jax.profiler.stop_trace()
    path = max(glob.glob(os.path.join(str(trace_dir), "**", "*.xplane.pb"),
                         recursive=True), key=os.path.getmtime)
    spans = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for i, line in enumerate(plane.lines):    # one line a thread
            for ev in line.events:
                if ev.name.startswith(obs.PREFIX):
                    spans.append(Span(
                        f"{plane.name}#{i}", ev.name[len(obs.PREFIX):],
                        ev.start_ns, ev.start_ns + ev.duration_ns,
                        dict(ev.stats)))
    return out, spans


def named(spans, name):
    return [s for s in spans if s.name == name]


def children(spans, parent, name):
    return [s for s in named(spans, name) if parent.holds(s)]


@pytest.fixture(scope="module")
def graph():
    w = ss.make_er_graph(3, 64, 0.2)
    return w, ss.dijkstra_ref(w)


SSSP_KW = dict(num_places=4, k=2, policy=Policy.HYBRID)


def test_run_sssp_spans_one_phase_each(graph, tmp_path):
    """One ``sssp.phase`` a phase, each holding one dispatch and one
    read-back; one prepare before them and one finish after."""
    w, final = graph
    run, spans = traced(lambda: run_sssp(w, seed=1, final=final, **SSSP_KW),
                        tmp_path)
    assert run.correct
    phases = named(spans, "sssp.phase")
    assert len(phases) == run.phases
    for p in phases:
        assert len(children(spans, p, "sssp.dispatch")) == 1
        assert len(children(spans, p, "sssp.readback")) == 1
    (prep,), (fin,) = named(spans, "sssp.prepare"), named(spans, "sssp.finish")
    assert prep.end <= min(p.start for p in phases)
    assert fin.start >= max(p.end for p in phases)


def test_run_sssp_batched_spans_carry_the_weights_bytes(graph, tmp_path):
    w, final = graph
    ws = np.stack([w, w])
    res, spans = traced(lambda: run_sssp_batched(
        ws, seeds=[1, 2], finals=np.stack([final, final]), **SSSP_KW),
        tmp_path)
    (prep,) = named(spans, "sssp.prepare")
    assert prep.args == {"bytes": ws.nbytes}
    phases = named(spans, "sssp.phase")
    assert len(phases) == res.joint_phases
    for p in phases:
        assert len(children(spans, p, "sssp.readback")) == 1
    assert len(named(spans, "sssp.finish")) == 1
    assert res.wall_s > 0


def test_serve_engine_step_and_packer_spans(tmp_path):
    """``serve.step`` per step with its plan, dispatch, read-back, replay
    and consume inside; ``serve.pack`` per request on the packer thread,
    carrying its rid, with the prefill and the publish inside."""
    from repro.configs import get_reduced
    from repro.models import materialize, model_p
    from repro.serve.config import ServeConfig
    from repro.serve.engine import Request, ServeEngine

    cfg = get_reduced("qwen3_1_7b")
    params = materialize(jax.random.PRNGKey(0), model_p(cfg))
    eng = ServeEngine(cfg, params, slots=2, max_len=32, frontends=2, k=1,
                      config=ServeConfig(step="continuous", packer="thread"))
    rids = [11, 12, 13]

    def drive():
        for i, rid in enumerate(rids):
            eng.submit(Request(rid=rid, tokens=np.arange(4, dtype=np.int32),
                               max_new=3, priority=float(i)), frontend=i % 2)
        eng.wait_packed()
        steps, done = 0, []
        while len(done) < len(rids) and steps < 40:
            done += eng.step()
            steps += 1
        return steps, done

    (steps, done), spans = traced(drive, tmp_path)
    assert sorted(r.rid for r in done) == rids
    step_spans = named(spans, "serve.step")
    assert len(step_spans) == steps
    for s in step_spans:
        for part in ("serve.plan", "serve.dispatch", "serve.readback",
                     "serve.replay", "serve.consume"):
            assert len(children(spans, s, part)) == 1, part
    packs = named(spans, "serve.pack")
    assert sorted(p.args["rid"] for p in packs) == rids
    for p in packs:
        assert p.thread != step_spans[0].thread
        assert len(children(spans, p, "serve.prefill")) == 1
        assert len(children(spans, p, "serve.publish_wait")) == 1


# ---------------------------------------------------------------------------
# device scopes
# ---------------------------------------------------------------------------

def _op_names(hlo_text):
    return set(re.findall(r'op_name="([^"]+)"', hlo_text))


def _scopes(hlo_text, names):
    return {n for n in names
            if any(n in op.split("/") for op in _op_names(hlo_text))}


def _phase_compiled(n=64):
    def phase(state, key, w, final):      # a fresh function: no trace cache
        return ss.sssp_phase(state, key, w, final, num_places=4, k=2,
                             policy=Policy.HYBRID)

    w = jnp.asarray(ss.make_er_graph(1, n, 0.2))
    final = jnp.zeros((n,), jnp.float64 if jax.config.x64_enabled
                      else jnp.float32)
    state = ss.init_sssp(w, 4)
    return jax.jit(phase).lower(state, jax.random.PRNGKey(0), w,
                                final).compile()


def _chunk_compiled(**kw):
    from repro.serve.fused_step import _build_chunk_impl, toy_loop

    loop = toy_loop(slots=2, frontends=2, k=1, continuous=True, **kw)
    fn = _build_chunk_impl(
        loop.decode_fn, k=loop.k, frontends=loop.frontends, slots=loop.slots,
        max_len=loop.max_len, n=2, preempt=loop.preemption == "margin",
        margin=loop.margin, rounds=loop.rounds, continuous=True,
        storage=loop.storage)
    bufs, _ = loop._pack_bufs(2)
    return fn.lower(loop.params, loop.carry, bufs).compile()


def test_every_scope_is_in_the_compiled_programs():
    assert _scopes(_phase_compiled().as_text(), SSSP_SCOPES) == set(
        SSSP_SCOPES)
    text = _chunk_compiled(preemption="margin", margin=0.5).as_text()
    assert _scopes(text, CHUNK_SCOPES) == set(CHUNK_SCOPES)
    klsm = _chunk_compiled(storage="klsm").as_text()
    assert "klsm_sync" in _scopes(klsm, ("klsm_sync",))


def _strip_metadata(hlo_text):
    """The program without its metadata: no ``metadata={...}`` attributes
    and no source-location tables."""
    text = re.sub(r",? metadata=\{[^}]*\}", "", hlo_text)
    keep, skip = [], False
    for line in text.splitlines():
        if line in ("FileNames", "FunctionNames", "FileLocations",
                    "StackFrames"):
            skip = True
        elif skip and not line.strip():
            skip = False
        elif not skip:
            keep.append(line)
    return "\n".join(keep)


@pytest.mark.parametrize("program", ["sssp_phase", "chunk"])
def test_scopes_change_metadata_only(program, monkeypatch):
    """The compiled program with the scopes equals the one without them,
    once metadata is stripped."""
    build = {"sssp_phase": _phase_compiled,
             "chunk": lambda: _chunk_compiled(preemption="margin",
                                              margin=0.5)}[program]
    with_scopes = build().as_text()
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    without = build().as_text()
    assert "/pop/" in with_scopes or "/decode/" in with_scopes
    assert _scopes(without, SSSP_SCOPES + CHUNK_SCOPES) == set()
    assert _strip_metadata(with_scopes) == _strip_metadata(without)
