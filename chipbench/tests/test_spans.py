"""The program's spans and scopes in a trace (``chipbench/spans.py``): the
reduction on hand traces and on recorded chip excerpts, and the metrics
read from it."""
import dataclasses
import json
import pathlib

import pytest

from chipbench import spans, tracing
from chipbench.spans import Event

DEV, HOST = "/device:TPU:0", "/host:CPU"
OPS, MODS = tracing.OPS_LINE, tracing.MODULES_LINE
MAIN, PACKER = "python3#4", "python3#9"
MS = 1e6
DATA = pathlib.Path(__file__).with_name("data")


def ev(plane, line, name, start_ms, dur_ms, **kw):
    return Event(plane, line, name, start_ms * MS, dur_ms * MS, **kw)


def hlo(name, calls="%fused_computation"):
    return (f"%{name} = f32[8]{{0}} fusion(f32[8]{{0}} %p), kind=kLoop, "
            f"calls={calls}")


HLO_PHASE = """HloModule jit__phase, is_scheduled=true

%fused_computation.2 (p: f32[8]) -> f32[8] {
  %p = f32[8]{0} parameter(0)
  ROOT %add.1 = f32[8]{0} add(%p, %p), metadata={op_name="jit(_phase)/relax/add"}
}

ENTRY %main (p: f32[8]) -> f32[8] {
  %fusion.2 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(_phase)/while/body/vmap(pop)/gt" stack_frame_id=2}
  %fusion.3 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop, calls=%fused_computation.2
  ROOT %while.1 = f32[8]{0} while(f32[8]{0} %p), condition=%c, body=%b, metadata={op_name="jit(_phase)/while"}
}
"""


def hand_trace():
    """A 20 ms window. Main thread: a phase over 1–9 ms holding its
    dispatch (1–2) and read-back (5–8); the packer thread: a pack over
    2–12 ms (rid 7) holding a prefill (3–6). Device: one ``_phase`` run over
    2–5 ms, whose ``while.1`` holds a ``pop`` fusion (2–3) and a ``relax``
    fusion (3.5–4.5), and a copy with no scope over 8.5–9 ms."""
    return [
        ev(HOST, MAIN, "cb:window", 0, 20),
        ev(HOST, MAIN, "cb:solve", 0, 20),
        ev(HOST, MAIN, "repro:sssp.phase", 1, 8),
        ev(HOST, MAIN, "repro:sssp.dispatch", 1, 1),
        ev(HOST, MAIN, "repro:sssp.readback", 5, 3),
        ev(HOST, PACKER, "repro:serve.pack", 2, 10, args=(("rid", 7),)),
        ev(HOST, PACKER, "repro:serve.prefill", 3, 3),
        ev(DEV, MODS, "jit__phase(123)", 2, 3),
        ev(DEV, MODS, "jit_copy(9)", 8.5, 0.5),
        ev(DEV, OPS, "%while.1 = f32[8]{0} while(f32[8]{0} %p), "
                     "condition=%c, body=%b", 2, 3),
        ev(DEV, OPS, hlo("fusion.2"), 2, 1),
        ev(DEV, OPS, hlo("fusion.3"), 3.5, 1),
        ev(DEV, OPS, "%copy.4 = f32[8]{0} copy(f32[8]{0} %p)", 8.5, 0.5),
    ]


def hand_summary():
    events = spans.resolve(hand_trace(), spans.hlo_index([HLO_PHASE]))
    return events, spans.reduce_events(events)


def test_scope_taken_from_the_op_name_in_the_hlo_text():
    ops = spans.hlo_ops(HLO_PHASE)
    assert ops["fusion.2"] == "jit(_phase)/while/body/vmap(pop)/gt"
    # no metadata of its own: its computation's root's
    assert ops["fusion.3"] == "jit(_phase)/relax/add"
    events, _ = hand_summary()
    got = {e.name: (e.scope, e.module) for e in events if e.line == OPS}
    assert got == {"while.1 while": (None, "jit__phase"),
                   "fusion.2 fusion": ("pop", "jit__phase"),
                   "fusion.3 fusion": ("relax", "jit__phase"),
                   "copy.4 copy": (None, "jit_copy")}


@pytest.mark.parametrize("op_name,scope", [
    ("jit(run)/while/body/cond/branch_1_fun/splice_in/select_n", "splice_in"),
    ("jit(run)/plan_fold/klsm_sync/sort", "klsm_sync"),
    ("jit(_phase_chunk_impl)/while/body/vmap(pop)/vmap(jit(_gumbel))/add",
     "pop"),
    ("jit(fold)/add", None),
    ("jit(f)/mul", None),
    (None, None),
])
def test_scope_of(op_name, scope):
    assert spans.scope_of(op_name) == scope


def test_program_spans_per_thread_with_self_time_and_args():
    events, s = hand_summary()
    assert s.program_spans == {
        "sssp.phase": (1, pytest.approx(0.008), pytest.approx(0.004)),
        "sssp.dispatch": (1, pytest.approx(0.001), pytest.approx(0.001)),
        "sssp.readback": (1, pytest.approx(0.003), pytest.approx(0.003)),
        # another thread: the phase's children do not count against it
        "serve.pack": (1, pytest.approx(0.010), pytest.approx(0.007)),
        "serve.prefill": (1, pytest.approx(0.003), pytest.approx(0.003)),
    }
    (pack,) = [e for e in events if e.name == "repro:serve.pack"]
    assert pack.args == (("rid", 7),)


def test_spans_that_close_after_the_window_are_left_out():
    trace = hand_trace() + [ev(HOST, MAIN, "repro:sssp.phase", 15, 10)]
    s = spans.reduce_events(spans.resolve(trace, {}))
    assert s.program_spans["sssp.phase"][0] == 1


def test_leaf_ops_by_scope_count_a_while_once():
    _, s = hand_summary()
    assert s.scopes == {"pop": (1, pytest.approx(0.001)),
                        "relax": (1, pytest.approx(0.001)),
                        spans.UNSCOPED: (1, pytest.approx(0.0005))}
    assert s.scopes_by_program["jit__phase"] == {
        "pop": (1, pytest.approx(0.001)), "relax": (1, pytest.approx(0.001))}
    assert s.scopes_by_program["jit_copy"] == {
        spans.UNSCOPED: (1, pytest.approx(0.0005))}


def test_idle_gaps_named_by_the_innermost_program_span():
    _, s = hand_summary()
    # busy [2, 5] and [8.5, 9]; gaps [0, 2] (middle 1 ms: the dispatch),
    # [5, 8.5] (6.75 ms: the read-back), [9, 20] (outside program spans;
    # the packer's spans are on another thread)
    assert s.idle_by_program_span == {
        "sssp.dispatch": pytest.approx(0.002),
        "sssp.readback": pytest.approx(0.0035),
        spans.OUTSIDE: pytest.approx(0.011)}
    b = s.breakdown()
    assert b["idle_gaps_program"][0] == [spans.OUTSIDE, pytest.approx(0.011)]
    assert b["idle_gaps"] == [["solve", pytest.approx(0.0165)]]


def _base_fields(s):
    return {f.name: getattr(s, f.name)
            for f in dataclasses.fields(tracing.TraceSummary)}


def test_recorded_excerpt_gives_the_existing_summary_unchanged():
    """On the first recorded excerpt, the existing fields and breakdown
    keys are exactly what ``tracing.reduce_events`` gives."""
    doc = json.loads((DATA / "sssp_solo_excerpt.json").read_text())
    old = tracing.reduce_events([tracing.Event(*e) for e in doc["events"]])
    new = spans.reduce_events([Event(*e) for e in doc["events"]])
    assert _base_fields(new) == _base_fields(old)
    b_old, b_new = old.breakdown(), new.breakdown()
    assert {k: b_new[k] for k in b_old} == b_old
    assert list(b_new) == list(b_old) + ["idle_gaps_program"]
    assert new.program_spans == {}
    assert new.idle_by_program_span == {
        spans.OUTSIDE: pytest.approx(new.window_s - new.busy_s)}


def _chip(name):
    return spans.load_excerpt(json.loads((DATA / name).read_text()))


CHIP = {
    # a phase of sssp_er10k_solo and a step of serve_qwen3_steady, recorded
    # on one TPU v5 lite by ``python3 -m chipbench.spans --excerpt``
    "sssp_solo_spans_excerpt.json": (
        "jit__phase", ("sssp.phase", "sssp.dispatch", "sssp.readback"),
        {"pop", "relax", "push", "stats"}),
    "serve_steady_spans_excerpt.json": (
        "jit_run", ("serve.step", "serve.plan", "serve.dispatch",
                    "serve.readback", "serve.replay", "serve.consume"),
        {"plan_fold", "fold", "pop_fill", "splice_in", "decode"}),
}


@pytest.mark.parametrize("name", sorted(CHIP))
def test_chip_excerpt_resolves_spans_and_scopes(name):
    """The chip's format: ops named by whole HLO text, module runs holding
    them, ``repro:`` spans on the host; every op gets its program, and
    nearly all of the step program's leaf time a scope."""
    program, want_spans, want_scopes = CHIP[name]
    events = _chip(name)
    ops = [e for e in events if e.line == OPS]
    assert ops and all(" = " not in e.name for e in ops)
    assert all(e.module for e in ops)
    s = spans.reduce_events(events)
    assert set(want_spans) <= set(s.program_spans)
    table = s.scopes_by_program[program]
    assert want_scopes <= set(table)
    total = sum(v[1] for v in table.values())
    assert table.get(spans.UNSCOPED, (0, 0.0))[1] < 0.1 * total
    idle = sum(s.idle_by_program_span.values())
    assert idle == pytest.approx(s.window_s - s.busy_s)
    assert s.idle_by_program_span.get(spans.OUTSIDE, 0.0) < 0.1 * idle


@pytest.mark.parametrize("name", sorted(CHIP))
def test_chip_excerpt_existing_fields_unchanged(name):
    events = _chip(name)
    old = tracing.reduce_events(events)
    new = spans.reduce_events(events)
    assert _base_fields(new) == _base_fields(old)
    assert {k: new.breakdown()[k] for k in old.breakdown()} == old.breakdown()


def test_serve_step_cache_ops_belong_to_splice_in():
    """The step program's largest ops: the staged-cache gathers and the
    select of the splice, and the layer loop of the decode."""
    scope = {e.name: e.scope for e in _chip("serve_steady_spans_excerpt.json")
             if e.line == OPS}
    assert scope["fusion.173 fusion"] == "splice_in"
    assert scope["fusion.174 fusion"] == "splice_in"
    assert scope["copy_select_fusion fusion"] == "splice_in"
    assert scope["fusion.249 fusion"] == "decode"


# ---------------------------------------------------------------------------
# the metrics
# ---------------------------------------------------------------------------

def summary(program_spans=None, scopes=None, modules=None):
    return spans.ProgramTraceSummary(
        window_s=1.0, busy_s=0.5, devices=1, ops={}, modules=modules or {},
        idle_by_span={}, program_spans=program_spans or {},
        scopes=scopes or {})


def test_sssp_metrics():
    s = summary(
        program_spans={"sssp.phase": (100, 0.4, 0.0),
                       "sssp.readback": (100, 0.1, 0.1),
                       "sssp.prepare": (2, 0.010, 0.010),
                       "sssp.finish": (2, 0.004, 0.004)},
        scopes={"pop": (500, 0.03), "relax": (100, 0.01)},
        modules={"jit__phase(1)": (100, 0.05), "jit__unstack(2)": (100, 0.1)})
    m = {n: f(s) for n, f in spans.METRICS.items()}
    assert m["sssp_driver_host_us"] == pytest.approx(3000.0)
    assert m["sssp_readback_us"] == pytest.approx(1000.0)
    assert m["sssp_call_overhead_ms"] == pytest.approx(7.0)
    assert m["sssp_pop_device_us"] == pytest.approx(300.0)
    assert [m[n] for n in m if n.startswith("serve_")] == [None] * 4


def test_serve_metrics():
    s = summary(
        program_spans={"serve.step": (10, 0.4, 0.01),
                       "serve.readback": (10, 0.3, 0.3)},
        scopes={"splice_in": (20, 0.15), "decode": (400, 0.1),
                "fold": (10, 0.002), "pop_fill": (10, 0.003),
                "plan_fold": (10, 0.001), spans.UNSCOPED: (5, 0.5)})
    m = {n: f(s) for n, f in spans.METRICS.items()}
    assert m["serve_step_host_ms"] == pytest.approx(10.0)
    assert m["serve_splice_ms_per_step"] == pytest.approx(15.0)
    assert m["serve_decode_ms_per_step"] == pytest.approx(10.0)
    assert m["serve_admission_device_ms_per_step"] == pytest.approx(0.6)
    assert [m[n] for n in m if n.startswith("sssp_")] == [None] * 4
