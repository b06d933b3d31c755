"""The reduction from trace events to busy/idle time, device time by
program and op, and idle gaps named by host span."""
import json
import pathlib

import pytest

from chipbench import tracing
from chipbench.tracing import Event

DEV, HOST = "/device:TPU:0", "/host:CPU"
OPS, MODS = tracing.OPS_LINE, tracing.MODULES_LINE
MS = 1e6


def ev(plane, line, name, start_ms, dur_ms):
    return Event(plane, line, name, start_ms * MS, dur_ms * MS)


def hand_trace():
    """A 10 ms window: two program runs (2 ms and 3 ms, ops overlapping
    inside the second), a host ``solve`` span over 0–6 ms and ``step`` over
    6–10 ms, and one op that starts before the window."""
    return [
        ev(HOST, "python3", "cb:window", 0, 10),
        ev(HOST, "python3", "cb:solve", 0, 6),
        ev(HOST, "python3", "cb:step", 6, 4),
        ev(HOST, "python3", "cb:inner", 4.5, 1),        # inside solve
        ev(DEV, MODS, "jit__phase(1)", 1, 2),
        ev(DEV, MODS, "jit_prefill_fn(2)", 7, 3),
        ev(DEV, MODS, "jit_small(3)", 0.2, 0.1),          # lists no ops
        ev(DEV, OPS, "fusion.1 fusion", 1, 1),
        ev(DEV, OPS, "k.1 custom-call tpu_custom_call", 2, 1),
        ev(DEV, OPS, "while.2 while", 7, 3),
        ev(DEV, OPS, "fusion.3 fusion", 7.5, 1),         # nested in while.2
        ev(DEV, OPS, "copy.9 copy", -1, 1.5),            # clipped to 0.5 ms
    ]


def test_busy_idle_by_hand():
    s = tracing.reduce_events(hand_trace())
    # busy: [0, 0.5] + [1, 3] + [7, 10] = 5.5 ms of 10
    assert s.window_s == pytest.approx(0.010)
    assert s.busy_s == pytest.approx(0.0055)
    assert s.idle_share == pytest.approx(0.45)
    assert s.devices == 1


def test_device_time_by_program_and_op():
    s = tracing.reduce_events(hand_trace())
    assert s.module_seconds("_phase") == (1, pytest.approx(0.002))
    assert s.module_seconds("prefill") == (1, pytest.approx(0.003))
    assert s.op_seconds("tpu_custom_call") == (1, pytest.approx(0.001))
    assert s.op_seconds("copy") == (1, pytest.approx(0.0005))


def test_idle_gaps_named_by_innermost_span():
    s = tracing.reduce_events(hand_trace())
    # gaps: [0.5, 1] and [3, 7]; their middles at 0.75 ms (solve) and 5 ms
    # (inner, the innermost span there)
    assert s.idle_by_span == {"solve": pytest.approx(0.0005),
                              "inner": pytest.approx(0.004)}
    b = s.breakdown()
    assert b["idle_gaps"][0][0] == "inner"
    assert b["device_ops"][0] == ["while.2 while", pytest.approx(0.003)]


def test_no_window_or_no_device_work_is_an_error():
    with pytest.raises(ValueError):
        tracing.reduce_events([e for e in hand_trace()
                               if e.name != "cb:window"])
    with pytest.raises(ValueError):
        tracing.reduce_events([e for e in hand_trace()
                               if not e.plane.startswith("/device")])


def test_op_name_shortens_hlo_text():
    text = ('%_phase.1 = (f32[1,10,1,128]{3,2,1,0:T(1,128)S(1)}) custom-call('
            'f32[1,10,8,128]{3,2,1,0:T(8,128)S(1)} %bitcast.108), '
            'custom_call_target="tpu_custom_call", operand_layout')
    assert tracing.op_name(text) == "_phase.1 custom-call tpu_custom_call"
    assert tracing.op_name(
        "%fusion.14 = s32[80]{0:T(128)S(1)} fusion(s32[10000]{0} %x)"
    ) == "fusion.14 fusion"
    assert tracing.op_name("jit__phase(123)") == "jit__phase(123)"


def _recorded():
    path = pathlib.Path(__file__).with_name("data") / "sssp_solo_excerpt.json"
    return [Event(*e) for e in json.loads(path.read_text())["events"]]


def test_recorded_trace_against_a_brute_force_count():
    """A real excerpt (12 ms of an SSSP solve on a v5e): busy time by
    sampling every 100 ns must agree with the interval union, and every
    program run inside the window must be counted once."""
    events = _recorded()
    s = tracing.reduce_events(events)
    w = next(e for e in events if e.name == tracing.WINDOW_SPAN)
    w0, w1 = w.start_ns, w.start_ns + w.dur_ns
    busy_line = [e for e in events if e.line in (OPS, MODS)]
    step = 100.0
    hits = sum(1 for i in range(int((w1 - w0) / step))
               if any(e.start_ns <= w0 + (i + 0.5) * step < e.start_ns + e.dur_ns
                      for e in busy_line))
    assert s.busy_s == pytest.approx(hits * step * 1e-9, rel=0.02, abs=2e-7)
    inside = [e for e in events if e.line == MODS
              and e.start_ns < w1 and e.start_ns + e.dur_ns > w0]
    assert sum(c for c, _ in s.modules.values()) == len(inside)
    assert 0.0 < s.idle_share < 1.0
    assert sum(s.idle_by_span.values()) == pytest.approx(
        s.window_s - s.busy_s)
