"""Host spans, compile counting, and the reduction of a profiler trace.

Spans are ``jax.profiler.TraceAnnotation``\\ s named ``cb:<layer call>``
around each call the harness makes into the program, so that an idle gap
on the device can be named by what the host was doing in it. The reduction
reads the profiler's ``.xplane.pb`` into plain event tuples first
(:func:`flatten`), so that it can be checked on a small recorded trace
(``chipbench/tests``).
"""
from __future__ import annotations

import contextlib
import dataclasses
import glob
import os
import re
import threading
from typing import Dict, Iterable, List, NamedTuple, Optional, Tuple

SPAN_PREFIX = "cb:"
WINDOW_SPAN = SPAN_PREFIX + "window"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


@contextlib.contextmanager
def span(name: str):
    """A host span in the profiler's trace (inert while no trace runs)."""
    import jax

    with jax.profiler.TraceAnnotation(SPAN_PREFIX + name):
        yield


class CompileCounter:
    """Counts traces, backend compiles and persistent-cache loads, process
    wide, from JAX's monitoring events. ``snapshot()`` before and after the
    window tells whether anything compiled inside it."""

    EVENTS = {
        "/jax/core/compile/jaxpr_trace_duration": "traces",
        "/jax/core/compile/backend_compile_duration": "compiles",
    }
    CACHE_HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        import jax._src.monitoring as mon

        self._lock = threading.Lock()
        self.counts = {"traces": 0, "compiles": 0, "cache_loads": 0}

        def on_duration(event, _duration, **_):
            key = self.EVENTS.get(event)
            if key is not None:
                with self._lock:
                    self.counts[key] += 1

        def on_event(event, **_):
            if event == self.CACHE_HIT:
                with self._lock:
                    self.counts["cache_loads"] += 1

        mon.register_event_duration_secs_listener(on_duration)
        mon.register_event_listener(on_event)

    def snapshot(self) -> Dict[str, int]:
        with self._lock:
            return dict(self.counts)


# ---------------------------------------------------------------------------
# trace capture and flattening
# ---------------------------------------------------------------------------

_OPCODE = re.compile(r"\s([a-z][\w\-.]*)\(")
_TARGET = re.compile(r'custom_call_target="([^"]+)"')


def op_name(text: str) -> str:
    """A device op's short name: the HLO instruction, its opcode and, for a
    custom call, its target (the trace names ops by their whole HLO text)."""
    if " = " not in text:
        return text
    lhs, rhs = text.split(" = ", 1)
    op = _OPCODE.search(" " + rhs)
    name = f"{lhs.lstrip('%')} {op.group(1) if op else ''}".strip()
    target = _TARGET.search(rhs)
    return f"{name} {target.group(1)}" if target else name


class Event(NamedTuple):
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float


def latest_xplane(trace_dir: str) -> str:
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(paths, key=os.path.getmtime)


def flatten(xplane_path: str) -> List[Event]:
    """Device-plane events of the op and module lines, and the harness's own
    host spans, as plain tuples."""
    from jax.profiler import ProfileData

    out: List[Event] = []
    for plane in ProfileData.from_file(xplane_path).planes:
        device = plane.name.startswith("/device:")
        host = plane.name.startswith("/host:")
        if not (device or host):
            continue
        for line in plane.lines:
            if device and line.name not in (OPS_LINE, MODULES_LINE):
                continue
            for ev in line.events:
                if host and not ev.name.startswith(SPAN_PREFIX):
                    continue
                name = op_name(ev.name) if line.name == OPS_LINE else ev.name
                out.append(Event(plane.name, line.name, name,
                                 float(ev.start_ns), float(ev.duration_ns)))
    return out


# ---------------------------------------------------------------------------
# reduction
# ---------------------------------------------------------------------------

def _union(intervals: Iterable[Tuple[float, float]]) -> List[Tuple[float, float]]:
    merged: List[Tuple[float, float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1] = (merged[-1][0], e)
        else:
            merged.append((s, e))
    return merged


def _clip(iv, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in iv if e > lo and s < hi]


@dataclasses.dataclass
class TraceSummary:
    window_s: float                         # the traced window (host span)
    busy_s: float                           # device busy, mean over devices
    devices: int
    ops: Dict[str, Tuple[int, float]]       # op name -> (count, seconds)
    modules: Dict[str, Tuple[int, float]]   # program name -> (count, seconds)
    idle_by_span: Dict[str, float]          # host span -> idle seconds

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def op_seconds(self, *needles: str) -> Tuple[int, float]:
        """Count and seconds of the ops whose name holds any needle."""
        return _match(self.ops, needles)

    def module_seconds(self, *needles: str) -> Tuple[int, float]:
        return _match(self.modules, needles)

    def breakdown(self, top: int = 10) -> dict:
        ops = sorted(self.ops.items(), key=lambda kv: -kv[1][1])[:top]
        gaps = sorted(self.idle_by_span.items(), key=lambda kv: -kv[1])[:top]
        return {"device_ops": [[n, s] for n, (_c, s) in ops],
                "idle_gaps": [[n, s] for n, s in gaps]}


def _match(table, needles) -> Tuple[int, float]:
    count, secs = 0, 0.0
    for name, (c, s) in table.items():
        if any(nd in name for nd in needles):
            count += c
            secs += s
    return count, secs


def reduce_events(events: List[Event]) -> TraceSummary:
    """Busy and idle time of the device inside the harness's ``cb:window``
    span, device time by op and by program, and each idle gap named by the
    innermost harness span that covers its middle."""
    windows = [e for e in events if e.name == WINDOW_SPAN]
    if not windows:
        raise ValueError("trace holds no cb:window span")
    w0 = windows[0].start_ns
    w1 = w0 + windows[0].dur_ns
    spans = [e for e in events if e.name.startswith(SPAN_PREFIX)
             and e.name != WINDOW_SPAN]
    by_plane: Dict[str, Dict[str, List[Event]]] = {}
    for e in events:
        if e.plane.startswith("/device:"):
            by_plane.setdefault(e.plane, {}).setdefault(e.line, []).append(e)

    ops: Dict[str, List[float]] = {}
    modules: Dict[str, List[float]] = {}
    busy_total = 0.0
    idle_by_span: Dict[str, float] = {}
    used = 0
    for lines in by_plane.values():
        # a program run covers its ops; some runs list no op of their own
        busy_events = lines.get(OPS_LINE, []) + lines.get(MODULES_LINE, [])
        busy = _clip(_union((e.start_ns, e.start_ns + e.dur_ns)
                            for e in busy_events), w0, w1)
        if not busy:
            continue
        used += 1
        busy_total += sum(e - s for s, e in busy)
        for table, line in ((ops, OPS_LINE), (modules, MODULES_LINE)):
            for e in lines.get(line, []):
                s, t = max(e.start_ns, w0), min(e.start_ns + e.dur_ns, w1)
                if t <= s:
                    continue
                acc = table.setdefault(e.name, [0, 0.0])
                acc[0] += 1
                acc[1] += (t - s) * 1e-9
        edges = [w0] + [x for iv in busy for x in iv] + [w1]
        for gs, ge in zip(edges[::2], edges[1::2]):
            if ge <= gs:
                continue
            name = _innermost(spans, 0.5 * (gs + ge))
            idle_by_span[name] = idle_by_span.get(name, 0.0) + (ge - gs) * 1e-9
    if used == 0:
        raise ValueError("no device operation ran inside the traced window")
    window_s = (w1 - w0) * 1e-9
    return TraceSummary(
        window_s=window_s, busy_s=busy_total * 1e-9 / used, devices=used,
        ops={k: (int(c), s) for k, (c, s) in ops.items()},
        modules={k: (int(c), s) for k, (c, s) in modules.items()},
        idle_by_span={k: v / used for k, v in idle_by_span.items()})


def _innermost(spans: List[Event], t: float) -> str:
    best: Optional[Event] = None
    for e in spans:
        if e.start_ns <= t <= e.start_ns + e.dur_ns:
            if best is None or e.dur_ns < best.dur_ns:
                best = e
    return best.name[len(SPAN_PREFIX):] if best is not None else "outside spans"


class Tracer:
    """Profiles the last ``seconds`` of a window of ``window_s`` into
    ``trace_dir``: starting the profiler costs little, but stopping it
    writes the trace and holds the host for seconds, which inside the
    window would stall the system under test. So the traced part ends
    with the window, and ``poll`` (called by the system module's loop) starts and
    stops it."""

    def __init__(self, trace_dir: str, seconds: float, window_s: float):
        self.trace_dir, self.seconds, self.window_s = (
            trace_dir, seconds, window_s)
        self._ann = None
        self._start_at = self._end_at = None
        self.started = False

    def arm(self, t0: float):
        self._start_at = t0 + max(self.window_s - self.seconds, 0.0)
        self._end_at = t0 + self.window_s

    def poll(self, now: float):
        if not self.started and now >= self._start_at:
            self._start()
        elif self._ann is not None and now >= self._end_at:
            self.stop()

    def _start(self):
        import jax

        jax.profiler.start_trace(self.trace_dir)
        self._ann = jax.profiler.TraceAnnotation(WINDOW_SPAN)
        self._ann.__enter__()
        self.started = True

    def stop(self):
        import jax

        if self._ann is None:
            return
        self._ann.__exit__(None, None, None)
        self._ann = None
        jax.profiler.stop_trace()

    def reduce(self) -> TraceSummary:
        self.stop()
        if not self.started:
            raise RuntimeError("the traced part of the window never began")
        return reduce_events(flatten(latest_xplane(self.trace_dir)))
