"""The program's own spans and device scopes in a profiler trace.

The program marks its host layers with spans named ``repro:<layer>``
(``repro.obs``: the SSSP phase driver, the serving step and its packer
thread) and the parts of its device programs with ``jax.named_scope``\\ s
(``pop``/``relax``/``push``/``stats`` in the SSSP phase; ``plan_fold``,
``fold``, ``pop_fill``, ``klsm_sync``, ``splice_in``, ``preempt`` and
``decode`` in the serving step). This module extends the reduction of
``chipbench/tracing.py`` to read them, and leaves that reduction as it is:
:func:`reduce_events` returns a :class:`ProgramTraceSummary`, a
``TraceSummary`` whose own fields and ``breakdown`` keys are the ones
``tracing.reduce_events`` gives, with three fields more.

A TPU trace names each device op by its whole HLO text, without the
``metadata={op_name=...}`` that carries the scope; the scope is taken from
the optimised HLO of the op's program (the text XLA dumps), by the op's
instruction name. :func:`read_xplane` keeps the raw text, and
:func:`resolve` shortens it and sets each op's scope and program, so that
a recorded excerpt (``chipbench/tests/data``) pins the chip's format.

    python3 -m chipbench.spans --workload <name> --seed <n> --seconds <s>

runs a cell as ``chipbench.run`` does, traces the last 5 s of its window,
and prints one JSON object: the program-span breakdown, the device time
by scope, and the eight per-layer metrics below (:data:`METRICS`). It
compiles every program afresh (the compile cache is off), so that XLA
dumps the optimised HLO; ``--excerpt SPAN:MS`` also writes the trace from
the start of the window's first ``SPAN`` for ``MS`` ms to
``<--out>/<workload>_spans_excerpt.json``.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re
from typing import Any, Dict, Iterable, List, NamedTuple, Optional, Tuple

from chipbench import tracing

PROGRAM_PREFIX = "repro:"
SCOPES = ("pop", "relax", "push", "stats", "plan_fold", "fold", "pop_fill",
          "klsm_sync", "splice_in", "preempt", "decode")
ADMISSION_SCOPES = ("plan_fold", "fold", "pop_fill", "klsm_sync", "preempt")
UNSCOPED = "unscoped"
OUTSIDE = "outside program spans"


class Event(NamedTuple):
    """``tracing.Event`` with three fields more. A host event's ``line`` is
    its thread (``<line name>#<index>``: threads can share a name)."""
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float
    scope: Optional[str] = None         # device op: its named scope
    args: Tuple[Tuple[str, Any], ...] = ()   # host span: its stats
    module: Optional[str] = None        # device op: the program it ran in

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


# ---------------------------------------------------------------------------
# reading a trace
# ---------------------------------------------------------------------------

def read_xplane(xplane_path: str) -> List[Event]:
    """The device planes' op and module events (an op named by its whole
    HLO text) and every host thread's ``cb:`` and ``repro:`` spans, with
    their stats."""
    from jax.profiler import ProfileData

    out: List[Event] = []
    for plane in ProfileData.from_file(xplane_path).planes:
        device = plane.name.startswith("/device:")
        if not (device or plane.name.startswith("/host:")):
            continue
        for i, line in enumerate(plane.lines):
            if device and line.name not in (tracing.OPS_LINE,
                                             tracing.MODULES_LINE):
                continue
            thread = line.name if device else f"{line.name}#{i}"
            for ev in line.events:
                if not (device or ev.name.startswith(
                        (tracing.SPAN_PREFIX, PROGRAM_PREFIX))):
                    continue
                args = () if device else tuple(
                    (k, _plain(v)) for k, v in ev.stats)
                out.append(Event(plane.name, thread, ev.name,
                                 float(ev.start_ns), float(ev.duration_ns),
                                 args=args))
    return out


def _plain(v):
    return v if isinstance(v, (int, float, str)) else str(v)


_MODULE = re.compile(r"^HloModule ([\w.\-]+)", re.M)
_COMPUTATION = re.compile(r"^(?:ENTRY )?%([\w.\-]+) .*\{$")
_INSTR = re.compile(r"^\s*(?:ROOT )?%([\w.\-]+) = ")
_OP_NAME = re.compile(r'metadata=\{[^}]*op_name="([^"]*)"')
_CALLS = re.compile(r"calls=%([\w.\-]+)")


def hlo_ops(text: str) -> Dict[str, str]:
    """Instruction name -> ``op_name`` metadata of one optimised HLO
    module's text. An instruction without metadata (XLA drops it on some
    fusions it makes) takes its called computation's: the root's, else the
    first found in the computation's instructions, nested calls
    included."""
    ops: Dict[str, str] = {}
    calls: Dict[str, str] = {}
    body: Dict[str, List[str]] = {}     # computation -> its instructions
    root: Dict[str, str] = {}
    comp = None
    for line in text.splitlines():
        c = _COMPUTATION.match(line)
        if c:
            comp = c.group(1)
            body[comp] = []
            continue
        m = _INSTR.match(line)
        if not m:
            continue
        name = m.group(1)
        if comp is not None:
            body[comp].append(name)
            if line.lstrip().startswith("ROOT "):
                root[comp] = name
        op = _OP_NAME.search(line)
        if op:
            ops[name] = op.group(1)
        called = _CALLS.search(line)
        if called:
            calls[name] = called.group(1)

    def of_computation(comp: str, depth: int = 0) -> Optional[str]:
        if depth > 8 or comp not in body:
            return None
        order = [root[comp]] if comp in root else []
        for name in order + body[comp]:
            if name in ops:
                return ops[name]
        for name in body[comp]:
            if name in calls:
                got = of_computation(calls[name], depth + 1)
                if got:
                    return got
        return None

    for name, comp in calls.items():
        if name not in ops:
            got = of_computation(comp)
            if got:
                ops[name] = got
    return ops


def hlo_index(texts: Iterable[str]) -> Dict[str, List[Dict[str, str]]]:
    """Module name -> the instruction tables of its compiled variants."""
    index: Dict[str, List[Dict[str, str]]] = {}
    for text in texts:
        m = _MODULE.search(text)
        if m:
            index.setdefault(m.group(1), []).append(hlo_ops(text))
    return index


def read_hlo_dump(dump_dir: str) -> Dict[str, List[Dict[str, str]]]:
    """:func:`hlo_index` of the optimised modules XLA dumped as text."""
    paths = glob.glob(os.path.join(dump_dir, "*after_optimizations.txt"))
    texts = []
    for p in sorted(paths):
        with open(p) as f:
            texts.append(f.read())
    return hlo_index(texts)


def scope_of(op_name: Optional[str]) -> Optional[str]:
    """The last of :data:`SCOPES` on an ``op_name`` path (a scope under
    ``vmap`` reads ``vmap(pop)``)."""
    if not op_name:
        return None
    for part in reversed(op_name.split("/")):
        while part.endswith(")") and "(" in part and not part.startswith(
                "jit("):
            part = part[part.index("(") + 1:-1]
        if part in SCOPES:
            return part
    return None


def _instruction(text: str) -> Optional[str]:
    m = _INSTR.match(text)
    return m.group(1) if m else None


def _program(module_event_name: str) -> str:
    """``jit__phase(4891889278507647015)`` -> ``jit__phase``."""
    return module_event_name.split("(", 1)[0]


def _placement(raw: List[Event], hlo: Dict[str, List[Dict[str, str]]]):
    """Each event of ``raw`` with the module run that holds it on its
    device (ops only) and its instruction name; and for each run name the
    instruction table of the compiled variant that holds the most of the
    instructions seen in its runs."""
    runs: Dict[str, List[Event]] = {}
    for e in raw:
        if e.line == tracing.MODULES_LINE:
            runs.setdefault(e.plane, []).append(e)
    for v in runs.values():
        v.sort(key=lambda e: e.start_ns)
    starts = {p: [e.start_ns for e in v] for p, v in runs.items()}

    def run_of(e: Event) -> Optional[Event]:
        i = bisect.bisect_right(starts.get(e.plane, []), e.start_ns) - 1
        if i >= 0 and e.start_ns < runs[e.plane][i].end_ns + 1.0:
            return runs[e.plane][i]
        return None

    placed = []
    seen: Dict[str, set] = {}
    for e in raw:
        if e.line != tracing.OPS_LINE:
            placed.append((e, None, None))
            continue
        r, instr = run_of(e), _instruction(e.name)
        placed.append((e, r, instr))
        if r is not None and instr:
            seen.setdefault(r.name, set()).add(instr)
    tables = {}
    for run_name, instrs in seen.items():
        variants = hlo.get(_program(run_name), [])
        if variants:
            tables[run_name] = max(
                variants, key=lambda v: len(instrs.intersection(v)))
    return placed, tables


def resolve(raw: List[Event],
            hlo: Dict[str, List[Dict[str, str]]]) -> List[Event]:
    """Each device op of ``raw`` with its short name (``tracing.op_name``),
    its program (the module run that holds it) and its scope (from the
    ``op_name`` of its instruction in ``hlo``)."""
    placed, tables = _placement(raw, hlo)
    out = []
    for e, r, instr in placed:
        if e.line != tracing.OPS_LINE:
            out.append(e)
            continue
        ops = tables.get(r.name, {}) if r is not None else {}
        out.append(e._replace(
            name=tracing.op_name(e.name), scope=scope_of(ops.get(instr)),
            module=_program(r.name) if r is not None else None))
    return out


# ---------------------------------------------------------------------------
# reduction
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class ProgramTraceSummary(tracing.TraceSummary):
    # span name (no prefix) -> (count, seconds, self seconds), of the spans
    # that closed inside the window; self = duration - child spans on the
    # same thread
    program_spans: Dict[str, Tuple[int, float, float]] = dataclasses.field(
        default_factory=dict)
    # scope -> (count, seconds) of leaf device ops (ops holding no other op)
    scopes: Dict[str, Tuple[int, float]] = dataclasses.field(
        default_factory=dict)
    # program -> scope -> (count, seconds) of its leaf ops
    scopes_by_program: Dict[str, Dict[str, Tuple[int, float]]] = (
        dataclasses.field(default_factory=dict))
    # innermost repro: span on the window's thread -> idle seconds
    idle_by_program_span: Dict[str, float] = dataclasses.field(
        default_factory=dict)

    def breakdown(self, top: int = 10) -> dict:
        out = super().breakdown(top)
        gaps = sorted(self.idle_by_program_span.items(),
                      key=lambda kv: -kv[1])[:top]
        out["idle_gaps_program"] = [[n, s] for n, s in gaps]
        return out

    def span(self, name: str) -> Tuple[int, float, float]:
        return self.program_spans.get(name, (0, 0.0, 0.0))

    def scope_seconds(self, *names: str) -> Optional[float]:
        """Leaf device seconds under any of ``names``; None if none ran."""
        got = [self.scopes[n][1] for n in names if n in self.scopes]
        return sum(got) if got else None


def reduce_events(events: List[Event]) -> ProgramTraceSummary:
    """``tracing.reduce_events`` of ``events``, and the program's spans,
    the leaf device time by scope, and each idle gap named by the
    innermost ``repro:`` span of the window's thread."""
    base = tracing.reduce_events(events)
    window = next(e for e in events if e.name == tracing.WINDOW_SPAN)
    w0, w1 = window.start_ns, window.start_ns + window.dur_ns
    spans = [e for e in events if e.name.startswith(PROGRAM_PREFIX)]
    scopes, by_program = _leaf_scopes(events, w0, w1)
    own = [e for e in spans if e.line == window.line]
    parents = _parents(own)
    starts = [e.start_ns for e in own]
    idle: Dict[str, float] = {}
    devices = 0
    for gaps in _idle_gaps(events, w0, w1):
        devices += 1
        for gs, ge in gaps:
            name = _innermost(own, starts, parents, 0.5 * (gs + ge))
            idle[name] = idle.get(name, 0.0) + (ge - gs) * 1e-9
    fields = {f.name: getattr(base, f.name)
              for f in dataclasses.fields(tracing.TraceSummary)}
    return ProgramTraceSummary(
        **fields, program_spans=_span_table(spans, w0, w1), scopes=scopes,
        scopes_by_program=by_program,
        idle_by_program_span={k: v / devices for k, v in idle.items()})


def _parents(spans: List[Event]) -> List[int]:
    """For spans of one thread sorted by start: each one's enclosing span
    (index), or -1. Spans of a thread nest."""
    spans.sort(key=lambda e: (e.start_ns, -e.dur_ns))
    parents, stack = [], []
    for i, e in enumerate(spans):
        while stack and spans[stack[-1]].end_ns < e.end_ns:
            stack.pop()
        parents.append(stack[-1] if stack else -1)
        stack.append(i)
    return parents


def _innermost(spans, starts, parents, t: float) -> str:
    """The innermost span holding ``t``: an ancestor of the last span that
    starts at or before ``t`` (spans nest)."""
    i = bisect.bisect_right(starts, t) - 1
    while i >= 0 and spans[i].end_ns < t:
        i = parents[i]
    return spans[i].name[len(PROGRAM_PREFIX):] if i >= 0 else OUTSIDE


def _span_table(spans: List[Event], w0: float, w1: float):
    by_thread: Dict[str, List[Event]] = {}
    for e in spans:
        by_thread.setdefault(e.line, []).append(e)
    table: Dict[str, List[float]] = {}
    for thread in by_thread.values():
        parents = _parents(thread)
        child_ns = [0.0] * len(thread)
        for e, p in zip(thread, parents):
            if p >= 0:
                child_ns[p] += e.dur_ns
        for e, c in zip(thread, child_ns):
            if not w0 <= e.end_ns <= w1:
                continue
            acc = table.setdefault(e.name[len(PROGRAM_PREFIX):],
                                   [0, 0.0, 0.0])
            acc[0] += 1
            acc[1] += e.dur_ns * 1e-9
            acc[2] += (e.dur_ns - c) * 1e-9
    return {k: (int(c), s, own) for k, (c, s, own) in table.items()}


def _leaf_ops(events: List[Event]) -> List[Event]:
    """The device ops that hold no other op of their device (a ``while``
    or ``conditional`` holds the ops of its body)."""
    ops_by_plane: Dict[str, List[Event]] = {}
    for e in events:
        if e.line == tracing.OPS_LINE and e.plane.startswith("/device:"):
            ops_by_plane.setdefault(e.plane, []).append(e)
    leaves = []
    for ops in ops_by_plane.values():
        ops.sort(key=lambda e: (e.start_ns, -e.dur_ns))
        leaf = [True] * len(ops)
        stack: List[int] = []
        for i, e in enumerate(ops):
            while stack and ops[stack[-1]].end_ns <= e.start_ns:
                stack.pop()
            if stack and e.end_ns <= ops[stack[-1]].end_ns:
                leaf[stack[-1]] = False
            stack.append(i)
        leaves += [e for e, is_leaf in zip(ops, leaf) if is_leaf]
    return leaves


def _leaf_scopes(events: List[Event], w0: float, w1: float):
    """Leaf ops clipped to the window, by scope and by program and scope."""
    scopes: Dict[str, List[float]] = {}
    by_program: Dict[str, Dict[str, List[float]]] = {}
    for e in _leaf_ops(events):
        s, t = max(e.start_ns, w0), min(e.end_ns, w1)
        if t <= s:
            continue
        scope = e.scope or UNSCOPED
        prog = by_program.setdefault(e.module or "?", {})
        for acc in (scopes.setdefault(scope, [0, 0.0]),
                    prog.setdefault(scope, [0, 0.0])):
            acc[0] += 1
            acc[1] += (t - s) * 1e-9
    return ({k: (int(c), s) for k, (c, s) in scopes.items()},
            {p: {k: (int(c), s) for k, (c, s) in v.items()}
             for p, v in by_program.items()})


def _idle_gaps(events: List[Event], w0: float, w1: float):
    """Per device that ran anything in the window: its idle gaps, the
    complement of its ops and program runs (as ``tracing`` counts busy)."""
    by_plane: Dict[str, List[Event]] = {}
    for e in events:
        if e.plane.startswith("/device:") and e.line in (
                tracing.OPS_LINE, tracing.MODULES_LINE):
            by_plane.setdefault(e.plane, []).append(e)
    for evs in by_plane.values():
        busy = tracing._clip(tracing._union(
            (e.start_ns, e.end_ns) for e in evs), w0, w1)
        if not busy:
            continue
        edges = [w0] + [x for iv in busy for x in iv] + [w1]
        yield [(gs, ge) for gs, ge in zip(edges[::2], edges[1::2])
               if ge > gs]


# ---------------------------------------------------------------------------
# the per-layer metrics these spans and scopes feed
# ---------------------------------------------------------------------------

def _mean_s(s: ProgramTraceSummary, name: str) -> Optional[float]:
    count, secs, _ = s.span(name)
    return secs / count if count else None


def _outside_ms(s, outer: str, inner: str) -> Optional[float]:
    """Mean ms of an ``outer`` span outside its ``inner`` child spans."""
    count, total, _ = s.span(outer)
    inners, inside, _ = s.span(inner)
    return 1e3 * (total - inside) / count if count and inners else None


def sssp_driver_host_us(s):
    """Mean host time of a phase outside its stats read-back."""
    ms = _outside_ms(s, "sssp.phase", "sssp.readback")
    return None if ms is None else 1e3 * ms


def sssp_readback_us(s):
    """Mean time of a phase's blocking stats read-back."""
    m = _mean_s(s, "sssp.readback")
    return None if m is None else 1e6 * m


def sssp_call_overhead_ms(s):
    """Mean set-up plus mean finish of one driver call."""
    prep, fin = _mean_s(s, "sssp.prepare"), _mean_s(s, "sssp.finish")
    return None if prep is None or fin is None else 1e3 * (prep + fin)


def sssp_pop_device_us(s):
    """Leaf device time under ``pop`` per phase program run."""
    runs, _ = s.module_seconds("_phase")
    pop = s.scope_seconds("pop")
    return None if pop is None or not runs else 1e6 * pop / runs


def serve_step_host_ms(s):
    """Mean host time of a serving step outside its event read-back."""
    return _outside_ms(s, "serve.step", "serve.readback")


def _per_step_ms(s, *scopes):
    steps = s.span("serve.step")[0]
    secs = s.scope_seconds(*scopes)
    return None if secs is None or not steps else 1e3 * secs / steps


def serve_splice_ms_per_step(s):
    return _per_step_ms(s, "splice_in")


def serve_decode_ms_per_step(s):
    return _per_step_ms(s, "decode")


def serve_admission_device_ms_per_step(s):
    return _per_step_ms(s, *ADMISSION_SCOPES)


METRICS = {f.__name__: f for f in (
    sssp_driver_host_us, sssp_readback_us, sssp_call_overhead_ms,
    sssp_pop_device_us, serve_step_host_ms, serve_splice_ms_per_step,
    serve_decode_ms_per_step, serve_admission_device_ms_per_step)}


def report(s: ProgramTraceSummary, top: int = 12) -> dict:
    """What the CLI prints of a summary: the breakdown, the spans, the
    scopes by program with their scoped share, the metrics."""
    idle = sum(s.idle_by_program_span.values())
    named = idle - s.idle_by_program_span.get(OUTSIDE, 0.0)
    programs = {}
    for prog, table in s.scopes_by_program.items():
        total = sum(v[1] for v in table.values())
        scoped = total - table.get(UNSCOPED, (0, 0.0))[1]
        if total > 0 and scoped > 0:
            programs[prog] = {"leaf_s": total, "scoped_share": scoped / total,
                              "scopes": table}
    return {"busy_s": s.busy_s, "window_s": s.window_s,
            "idle_share": s.idle_share,
            "idle_named_share": named / idle if idle else None,
            "breakdown": s.breakdown(top),
            "program_spans": s.program_spans, "scopes": s.scopes,
            "scoped_programs": programs,
            "metrics": {n: f(s) for n, f in METRICS.items()}}


def unscoped_ops(events: List[Event], program: str, top: int = 12):
    """The leaf ops of ``program`` left without a scope, by device time
    (whole trace, not clipped)."""
    table: Dict[str, float] = {}
    for e in _leaf_ops(events):
        if e.module == program and not e.scope:
            table[e.name] = table.get(e.name, 0.0) + e.dur_ns * 1e-9
    return sorted(table.items(), key=lambda kv: -kv[1])[:top]


# ---------------------------------------------------------------------------
# excerpts
# ---------------------------------------------------------------------------

def excerpt(raw: List[Event], hlo: Dict[str, List[Dict[str, str]]],
            span_name: str, ms: float) -> dict:
    """The raw events from the start of the window's first ``span_name``
    for ``ms`` ms, the window span cut to that stretch, and the HLO
    ``op_name`` of each op instruction in it, as a JSON-able record."""
    window = next(e for e in raw if e.name == tracing.WINDOW_SPAN)
    t0 = min(e.start_ns for e in raw
             if e.name == PROGRAM_PREFIX + span_name
             and e.start_ns >= window.start_ns)
    t1 = t0 + ms * 1e6
    keep = [window._replace(start_ns=t0, dur_ns=t1 - t0)]
    keep += [e for e in raw if e is not window and e.start_ns < t1
             and e.end_ns > t0]
    placed, tables = _placement(keep, hlo)
    ops: Dict[str, Dict[str, str]] = {}
    for _e, r, instr in placed:
        op = tables.get(r.name, {}).get(instr) if r is not None else None
        if op is not None:
            ops.setdefault(_program(r.name), {})[instr] = op
    names = sorted({e.name for e in keep})
    index = {n: i for i, n in enumerate(names)}
    return {"names": names,
            "events": [[e.plane, e.line, index[e.name], e.start_ns, e.dur_ns,
                        [list(a) for a in e.args]] for e in keep],
            "hlo_op_names": ops}


def load_excerpt(doc: dict) -> List[Event]:
    """The events of a recorded excerpt (each names an entry of its
    ``names``), resolved against the HLO ``op_name`` metadata it holds."""
    raw = [Event(p, line, doc["names"][i], s, d,
                 args=tuple(tuple(a) for a in args))
           for p, line, i, s, d, args in doc["events"]]
    return resolve(raw, {m: [ops] for m, ops in doc["hlo_op_names"].items()})


# ---------------------------------------------------------------------------
# the command line
# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    import argparse
    import gc
    import json
    import pathlib
    import sys
    import tempfile
    import time

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--excerpt", default=None,
                    help="SPAN:MS, e.g. sssp.phase:8")
    ap.add_argument("--out", default=".",
                    help="directory for the excerpt")
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU rehearsal at the files' rehearse sizes (host "
                         "spans only: a CPU trace has no device plane)")
    args = ap.parse_args(argv)
    scratch = tempfile.mkdtemp(prefix="chipbench-spans-")
    dump = os.path.join(scratch, "hlo")
    # before JAX loads: XLA dumps each optimised program as text
    os.environ["XLA_FLAGS"] = " ".join(filter(None, [
        os.environ.get("XLA_FLAGS"), f"--xla_dump_to={dump}",
        "--xla_dump_hlo_as_text"]))
    from chipbench import run as cb

    sys.path.insert(0, str(cb.ROOT / "src"))
    if args.rehearse:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import jax

    from chipbench import spec
    from chipbench.common import Context, with_overrides

    # no compile cache: a program loaded from it is not dumped
    jax.config.update("jax_enable_compilation_cache", False)
    cell = spec.load_cell(args.workload, cb.ROOT)
    try:
        device = cb.device_info(args.rehearse, cell.chips)
    except cb.NoDevice as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 2
    drv = spec.system_module(cell.system)
    ctx = Context(config=with_overrides(cell.config, args.rehearse),
                  traffic=with_overrides(cell.traffic, args.rehearse),
                  seed=args.seed, seconds=args.seconds, log=cb.log)
    state = drv.setup(ctx)
    gc.collect()
    gc.freeze()
    trace_dir = os.path.join(scratch, "trace")
    ctx.tracer = tracing.Tracer(trace_dir, min(cb.TRACE_SECONDS, args.seconds),
                                args.seconds)
    ctx.tracer.arm(time.perf_counter())
    gc.disable()
    try:
        out = drv.window(ctx, state)
    finally:
        gc.enable()
    ctx.tracer.stop()
    drv.release(state)
    checks = drv.check(ctx, state, out)
    raw = read_xplane(tracing.latest_xplane(trace_dir))
    hlo = read_hlo_dump(dump)
    events = resolve(raw, hlo)
    result = {"workload": args.workload, "seed": args.seed,
              "device": device["kind"],
              "correct": all(c.ok for c in checks),
              "end_to_end_traced": out.end_to_end,
              "hlo_modules": len(hlo)}
    try:
        summary = reduce_events(events)
    except ValueError as e:        # a CPU trace: no device plane
        if not args.rehearse:
            raise
        result["device_reduction"] = str(e)
        w = next(e for e in events if e.name == tracing.WINDOW_SPAN)
        result["program_spans"] = _span_table(
            [e for e in events if e.name.startswith(PROGRAM_PREFIX)],
            w.start_ns, w.end_ns)
    else:
        result.update(report(summary))
        result["unscoped_ops"] = {p: unscoped_ops(events, p)
                                  for p in result["scoped_programs"]}
    if args.excerpt:
        name, ms = args.excerpt.split(":")
        path = pathlib.Path(args.out) / f"{args.workload}_spans_excerpt.json"
        path.parent.mkdir(parents=True, exist_ok=True)
        doc = {"about": f"{ms} ms of a traced window of {args.workload} on "
                        f"one {device['kind']}, from the start of a "
                        f"repro:{name} span (chipbench.spans.excerpt)"}
        doc.update(excerpt(raw, hlo, name, float(ms)))
        path.write_text(json.dumps(doc))
        result["excerpt"] = str(path)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
