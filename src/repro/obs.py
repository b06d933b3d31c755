"""Host spans of the program, for the JAX profiler's trace.

``span("sssp.phase")`` is a ``jax.profiler.TraceAnnotation`` named
``repro:sssp.phase``. It keeps nothing itself: while no trace runs it is
inert (about 0.5 µs to enter and leave on a TPU v5e host), and while one runs
the profiler records it on the calling thread, on the same clock as the
device's ops, with its keyword arguments as event stats. So a trace shows
where the host time between device programs goes.

Spans (DESIGN.md §17):

* SSSP drivers (``core/engine.py``): ``sssp.prepare``, ``sssp.phase`` >
  {``sssp.dispatch``, ``sssp.readback``}, ``sssp.finish``;
* serving (``serve/engine.py``, ``serve/fused_step.py``): ``serve.step`` >
  {``serve.plan``, ``serve.consume``, ``serve.dispatch``,
  ``serve.readback``, ``serve.replay``}; on the packer thread
  ``serve.pack`` > {``serve.prefill``, ``serve.publish_wait``}.

Device programs carry ``jax.named_scope``\\ s instead (HLO metadata only).
"""
from __future__ import annotations

import jax

PREFIX = "repro:"


def span(name: str, **args) -> jax.profiler.TraceAnnotation:
    """A host span ``repro:<name>``; ``args`` become the event's stats."""
    return jax.profiler.TraceAnnotation(PREFIX + name, **args)
