"""What the harness hands a system module, and what it hands back.

A system module (``chipbench/systems/<system>.py``) has four functions,
and a fifth, ``control``, that ``chipbench/control.py`` calls:

* ``setup(ctx) -> state``: make the data and the weights from the seed,
  build the system under test, and warm up every shape the window uses;
* ``window(ctx, state) -> Outcome``: drive the system for ``ctx.seconds``
  (calling ``ctx.poll()`` now and then, which starts and stops the
  traced part of the window);
* ``release(state)``: free the program's device state, after the harness
  has read the memory peak;
* ``check(ctx, state, outcome) -> [Check]``: compare what the window
  produced with the benchmark's own reference.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Dict, List, Optional


@dataclasses.dataclass
class Check:
    """One number compared, beside its limit (``value <= limit`` passes)."""
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return self.value <= self.limit


@dataclasses.dataclass
class Outcome:
    end_to_end: Dict[str, float]
    counters: Dict[str, Any]
    attempted: int
    failed: int
    data: Any = None                    # what ``check`` compares


@dataclasses.dataclass
class Context:
    config: dict
    traffic: dict
    seed: int
    seconds: float
    log: Callable[[str], None]
    tracer: Optional[Any] = None

    def poll(self, now: Optional[float] = None):
        if self.tracer is not None:
            self.tracer.poll(time.perf_counter() if now is None else now)


def with_overrides(doc: dict, rehearse: bool) -> dict:
    """``doc`` with its ``rehearse`` block applied when rehearsing."""
    out = {k: v for k, v in doc.items() if k != "rehearse"}
    if rehearse:
        out.update(doc.get("rehearse", {}))
    return out


def percentile(values: List[float], q: float) -> float:
    """The q-th percentile (linear interpolation, as numpy's default)."""
    import numpy as np

    return float(np.percentile(np.asarray(values, np.float64), q))


def seed_words(seed: int, tag: int, count: int = 1):
    """``count`` 31-bit integers from a run seed of any size and a tag."""
    import numpy as np

    rng = np.random.default_rng([int(seed) & 0xFFFFFFFFFFFFFFFF, tag])
    return rng.integers(0, 2**31 - 1, count)
