"""One general generator for the open-loop traffic mixes.

A mix (``chipbench/traffic/<mix>.json``) lists ``streams``. A stream has an
``arrival`` (``poisson`` with ``rate_per_s``, or ``burst`` with
``period_s``, ``offset_s`` and ``count``), a ``prompt`` and an ``output``
length (``fixed``, or ``lognormal`` with ``median`` and ``sigma``, clipped
to ``min``/``max``, and for prompts snapped up to one of ``buckets``), and
``classes`` (priorities, lower = more urgent) with ``weights``.

Every seed gets the same work: the arrival times, the lengths, the
priorities and the frontends are drawn from the mix's ``base_seed`` alone,
and the run's seed draws only what the requests say (the prompt tokens)
and the weights. A permuted schedule changed which requests met a busy
server, and with it the latency tail, by more than the system's own noise
(PERF.md); so the spread between runs measures the system, not the draw.
The structure (classes, Poisson stream plus periodic bursts) follows the
repository's trace generator, with arrivals in wall-clock seconds.
"""
from __future__ import annotations

import dataclasses
import math
from typing import List

import numpy as np


@dataclasses.dataclass(frozen=True)
class Arrival:
    due_s: float          # seconds after the window opens
    prompt_len: int
    max_new: int
    priority: float
    frontend: int


def _lengths(spec: dict, rng: np.random.Generator, n: int) -> np.ndarray:
    if "fixed" in spec:
        return np.full(n, int(spec["fixed"]), np.int64)
    if spec.get("dist") != "lognormal":
        raise ValueError(f"unknown length distribution {spec!r}")
    x = rng.lognormal(math.log(spec["median"]), spec["sigma"], n)
    x = np.clip(np.rint(x), spec.get("min", 1), spec.get("max", np.inf))
    buckets = spec.get("buckets")
    if buckets:
        b = np.asarray(sorted(buckets))
        x = b[np.minimum(np.searchsorted(b, x), len(b) - 1)]
    return x.astype(np.int64)


def _classes(spec: dict, rng: np.random.Generator, n: int) -> np.ndarray:
    classes = np.asarray(spec["classes"], np.float64)
    w = np.asarray(spec.get("weights", [1.0] * len(classes)), np.float64)
    # a fixed share of each class (largest remainders), not a draw
    share = w / w.sum() * n
    count = np.floor(share).astype(int)
    for i in np.argsort(-(share - count))[: n - count.sum()]:
        count[i] += 1
    return np.repeat(classes, count)


def _base_rng(mix: dict, stream_index: int) -> np.random.Generator:
    return np.random.default_rng([int(mix["base_seed"]), stream_index])


def _stream(mix: dict, i: int, st: dict, seconds: float,
            frontends: int) -> List[Arrival]:
    base = _base_rng(mix, i)
    arr = st["arrival"]
    if arr["kind"] == "poisson":
        n = int(math.ceil(arr["rate_per_s"] * seconds))
        gaps = base.exponential(1.0 / arr["rate_per_s"], n)
        # scaled so that all n arrivals fall inside the window: the rate is
        # exactly n / seconds
        due = (np.cumsum(gaps) - 0.5 * gaps[0]) * (seconds / gaps.sum())
    elif arr["kind"] == "burst":
        starts = np.arange(arr["offset_s"], seconds, arr["period_s"])
        n = len(starts) * int(arr["count"])
        due = np.repeat(starts, int(arr["count"]))
    else:
        raise ValueError(f"unknown arrival kind {arr['kind']!r}")
    plen = _lengths(st["prompt"], base, n)
    out = _lengths(st["output"], base, n)
    prio = base.permutation(_classes(st, base, n))
    front = base.integers(0, frontends, n)
    return [Arrival(float(due[j]), int(plen[j]), int(out[j]),
                    float(prio[j]), int(front[j]))
            for j in range(n) if due[j] < seconds]


def generate(mix: dict, seconds: float, frontends: int) -> List[Arrival]:
    """The mix's arrivals in ``[0, seconds)``, sorted by due time."""
    out: List[Arrival] = []
    for i, st in enumerate(mix["streams"]):
        out += _stream(mix, i, st, seconds, frontends)
    out.sort(key=lambda a: a.due_s)
    return out


def prompt_buckets(mix: dict) -> List[int]:
    """Every prompt length the mix can send (the shapes set-up warms)."""
    lens = set()
    for st in mix["streams"]:
        p = st["prompt"]
        if "fixed" in p:
            lens.add(int(p["fixed"]))
        elif p.get("buckets"):
            lens.update(int(b) for b in p["buckets"])
        else:
            raise ValueError("a prompt length needs `fixed` or `buckets`: "
                             "each length is a shape to warm up")
    return sorted(lens)
