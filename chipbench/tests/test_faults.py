"""A run with the timed path broken underneath comes out not correct: the
harness's look for a chip is skipped (CPU rehearsal sizes) and the rest of
a run is driven, once for each fault the cell can have. One chip: no cell
has an exchange between chips to leave out."""
import dataclasses

import numpy as np
import pytest

import repro.core as core
from chipbench import run as harness
from chipbench.systems import serve as serve_system
from repro.core import engine
from repro.serve.fused_step import FusedServeLoop


def run_cell(workload, seconds=1.0):
    return harness.run(workload, 2**31 + 3, seconds, False, rehearse=True)


def test_sound_runs_are_correct():
    assert run_cell("sssp_er10k_solo")["correct"] is True
    assert run_cell("sssp_er10k_batch8")["correct"] is True


def test_sssp_phase_returning_its_state_unchanged(monkeypatch):
    real = engine._phase

    def stuck(state, *args, **kw):
        _new, stats = real(state, *args, **kw)
        return state, stats

    monkeypatch.setattr(engine, "_phase", stuck)
    res = run_cell("sssp_er10k_solo")
    assert res["correct"] is False
    assert res["checks"]["unfinished"]["value"] > 0


def test_sssp_distance_altered_where_produced(monkeypatch):
    real = core.run_sssp

    def altered(*args, **kw):
        run = real(*args, **kw)
        dist = run.dist.copy()
        dist[3] *= 1.001
        return dataclasses.replace(run, dist=dist)

    monkeypatch.setattr(core, "run_sssp", altered)
    res = run_cell("sssp_er10k_solo")
    assert res["correct"] is False
    assert res["checks"]["dist_rel_gap"]["value"] > 1e-4


def test_sssp_half_the_batch_left_out(monkeypatch):
    real = core.run_sssp_batched

    def half(ws, *, seeds, finals, **kw):
        g = len(seeds) // 2
        res = real(np.asarray(ws)[:g], seeds=seeds[:g], finals=finals[:g],
                   **kw)
        return dataclasses.replace(res, runs=res.runs + res.runs)

    monkeypatch.setattr(core, "run_sssp_batched", half)
    res = run_cell("sssp_er10k_batch8")
    assert res["correct"] is False


@pytest.fixture
def short_drain(monkeypatch):
    monkeypatch.setattr(serve_system, "DRAIN_S", 1.0)


def _in_window(monkeypatch, install):
    """Break the timed path once set-up is done: ``install(monkeypatch)``
    runs when the window opens."""
    real = serve_system.window

    def window(ctx, st):
        install(monkeypatch)
        return real(ctx, st)

    monkeypatch.setattr(serve_system, "window", window)


def _edit_steps(edit):
    def install(mp):
        real = FusedServeLoop.run_steps

        def run_steps(self, n):
            return [edit(self, rec) for rec in real(self, n)]

        mp.setattr(FusedServeLoop, "run_steps", run_steps)
    return install


def test_serve_token_altered_where_produced(monkeypatch):
    def edit(loop, rec):
        rec.tokens[:] = [(s, it, (tok + 1) % 512) for s, it, tok in rec.tokens]
        return rec

    _in_window(monkeypatch, _edit_steps(edit))
    res = run_cell("serve_qwen3_steady", 3.0)
    assert res["correct"] is False
    assert res["checks"]["logit_gap"]["value"] > 0.2


def test_serve_step_returning_its_state_unchanged(monkeypatch, short_drain):
    from repro.serve.engine import ServeEngine

    _in_window(monkeypatch,
               lambda mp: mp.setattr(ServeEngine, "step", lambda self: []))
    res = run_cell("serve_qwen3_steady", 2.0)
    assert res["correct"] is False
    assert res["checks"]["undelivered"]["value"] > 0


def test_serve_half_the_batch_left_out(monkeypatch, short_drain):
    def edit(loop, rec):
        # every other decode slot's tokens never reach the client
        rec.tokens[:] = [t for t in rec.tokens if t[0] % 2 == 0]
        rec.finished[:] = [f for f in rec.finished if f[0] % 2 == 0]
        return rec

    _in_window(monkeypatch, _edit_steps(edit))
    res = run_cell("serve_qwen3_steady", 3.0)
    assert res["correct"] is False
