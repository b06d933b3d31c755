"""Device-sharded batched engine + natively-batched kernel (ISSUE 2 tentpole
contract):

  * ``relaxed_topk_batched`` (one 2-D-grid kernel launch) == a loop of
    per-instance ``relaxed_topk`` calls, bit-for-bit, for both the jnp
    reference backend and Pallas in interpret mode,
  * batched ``phase_pop`` with the kernel-path backend == a loop of
    single-instance pops (the PR 1 equivalence, now through the natively
    batched arbitration),
  * sharded == single-device batched bit-identity across 8 forced host
    devices — B divisible by D and the B % D != 0 padded case — via the
    ``sharded_batch`` selftest subprocess (device count locks at jax init),
  * the interpret-mode default footgun stays fixed: ``relaxed_topk``'s
    ``interpret`` default routes through the backend logic instead of being
    hardwired True.
"""
import inspect
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import batched, kpriority as kp
from repro.kernels import default_interpret
from repro.kernels.ops import flash_attention
from repro.kernels.relaxed_topk import (
    relaxed_topk,
    relaxed_topk_batched,
    topk_select_batched,
)
from repro.kernels.ref import relaxed_topk_batched_ref, relaxed_topk_ref


# ---------------------------------------------------------------------------
# batched kernel == per-instance kernel
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", ["ref", "pallas_interpret"])
@pytest.mark.parametrize("bn,p,c", [((4, 1000), 16, 8), ((3, 512), 32, 32),
                                    ((2, 300), 8, 2)])
def test_batched_kernel_matches_per_instance(backend, bn, p, c):
    b, n = bn
    x = jax.random.normal(jax.random.PRNGKey(n + p), (b, n))
    bv, bi = topk_select_batched(x, p, c=c, block_size=256, backend=backend)
    assert bv.shape == (b, p) and bi.shape == (b, p)
    for i in range(b):
        if backend == "ref":
            v, j = relaxed_topk_ref(x[i], p, c=c, block_size=256)
        else:
            v, j = relaxed_topk(x[i], p, c=c, block_size=256, interpret=True)
        np.testing.assert_array_equal(np.asarray(bv[i]), np.asarray(v))
        np.testing.assert_array_equal(np.asarray(bi[i]), np.asarray(j))


def _assert_backends_agree(x):
    x = jnp.asarray(x)
    pv, pi = relaxed_topk_batched(x, 12, c=4, block_size=128, interpret=True)
    rv, ri = relaxed_topk_batched_ref(x, 12, c=4, block_size=128)
    np.testing.assert_array_equal(np.asarray(pv), np.asarray(rv))
    np.testing.assert_array_equal(np.asarray(pi), np.asarray(ri))


def test_batched_kernel_backends_agree():
    """Pallas (interpret) and the jnp oracle share the deterministic
    tie-break: bit-identical batched selections."""
    _assert_backends_agree(
        np.random.default_rng(0).integers(0, 5, (4, 700)).astype(np.float32)
    )  # heavy ties


def test_batched_kernel_backends_agree_mostly_masked():
    """The same when most entries are -inf (the scheduler's masked scores):
    both return distinct indices in ascending order for the -inf ties."""
    rng = np.random.default_rng(0)
    x = rng.integers(0, 5, (4, 700)).astype(np.float32)
    x[rng.random(x.shape) < 0.995] = -np.inf
    _assert_backends_agree(x)


def test_batched_kernel_p_larger_than_n():
    x = jax.random.normal(jax.random.PRNGKey(1), (3, 100))
    v, i = relaxed_topk_batched(x, 128, c=128, block_size=128, interpret=True)
    assert v.shape == (3, 128) and i.shape == (3, 128)
    # all n real items selected; the tail is -inf block padding (same
    # contract as the 1-D kernel, see test_kernels.py)
    assert np.isfinite(np.asarray(v)[:, :100]).all()
    assert np.all(np.asarray(v)[:, 100:] == -np.inf)


# ---------------------------------------------------------------------------
# interpret-mode default: routed through backend logic, not hardwired True
# ---------------------------------------------------------------------------

def test_interpret_default_routes_through_backend_logic():
    for fn in (relaxed_topk, relaxed_topk_batched, flash_attention):
        assert inspect.signature(fn).parameters["interpret"].default is None
    # on the CPU container the resolved default must be interpret mode
    # (the kernel only compiles under Mosaic); on TPU it must compile —
    # exactly topk_select's auto-backend split
    expected = jax.default_backend() != "tpu"
    assert default_interpret() is expected
    x = jax.random.normal(jax.random.PRNGKey(2), (400,))
    v_default, i_default = relaxed_topk(x, 8, c=8, block_size=128)
    v_explicit, i_explicit = relaxed_topk(
        x, 8, c=8, block_size=128, interpret=expected
    )
    np.testing.assert_array_equal(np.asarray(v_default),
                                  np.asarray(v_explicit))
    np.testing.assert_array_equal(np.asarray(i_default),
                                  np.asarray(i_explicit))


# ---------------------------------------------------------------------------
# natively-batched fused arbitration == per-instance loop (kernel path)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("policy,k", [
    (kp.Policy.IDEAL, 2),
    (kp.Policy.CENTRALIZED, 3),
    (kp.Policy.HYBRID, 3),
])
def test_batched_phase_pop_kernel_backend_matches_loop(policy, k):
    """The batched fused arbitration (ONE relaxed_topk_batched launch) must
    equal per-instance phase_pop for the interpret-mode kernel backend too —
    the batched kernel is on the arbitration hot path, not just vmap."""
    batch, m, places = 3, 96, 4
    rng = np.random.default_rng(13)
    bstate = batched.init_pool(m, places, batch=batch)
    states = [kp.init_pool(m, places) for _ in range(batch)]
    for t in range(4):
        mask = jnp.asarray(rng.random((batch, m)) < 0.3)
        prios = jnp.asarray(rng.random((batch, m)).astype(np.float32))
        creators = jnp.asarray(
            rng.integers(0, places, (batch, m)).astype(np.int32))
        push_keys = jnp.stack(
            [jax.random.PRNGKey(70 * t + b) for b in range(batch)])
        pop_keys = jnp.stack(
            [jax.random.PRNGKey(400 * t + b) for b in range(batch)])
        bstate = batched.push(
            bstate, mask, prios, creators, k=k, policy=policy, key=push_keys)
        bstate, bres = batched.phase_pop(
            bstate, pop_keys, num_places=places, k=k, policy=policy,
            topk_backend="pallas_interpret", block_size=128,
        )
        for b in range(batch):
            states[b] = kp.push(
                states[b], mask[b], prios[b], creators[b],
                k=k, policy=policy, key=push_keys[b])
            states[b], res = kp.phase_pop(
                states[b], pop_keys[b], num_places=places, k=k, policy=policy,
                topk_backend="pallas_interpret", block_size=128,
            )
            np.testing.assert_array_equal(
                np.asarray(bres.slot[b]), np.asarray(res.slot))
            np.testing.assert_array_equal(
                np.asarray(bres.valid[b]), np.asarray(res.valid))
            for name, bl, sl in zip(
                kp.PoolState._fields, bstate, states[b]
            ):
                np.testing.assert_array_equal(
                    np.asarray(bl[b]), np.asarray(sl),
                    err_msg=f"field {name} instance {b} phase {t}")


# ---------------------------------------------------------------------------
# phase-chunked driver == phase-per-dispatch driver
# ---------------------------------------------------------------------------

def test_run_sssp_batched_phase_chunk_identical():
    from repro.core.engine import run_sssp_batched
    from repro.core.sssp import dijkstra_ref, make_er_graph

    ws = np.stack([make_er_graph(60 + g, 80, 0.15) for g in range(3)])
    finals = np.stack([dijkstra_ref(w) for w in ws])
    kwargs = dict(num_places=4, k=2, policy=kp.Policy.HYBRID,
                  seeds=[0, 1, 2], finals=finals)
    a = run_sssp_batched(ws, **kwargs)
    b = run_sssp_batched(ws, phase_chunk=8, **kwargs)
    for g in range(3):
        np.testing.assert_array_equal(a.runs[g].dist, b.runs[g].dist)
        assert a.runs[g].phases == b.runs[g].phases
        assert a.runs[g].total_relaxed == b.runs[g].total_relaxed
        assert a.runs[g].total_pushes == b.runs[g].total_pushes
        assert a.runs[g].correct and b.runs[g].correct


def test_run_sssp_batched_phase_chunk_respects_max_phases():
    """The hard cap truncates a chunked run bit-identically to an unchunked
    one (the final chunk shrinks; state never advances past the cap)."""
    from repro.core.engine import run_sssp_batched
    from repro.core.sssp import dijkstra_ref, make_er_graph

    ws = np.stack([make_er_graph(70 + g, 80, 0.15) for g in range(2)])
    finals = np.stack([dijkstra_ref(w) for w in ws])
    kwargs = dict(num_places=4, k=2, policy=kp.Policy.HYBRID,
                  seeds=[0, 1], finals=finals, max_phases=10)
    a = run_sssp_batched(ws, **kwargs)
    b = run_sssp_batched(ws, phase_chunk=16, **kwargs)   # chunk > cap
    assert a.joint_phases == b.joint_phases == 10
    for g in range(2):
        np.testing.assert_array_equal(a.runs[g].dist, b.runs[g].dist)
        assert a.runs[g].phases == b.runs[g].phases
        for f, col in a.runs[g].per_phase.items():
            np.testing.assert_array_equal(col, b.runs[g].per_phase[f], f)


# ---------------------------------------------------------------------------
# sharded == batched across 8 devices (subprocess: device count locks at init)
# ---------------------------------------------------------------------------

def test_sharded_selftest_8_devices():
    """Pins sharded == single-device batched bit-identity for B == D and the
    B % D != 0 padded case, sharded SSSP == batched SSSP, and exactly-once on
    the composed (batch × place) engine."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    out = subprocess.run(
        [sys.executable, "-m", "repro.core.sharded_batch", "--selftest"],
        capture_output=True, text=True, env=env, timeout=600,
    )
    assert "SHARDED_OK" in out.stdout, (out.stdout[-500:], out.stderr[-2000:])
    assert "SHARDED_POOL_OK B=6" in out.stdout, out.stdout[-500:]
    assert "SHARDED_SSSP_OK G=5" in out.stdout, out.stdout[-500:]
    assert "SERVE_MESH_OK" in out.stdout, out.stdout[-500:]


def test_pod_steal_selftest_8_devices():
    """Pins the cross-pod block-stealing plane (ISSUE 8 tentpole) on the
    4-axis multi-pod test mesh: steal decisions, pop streams, and full state
    records bit-identical to the HostPodQueues twin, exactly-once at drain,
    and at least one steal actually fired."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    out = subprocess.run(
        [sys.executable, "-m", "repro.core.sharded_batch", "--selftest-pod"],
        capture_output=True, text=True, env=env, timeout=600,
    )
    assert "POD_STEAL_OK" in out.stdout, (out.stdout[-500:],
                                          out.stderr[-2000:])


# ---------------------------------------------------------------------------
# serve engine mesh= path (1-device mesh: placement-only smoke)
# ---------------------------------------------------------------------------

def test_serve_engine_mesh_path():
    from repro.configs import get_reduced
    from repro.launch.mesh import make_batch_mesh
    from repro.models import materialize, model_p
    from repro.serve.config import ServeConfig
    from repro.serve.engine import Request, ServeEngine

    cfg = get_reduced("qwen3_1_7b")
    params = materialize(jax.random.PRNGKey(0), model_p(cfg))
    mesh = make_batch_mesh(1)
    eng = ServeEngine(cfg, params, slots=2, max_len=32, frontends=2, k=2,
                      config=ServeConfig(mesh=mesh))
    rng = np.random.default_rng(0)
    for i in range(3):
        eng.submit(
            Request(rid=i,
                    tokens=rng.integers(0, cfg.vocab_size, 6).astype(np.int32),
                    max_new=4, priority=float(i)),
            frontend=i % 2,
        )
    eng.flush_frontends()
    done = eng.run()
    assert len(done) == 3
    assert all(len(r.out) == 4 for r in done)
