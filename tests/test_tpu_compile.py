"""Compile the main path's kernels for a described TPU v5e, at real widths.

No chip is needed: the TPU compiler is installed and compiles for a topology
that is described, not attached. These compiles catch what interpret mode
cannot — Mosaic's block-shape rules, unsupported stores, VMEM limits — at no
chip time. Nothing runs, so they say nothing about results or speed.

The topology is described inside a module-scoped fixture (never at import:
only one process may load the TPU library, and every pytest worker imports
every test file). The code under test asks ``jax.default_backend()``, which
is the CPU here, so each test passes ``interpret=False`` /
``topk_backend="pallas"`` itself.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec
from jax.sharding import SingleDeviceSharding

from repro.core import kpriority as kp

SSSP_N, SSSP_PLACES, HYBRID_K = 10_000, 80, 32


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache but
    cannot be read back without one; keep these compiles out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _shape(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _selection_c(policy):
    return kp.fused_selection_c(policy, HYBRID_K, SSSP_PLACES, SSSP_N, 1024)


@pytest.mark.parametrize("batch", [1, 4])
@pytest.mark.parametrize("policy", [kp.Policy.IDEAL, kp.Policy.HYBRID])
def test_relaxed_topk_batched_compiles(one_chip, batch, policy):
    from repro.kernels.relaxed_topk import relaxed_topk_batched

    c = _selection_c(policy)
    fn = jax.jit(lambda x: relaxed_topk_batched(
        x, SSSP_PLACES, c=c, interpret=False))
    compiled = fn.lower(
        _shape((batch, SSSP_N), jnp.float32, one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_relaxed_topk_1d_compiles(one_chip):
    from repro.kernels.relaxed_topk import relaxed_topk

    fn = jax.jit(lambda x: relaxed_topk(x, SSSP_PLACES, interpret=False))
    compiled = fn.lower(_shape((SSSP_N,), jnp.float32, one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_sssp_phase_compiles_with_kernel(one_chip):
    """One whole SSSP phase at the paper's n = 10^4, P = 80 (the dense
    weights alone are 400 MB) runs stage 1 of its pop on the kernel."""
    from repro.core import sssp as ss

    state = jax.eval_shape(
        lambda w: ss.init_sssp(w, SSSP_PLACES),
        jax.ShapeDtypeStruct((SSSP_N, SSSP_N), jnp.float32))
    state = jax.tree.map(
        lambda a: _shape(a.shape, a.dtype, one_chip), state)
    fn = jax.jit(lambda s, key, w, f: ss.sssp_phase(
        s, key, w, f, num_places=SSSP_PLACES, k=HYBRID_K,
        policy=kp.Policy.HYBRID, topk_backend="pallas"))
    compiled = fn.lower(
        state,
        _shape((2,), jnp.uint32, one_chip),
        _shape((SSSP_N, SSSP_N), jnp.float32, one_chip),
        _shape((SSSP_N,), jnp.float32, one_chip),
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_flash_attention_compiles_at_qwen3_widths(one_chip):
    """bf16, 16 query heads over 8 KV heads, head_dim 128, S = 512."""
    from repro.kernels.ops import flash_attention

    fn = jax.jit(lambda q, k, v: flash_attention(
        q, k, v, causal=True, interpret=False))
    compiled = fn.lower(
        _shape((1, 16, 512, 128), jnp.bfloat16, one_chip),
        _shape((1, 8, 512, 128), jnp.bfloat16, one_chip),
        _shape((1, 8, 512, 128), jnp.bfloat16, one_chip),
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_sharded_sssp_chunk_compiles_on_four_chips(topo):
    """Four n = 10^4 graphs over a 4-chip ``batch`` mesh: the shard_map'd
    phase chunk of ``run_sssp_batched(mesh=...)`` with the kernel inside."""
    from repro.core import engine
    from repro.core import sssp as ss

    graphs = 4
    mesh = Mesh(np.array(topo.devices), ("batch",),
                axis_types=(AxisType.Auto,))
    sharded = NamedSharding(mesh, PartitionSpec("batch"))
    state = jax.eval_shape(
        jax.vmap(functools.partial(ss.init_sssp, num_places=SSSP_PLACES)),
        jax.ShapeDtypeStruct((graphs, SSSP_N, SSSP_N), jnp.float32))
    state = jax.tree.map(
        lambda a: _shape(a.shape, a.dtype, sharded), state)
    fn = engine._phase_chunk_sharded(
        mesh, 16, SSSP_PLACES, HYBRID_K, kp.Policy.HYBRID, "fused", "pallas")
    compiled = fn.lower(
        state,
        _shape((graphs, 2), jnp.uint32, sharded),
        _shape((graphs, SSSP_N, SSSP_N), jnp.float32, sharded),
        _shape((graphs, SSSP_N), jnp.float32, sharded),
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("preemption", ["off", "margin"])
def test_serving_step_keeps_caches_in_place(one_chip, preemption):
    """The fused serving step at Qwen3-1.7B's widths, in the shape the
    serving benchmark runs (8 slots of 1024 positions, 40 staging rows, one
    step a dispatch), keeps its KV caches in place: no temporary as large
    as one slot's column of the caches, and no copy of a whole cache leaf.
    The splice writes only the admitted slots' columns, and decode updates
    the carried caches in place."""
    import re

    from repro.configs.qwen3_1_7b import CONFIG
    from repro.models import init_cache, materialize, model_p
    from repro.serve.engine import _fused_model_fns
    from repro.serve.fused_step import FusedServeLoop, _build_chunk_impl
    from repro.serve.streaming import AdmissionBuffer

    slots, max_len, frontends, cap = 8, 1024, 2, 64
    decode_fn, prefill_fn = _fused_model_fns(CONFIG, max_len)
    loop_kw = dict(slots=slots, frontends=frontends, k=4, max_len=max_len,
                   capacity=4096, buffer_cap=cap, staging_rows=40,
                   decode_fn=decode_fn, prefill_fn=prefill_fn,
                   preemption=preemption, margin=0.5, continuous=True)
    carry = jax.eval_shape(lambda: FusedServeLoop(
        caches=init_cache(CONFIG, slots, max_len), **loop_kw).carry)
    params = jax.eval_shape(
        lambda: materialize(jax.random.PRNGKey(0), model_p(CONFIG)))
    bufs = AdmissionBuffer(
        prio=jax.ShapeDtypeStruct((1, frontends, cap), jnp.float32),
        slot=jax.ShapeDtypeStruct((1, frontends, cap), jnp.int32),
        arrival=jax.ShapeDtypeStruct((1, frontends, cap), jnp.int32),
        count=jax.ShapeDtypeStruct((1, frontends), jnp.int32))
    fn = _build_chunk_impl(
        decode_fn, k=4, frontends=frontends, slots=slots, max_len=max_len,
        n=1, preempt=preemption == "margin", margin=0.5,
        rounds=slots if preemption == "margin" else 0, continuous=True)
    on_chip = functools.partial(
        jax.tree.map, lambda a: _shape(a.shape, a.dtype, one_chip))
    compiled = fn.lower(on_chip(params), on_chip(carry),
                        on_chip(bufs)).compile()
    leaves = jax.tree.leaves(carry.caches)
    column = max(a.dtype.itemsize * a.size // slots for a in leaves)
    assert compiled.memory_analysis().temp_size_in_bytes < column
    shapes = {",".join(map(str, a.shape)) for a in leaves}
    copies = re.findall(r"= \w+\[([\d,]+)\]\{[^}]*\} copy\(",
                        compiled.as_text())
    assert not shapes & set(copies), copies
