"""Checkpointing: atomicity, keep-N, async, preemption-resume determinism,
elastic resharding (subprocess with a multi-device mesh)."""
import os
import subprocess
import sys
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint.manager import CheckpointManager


def tree():
    return {"a": jnp.arange(12, dtype=jnp.float32).reshape(3, 4),
            "b": {"c": jnp.ones((5,), jnp.bfloat16)}}


def test_save_restore_bitwise():
    with tempfile.TemporaryDirectory() as d:
        mgr = CheckpointManager(d)
        t = tree()
        mgr.save(3, t)
        like = jax.tree.map(lambda x: np.zeros(x.shape, x.dtype), t)
        r = mgr.restore(3, like)
        for a, b in zip(jax.tree.leaves(t), jax.tree.leaves(r)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_keep_n_gc():
    with tempfile.TemporaryDirectory() as d:
        mgr = CheckpointManager(d, keep=2)
        for s in (1, 2, 3, 4):
            mgr.save(s, tree())
        assert mgr.all_steps() == [3, 4]


def test_async_save():
    with tempfile.TemporaryDirectory() as d:
        mgr = CheckpointManager(d)
        mgr.save(1, tree(), blocking=False)
        mgr.wait()
        assert mgr.latest_step() == 1


@pytest.mark.slow
def test_preemption_resume_bitwise():
    """Train 12 steps; kill at 6; resume; final params identical."""
    from repro.configs import get_reduced
    from repro.train.loop import train
    import dataclasses
    cfg = dataclasses.replace(get_reduced("qwen3_1_7b"), num_layers=1)
    with tempfile.TemporaryDirectory() as d:
        ck = os.path.join(d, "ck")
        r_full = train(cfg, steps=12, ckpt_dir=None, log_every=12)
        # run to 6 with checkpointing, then "preempt" and resume to 12
        train(cfg, steps=6, ckpt_dir=ck, ckpt_every=6, log_every=6)
        r_resumed = train(cfg, steps=12, ckpt_dir=ck, ckpt_every=6,
                          log_every=12)
        assert r_resumed.resumed_from == 6
        assert abs(r_full.losses[-1][1] - r_resumed.losses[-1][1]) < 1e-5


ELASTIC_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys
import tempfile
import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.checkpoint.manager import CheckpointManager
from repro.launch.mesh import make_mesh

d = sys.argv[1]
t = {"w": jnp.arange(64, dtype=jnp.float32).reshape(8, 8)}
mesh_a = make_mesh((2, 4), ("data", "model"))
sh_a = {"w": NamedSharding(mesh_a, P("data", "model"))}
t_a = jax.device_put(t["w"], sh_a["w"])
mgr = CheckpointManager(d)
mgr.save(1, {"w": t_a})
# elastic: restore onto a DIFFERENT mesh shape (simulates node loss 8->4)
mesh_b = make_mesh((4, 1), ("data", "model"))
sh_b = {"w": NamedSharding(mesh_b, P("data", "model"))}
like = {"w": np.zeros((8, 8), np.float32)}
r = mgr.restore_sharded(1, like, sh_b)
assert r["w"].sharding == sh_b["w"]
np.testing.assert_array_equal(np.asarray(r["w"]), np.asarray(t["w"]))
print("ELASTIC_OK")
"""


@pytest.mark.slow
def test_elastic_reshard_subprocess():
    """Save on a (2,4) mesh, restore on (4,1): elastic scaling after node
    failure. Subprocess because device count is locked at jax init."""
    with tempfile.TemporaryDirectory() as d:
        env = dict(os.environ)
        env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
        out = subprocess.run(
            [sys.executable, "-c", ELASTIC_SCRIPT, d],
            capture_output=True, text=True, env=env, timeout=300,
        )
        assert "ELASTIC_OK" in out.stdout, out.stderr[-2000:]
