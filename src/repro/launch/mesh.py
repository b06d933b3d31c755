"""Production meshes and logical→physical axis rules.

Defined as FUNCTIONS (not module constants) so importing this module never
touches jax device state."""
from __future__ import annotations

from typing import Dict, Sequence

import jax
from jax.sharding import AxisType


def make_mesh(shape: Sequence[int], axes: Sequence[str]):
    """``jax.make_mesh`` with every axis ``Auto`` (GSPMD-propagated
    shardings), the mode all of this repo's meshes are written for."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def logical_rules(multi_pod: bool = False) -> Dict[str, object]:
    """fsdp/data_b span the full DP domain (pod × data); tensor = TP/EP.
    expert_dp = the intra-pod data axis: experts shard over
    (expert_dp × tensor) = 256 ways on both meshes (pod replicates experts,
    so cross-pod traffic stays DP-gradient-only)."""
    if multi_pod:
        return {
            "fsdp": ("pod", "data"),
            "data_b": ("pod", "data"),
            "tensor": "model",
            "expert_dp": "data",
        }
    return {"fsdp": "data", "data_b": "data", "tensor": "model",
            "expert_dp": "data"}


def make_test_mesh(shape=(2, 2), axes=("data", "model")):
    """Small mesh for multi-device unit tests (host platform)."""
    return make_mesh(shape, axes)


BATCH_AXIS = "batch"


def make_batch_mesh(num_devices: int | None = None):
    """1-D mesh over the ``batch`` axis: B independent scheduler/pool
    instances spread across D devices with zero cross-device traffic between
    instances (core/sharded_batch.py). Defaults to all local devices."""
    d = len(jax.devices()) if num_devices is None else num_devices
    return make_mesh((d,), (BATCH_AXIS,))


def make_production_batch_mesh(
    *, multi_pod: bool = False, batch: int = 2, data: int = 16,
    model: int = 16,
):
    """Compose the ``batch`` pool axis with :func:`make_production_mesh`'s
    axes: ``(batch, [pod,] data, model)``. The serving layout of DESIGN.md
    §9 — decode-cache slots and the device-resident admission pool shard
    over the leading ``batch`` axis (each device group admits the slots it
    decodes), the model shards over the trailing (pod ×) data × model axes
    exactly as :func:`logical_rules` assigns them. Defaults are
    production-scale; pass small ``batch``/``data``/``model`` for host tests
    (e.g. ``batch=2, data=2, model=2`` under 8 forced host devices)."""
    shape = (batch, 2, data, model) if multi_pod else (batch, data, model)
    axes = ((BATCH_AXIS, "pod", "data", "model") if multi_pod
            else (BATCH_AXIS, "data", "model"))
    return make_mesh(shape, axes)


def make_test_production_batch_mesh(*, multi_pod: bool = False):
    """The 8-device (2 × 2 × 2) batch × data × model mesh every multi-device
    serving selftest runs under (subprocesses forced to 8 host devices via
    XLA_FLAGS): the smallest mesh that exercises the full composed-axis
    placement of :func:`make_production_batch_mesh` — admission pool and
    decode slots sharded over ``batch``, model over data × model.

    ``multi_pod=True`` reshapes the same 8 devices to the 4-axis
    (2 × 2 × 2 × 1) ``batch × pod × data × model`` mesh — the smallest mesh
    with a real ``pod`` axis, which the cross-pod block-stealing selftest
    (``python -m repro.core.sharded_batch --selftest-pod``, DESIGN.md §14.1)
    runs its steal collectives over."""
    if multi_pod:
        return make_production_batch_mesh(
            multi_pod=True, batch=2, data=2, model=1)
    return make_production_batch_mesh(batch=2, data=2, model=2)


def make_batch_place_mesh(batch: int, place: int):
    """2-D (batch × place) mesh composing the instance axis with the
    explicit-collective engine's ``place`` axis (core/distributed.py): B
    scheduler instances, each spanning ``place`` devices. Instance traffic is
    zero on ``batch``; the ρ-bounded publication/proposal collectives of each
    instance stay inside its ``place`` sub-mesh."""
    from repro.core.distributed import AXIS as PLACE_AXIS

    return make_mesh((batch, place), (BATCH_AXIS, PLACE_AXIS))
