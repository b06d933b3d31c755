"""relaxed_topk — ρ-relaxed priority selection as a Pallas TPU kernel.

This is the paper's idea turned into a TPU-native compute kernel. Selecting
the P best of N priorities *exactly* requires a global sort/merge — a bad fit
for a machine built around block-local VMEM compute. Under **structural
ρ-relaxation** (paper §5.3: a pop may never ignore more than ρ items,
regardless of age) we may instead:

  1. tile the N priorities into B VMEM blocks (one grid step each),
  2. extract each block's local top-c (c iterations of max+mask on the VPU —
     no sort, no cross-block traffic),
  3. take the exact top-P of the B·c candidates (tiny).

Guarantee (proved in tests): the selected set ignores at most ρ = max(0, P−c)
items — every ignored item is dominated by ≥ c better items *inside its own
block*. Block ↔ place, c ↔ the per-place publication budget k of the hybrid
structure: the kernel is the hybrid k-priority pop with one block per place.
c = P recovers the exact (ρ = 0, "ideal") selection.

Convention: LARGER value = higher priority (negate for min-priority pops).
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import default_interpret

NEG_INF = float("-inf")


def _block_topc_kernel(x_ref, vals_ref, idx_ref, *, c: int, block_size: int):
    """Top-c values (+global indices) of one (instance, block) grid cell.

    Grid axis 0 is the instance, axis 1 the block. The block arrives as a
    (block_size // 128, 128) tile, so every reduction and iota is 2-D. Each
    of the c rounds takes the max, then the lowest not-yet-taken flat index
    attaining it (so ties, -inf padding included, resolve to distinct
    indices in ascending order, exactly as ``lax.top_k``), and masks that
    element out. O(c · block_size) VPU work, no sort network. Results are
    gathered in one lane vector per output and written with a single
    aligned store (Mosaic has no scalar stores to VMEM).
    """
    j = pl.program_id(1)
    rows = block_size // 128
    lanes = vals_ref.shape[-1]
    x = x_ref[0, 0]                                           # [rows, 128]
    gidx = (
        jax.lax.broadcasted_iota(jnp.int32, (rows, 128), 0) * 128
        + jax.lax.broadcasted_iota(jnp.int32, (rows, 128), 1)
        + j * block_size
    )
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, lanes), 1)
    taken = jnp.iinfo(jnp.int32).max

    def body(i, carry):
        x, g, vals, idx = carry
        m = jnp.max(jnp.max(x, axis=1, keepdims=True), axis=0, keepdims=True)
        cand = jnp.where(x >= m, g, taken)
        jj = jnp.min(jnp.min(cand, axis=1, keepdims=True), axis=0,
                     keepdims=True)                           # [1, 1]
        hit = g == jj
        x = jnp.where(hit, NEG_INF, x)
        g = jnp.where(hit, taken, g)
        vals = jnp.where(lane == i, m, vals)
        idx = jnp.where(lane == i, jj, idx)
        return x, g, vals, idx

    vals0 = jnp.full((1, lanes), NEG_INF, jnp.float32)
    idx0 = jnp.full((1, lanes), -1, jnp.int32)
    _, _, vals, idx = jax.lax.fori_loop(0, c, body, (x, gidx, vals0, idx0))
    vals_ref[0, 0] = vals
    idx_ref[0, 0] = idx


def relaxed_topk(
    x: jnp.ndarray,
    p: int,
    *,
    c: int | None = None,
    block_size: int = 1024,
    interpret: bool | None = None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """ρ-relaxed top-p of a 1-D priority array.

    Returns (values[p], indices[p]) sorted descending. ρ = max(0, p - c).
    ``x`` is padded with -inf to a multiple of ``block_size`` (padding can
    never be selected unless p > N). ``interpret=None`` (default) resolves
    through :func:`repro.kernels.default_interpret`: compiled on TPU,
    interpret elsewhere — a direct caller on TPU gets the compiled kernel,
    not silent interpret-mode Pallas. The B = 1 slice of
    :func:`relaxed_topk_batched` (one kernel, no drift).
    """
    v, i = relaxed_topk_batched(
        x[None], p, c=c, block_size=block_size, interpret=interpret
    )
    return v[0], i[0]


def relaxed_topk_batched(
    x: jnp.ndarray,
    p: int,
    *,
    c: int | None = None,
    block_size: int = 1024,
    interpret: bool | None = None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """ρ-relaxed top-p of B independent priority arrays — ONE kernel launch.

    ``x`` is [B, N]; returns (values[B, p], indices[B, p]), row b bit-identical
    to ``relaxed_topk(x[b], p, ...)``. The Pallas grid is 2-D over
    (instance, block): all B instances' block-local top-c extractions run in
    the same launch (no per-instance host-side Python, no vmap-lifted
    kernel), then one batched exact top-p merges each row's B·c candidates.

    Layout (Mosaic's (8, 128) block rule): the input is viewed as
    [B, nb, block_size // 128, 128] and each grid cell reads one whole
    [block_size // 128, 128] tile; each cell writes its c candidates as one
    [1, L] lane row of a [B, nb, 1, L] output, L = c rounded up to 128.
    Both blocks equal the array's trailing two dims, so any B, nb and
    lane-aligned block_size is legal.
    """
    if interpret is None:
        interpret = default_interpret()
    if c is None:
        c = p
    batch, n = x.shape
    assert block_size % 128 == 0, "block_size must be lane-aligned (128)"
    n_pad = -n % block_size
    xp = jnp.pad(
        x.astype(jnp.float32), ((0, 0), (0, n_pad)), constant_values=NEG_INF
    )
    nb = xp.shape[1] // block_size
    rows = block_size // 128
    c_eff = min(c, block_size)
    lanes = -(-c_eff // 128) * 128

    vals, idx = pl.pallas_call(
        functools.partial(_block_topc_kernel, c=c_eff, block_size=block_size),
        grid=(batch, nb),
        in_specs=[
            pl.BlockSpec((1, 1, rows, 128), lambda b, j: (b, j, 0, 0))
        ],
        out_specs=[
            pl.BlockSpec((1, 1, 1, lanes), lambda b, j: (b, j, 0, 0)),
            pl.BlockSpec((1, 1, 1, lanes), lambda b, j: (b, j, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((batch, nb, 1, lanes), jnp.float32),
            jax.ShapeDtypeStruct((batch, nb, 1, lanes), jnp.int32),
        ],
        interpret=interpret,
    )(xp.reshape(batch, nb, rows, 128))

    return _merge_topp_batched(
        vals[:, :, 0, :c_eff], idx[:, :, 0, :c_eff], p
    )


def _merge_topp_batched(
    vals: jnp.ndarray, idx: jnp.ndarray, p: int
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Exact per-row top-p over each instance's [nb, c] candidates (tiny)."""
    batch = vals.shape[0]
    flat_v = vals.reshape(batch, -1)
    flat_i = idx.reshape(batch, -1)
    top_v, pos = jax.lax.top_k(flat_v, min(p, flat_v.shape[1]))
    top_i = jnp.take_along_axis(flat_i, pos, axis=1)
    if top_v.shape[1] < p:  # degenerate: fewer candidates than p
        pad = p - top_v.shape[1]
        top_v = jnp.pad(top_v, ((0, 0), (0, pad)), constant_values=NEG_INF)
        top_i = jnp.pad(top_i, ((0, 0), (0, pad)), constant_values=-1)
    return top_v, top_i


# ---------------------------------------------------------------------------
# backend-selecting entry point (used by core.kpriority's fused arbitration)
# ---------------------------------------------------------------------------

def topk_select(
    x: jnp.ndarray,
    p: int,
    *,
    c: int | None = None,
    block_size: int = 1024,
    backend: str = "auto",
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """ρ-relaxed top-p with an explicit backend choice.

    ``backend``:
      * ``"auto"``             — Pallas (compiled) on TPU, pure-jnp reference
                                 everywhere else (interpret-mode Pallas is far
                                 too slow to sit on a scheduler's hot path),
      * ``"pallas"``           — compiled Pallas kernel,
      * ``"pallas_interpret"`` — Pallas in interpret mode (CPU validation),
      * ``"ref"``              — the pure-jnp oracle from kernels/ref.py.

    All backends share the deterministic lowest-index tie-break, so the
    selection is bit-identical across them (tests assert this).
    """
    if backend == "auto":
        backend = "pallas" if jax.default_backend() == "tpu" else "ref"
    if backend == "ref":
        from repro.kernels.ref import relaxed_topk_ref

        return relaxed_topk_ref(x, p, c=c, block_size=block_size)
    if backend in ("pallas", "pallas_interpret"):
        return relaxed_topk(
            x, p, c=c, block_size=block_size,
            interpret=(backend == "pallas_interpret"),
        )
    raise ValueError(f"unknown topk backend: {backend!r}")


def topk_select_batched(
    x: jnp.ndarray,
    p: int,
    *,
    c: int | None = None,
    block_size: int = 1024,
    backend: str = "auto",
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Batched ρ-relaxed top-p ([B, N] → [B, p]) with explicit backend choice.

    Same backend semantics as :func:`topk_select`; row b of every backend is
    bit-identical to the single-instance call on ``x[b]`` (pinned in
    tests/test_sharded_batch.py), and the Pallas backends run all B instances
    as ONE 2-D-grid kernel launch.
    """
    if backend == "auto":
        backend = "pallas" if jax.default_backend() == "tpu" else "ref"
    if backend == "ref":
        from repro.kernels.ref import relaxed_topk_batched_ref

        return relaxed_topk_batched_ref(x, p, c=c, block_size=block_size)
    if backend in ("pallas", "pallas_interpret"):
        return relaxed_topk_batched(
            x, p, c=c, block_size=block_size,
            interpret=(backend == "pallas_interpret"),
        )
    raise ValueError(f"unknown topk backend: {backend!r}")
