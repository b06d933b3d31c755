"""Jitted public wrappers for the Pallas kernels.

Every wrapper's ``interpret`` defaults to None, which resolves through
:func:`repro.kernels.default_interpret`: compiled on TPU, interpret mode
elsewhere. No model path calls ``flash_attention``: the models attend with
the pure-XLA ``models.attention.blockwise_attention`` (DESIGN.md §7.2).
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention import flash_attention as _flash
from repro.kernels.relaxed_topk import relaxed_topk as _rtopk
from repro.kernels.relaxed_topk import relaxed_topk_batched as _rtopk_batched


@functools.partial(
    jax.jit, static_argnames=("p", "c", "block_size", "interpret")
)
def relaxed_topk(
    x: jnp.ndarray,
    p: int,
    c: Optional[int] = None,
    block_size: int = 1024,
    interpret: Optional[bool] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """ρ-relaxed top-p (ρ = max(0, p-c)); see kernels/relaxed_topk.py."""
    return _rtopk(x, p, c=c, block_size=block_size, interpret=interpret)


@functools.partial(
    jax.jit, static_argnames=("p", "c", "block_size", "interpret")
)
def relaxed_topk_batched(
    x: jnp.ndarray,
    p: int,
    c: Optional[int] = None,
    block_size: int = 1024,
    interpret: Optional[bool] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Batched ρ-relaxed top-p ([B, N] → [B, p]), one 2-D-grid kernel launch
    for all B instances; see kernels/relaxed_topk.py."""
    return _rtopk_batched(x, p, c=c, block_size=block_size, interpret=interpret)


@functools.partial(
    jax.jit,
    static_argnames=(
        "causal", "window", "sm_scale", "block_q", "block_kv", "interpret"
    ),
)
def flash_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    causal: bool = True,
    window: Optional[int] = None,
    sm_scale: Optional[float] = None,
    block_q: int = 128,
    block_kv: int = 128,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    return _flash(
        q, k, v,
        causal=causal, window=window, sm_scale=sm_scale,
        block_q=block_q, block_kv=block_kv, interpret=interpret,
    )
