"""Tiled online-softmax attention (FlashAttention) as a Pallas TPU kernel.

Prefill at 32k context is the compute hot-spot of every attention arch in the
assigned pool; materializing S×S scores at 32k is ~2 GB/head — far beyond
VMEM. The kernel streams KV blocks through VMEM with the online-softmax
recurrence, keeping a (Bq, D) accumulator and (Bq,) running max/denominator
in scratch.

GQA is handled *inside the BlockSpec index maps* (kv block index = h // group)
so grouped KV heads are never materialized per-query-head. Supports causal
and sliding-window (RG-LRU local attention) masking and tail padding.

TPU notes: scratch running stats are kept as (Bq, 128) lane-replicated tiles
(the canonical TPU layout for per-row scalars); score/accumulate matmuls hit
the MXU with (Bq, D)·(D, Bk) and (Bq, Bk)·(Bk, D) shapes — keep Bq, Bk, D
multiples of 128 for full tiles.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import default_interpret

NEG_INF = float("-inf")


def _flash_kernel(
    q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref,
    *,
    sm_scale: float,
    causal: bool,
    window: Optional[int],
    block_q: int,
    block_kv: int,
    kv_len: int,
    num_kv_blocks: int,
):
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0, 0].astype(jnp.float32)            # [Bq, D]
    k = k_ref[0, 0].astype(jnp.float32)            # [Bk, D]
    v = v_ref[0, 0].astype(jnp.float32)            # [Bk, D]

    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * sm_scale                                    # [Bq, Bk]

    qpos = iq * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_kv), 0)
    kpos = ik * block_kv + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_kv), 1)
    mask = kpos < kv_len                            # tail padding
    if causal:
        mask &= qpos >= kpos
    if window is not None:
        mask &= (qpos - kpos) < window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[:, :1]                           # [Bq, 1]
    l_prev = l_ref[:, :1]
    m_cur = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    safe_m = jnp.where(jnp.isfinite(m_cur), m_cur, 0.0)
    alpha = jnp.exp(m_prev - safe_m)                # 0 when m_prev == -inf
    p = jnp.exp(s - safe_m)                         # 0 where s == -inf
    l_cur = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    m_ref[...] = jnp.broadcast_to(m_cur, m_ref.shape)
    l_ref[...] = jnp.broadcast_to(l_cur, l_ref.shape)

    @pl.when(ik == num_kv_blocks - 1)
    def _finalize():
        lsum = l_ref[:, :1]
        o_ref[0, 0] = jnp.where(
            lsum > 0, acc_ref[...] / jnp.where(lsum > 0, lsum, 1.0), 0.0
        ).astype(o_ref.dtype)


def flash_attention(
    q: jnp.ndarray,                  # [B, H, Sq, D]
    k: jnp.ndarray,                  # [B, Hkv, Skv, D]
    v: jnp.ndarray,                  # [B, Hkv, Skv, D]
    *,
    causal: bool = True,
    window: Optional[int] = None,
    sm_scale: Optional[float] = None,
    block_q: int = 128,
    block_kv: int = 128,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Causal/windowed GQA attention, ``q`` [B, H, Sq, D] over ``k``/``v``
    [B, Hkv, Skv, D]. ``interpret=None`` resolves through
    :func:`repro.kernels.default_interpret`: compiled on TPU, interpret
    mode elsewhere."""
    if interpret is None:
        interpret = default_interpret()
    b, h, sq, d = q.shape
    _, hkv, skv, _ = k.shape
    assert h % hkv == 0, "query heads must be a multiple of kv heads"
    group = h // hkv
    if sm_scale is None:
        sm_scale = d ** -0.5

    block_q = min(block_q, max(8, sq))
    block_kv = min(block_kv, max(8, skv))
    pad_q = -sq % block_q
    pad_kv = -skv % block_kv
    qp = jnp.pad(q, ((0, 0), (0, 0), (0, pad_q), (0, 0)))
    kp = jnp.pad(k, ((0, 0), (0, 0), (0, pad_kv), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, 0), (0, pad_kv), (0, 0)))
    nq = qp.shape[2] // block_q
    nk = kp.shape[2] // block_kv

    kernel = functools.partial(
        _flash_kernel,
        sm_scale=sm_scale,
        causal=causal,
        window=window,
        block_q=block_q,
        block_kv=block_kv,
        kv_len=skv,
        num_kv_blocks=nk,
    )
    out = pl.pallas_call(
        kernel,
        grid=(b, h, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d), lambda b_, h_, iq, ik: (b_, h_, iq, 0)),
            pl.BlockSpec(
                (1, 1, block_kv, d),
                lambda b_, h_, iq, ik, g=group: (b_, h_ // g, ik, 0),
            ),
            pl.BlockSpec(
                (1, 1, block_kv, d),
                lambda b_, h_, iq, ik, g=group: (b_, h_ // g, ik, 0),
            ),
        ],
        out_specs=pl.BlockSpec(
            (1, 1, block_q, d), lambda b_, h_, iq, ik: (b_, h_, iq, 0)
        ),
        out_shape=jax.ShapeDtypeStruct(qp.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
        ],
        interpret=interpret,
    )(qp, kp, vp)
    return out[:, :, :sq, :]

