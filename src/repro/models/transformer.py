"""Backbone assembly: block kinds → period segments → scan-over-layers.

Layers are grouped into *segments* of repeating period patterns (e.g.
RecurrentGemma's (rec, rec, local)) and executed with lax.scan over stacked
parameters — HLO size is independent of depth, which keeps 61-layer dry-run
compiles tractable and is the standard production structure. Remat wraps one
period (cfg.remat == "full").

Three entry points:
  train_loss(params, cfg, batch)                  -> (loss, metrics)
  prefill(params, cfg, batch, max_len)            -> (last_logits, caches)
  decode_step(params, cfg, caches, tokens, pos)   -> (logits, caches)
"""
from __future__ import annotations

from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import shard
from repro.models.attention import (
    gqa_decode, gqa_forward, gqa_p, mla_decode, mla_forward, mla_p,
)
from repro.models.layers import (
    chunked_softmax_xent, embed, embed_p, mlp, mlp_p, rmsnorm, rmsnorm_p,
)
from repro.models.module import DATA, FSDP, P, TENSOR, stack
from repro.models.moe import moe_forward, moe_p
from repro.models.rglru import rglru_forward, rglru_p
from repro.models.ssm import ssm_forward, ssm_p

F32 = jnp.float32


# ---------------------------------------------------------------------------
# segmentation
# ---------------------------------------------------------------------------

def segments(cfg: ModelConfig) -> List[Tuple[Tuple[str, ...], int]]:
    """[(pattern, repeat_count), ...] covering all layers in order."""
    kinds = cfg.block_kinds()
    p = len(cfg.attn_pattern)
    segs: List[Tuple[Tuple[str, ...], int]] = []
    if p == 1 or (cfg.moe and cfg.moe.first_dense_layers):
        # run-length encode (handles deepseek's dense prefix)
        i = 0
        while i < len(kinds):
            j = i
            while j < len(kinds) and kinds[j] == kinds[i]:
                j += 1
            segs.append(((kinds[i],), j - i))
            i = j
    else:
        n_full = len(kinds) // p
        if n_full:
            segs.append((cfg.attn_pattern, n_full))
        tail = kinds[n_full * p :]
        if tail:
            segs.append((tuple(tail), 1))
    return segs


# ---------------------------------------------------------------------------
# parameter descriptors
# ---------------------------------------------------------------------------

def block_p(cfg: ModelConfig, kind: str) -> dict:
    d = cfg.d_model
    if kind in ("attn", "moe", "local"):
        attn = mla_p(cfg) if cfg.mla else gqa_p(cfg)
        if kind == "moe":
            ffn = moe_p(cfg)
        else:
            ffn = mlp_p(d, cfg.d_ff, cfg.mlp_style)
        return {"ln1": rmsnorm_p(d), "attn": attn, "ln2": rmsnorm_p(d), "mlp": ffn}
    if kind == "rec":
        return {"ln1": rmsnorm_p(d), "rec": rglru_p(cfg),
                "ln2": rmsnorm_p(d), "mlp": mlp_p(d, cfg.d_ff, cfg.mlp_style)}
    if kind == "ssm":
        return {"ln1": rmsnorm_p(d), "ssm": ssm_p(cfg)}
    raise ValueError(kind)


def model_p(cfg: ModelConfig) -> dict:
    d, v = cfg.d_model, cfg.vocab_size
    tree: Dict[str, Any] = {}
    if cfg.input_mode == "tokens":
        tree["embed"] = embed_p(v, d)
    if cfg.pos == "learned":
        tree["pos_embed"] = P((32768, d), (None, FSDP), init="embed")
    tree["segments"] = [
        stack({f"b{i}": block_p(cfg, kind) for i, kind in enumerate(pat)}, n)
        for pat, n in segments(cfg)
    ]
    tree["final_norm"] = rmsnorm_p(d)
    if not cfg.tie_embeddings:
        tree["head"] = P((d, v), (FSDP, TENSOR))
    if cfg.mtp:
        mtp_kind = "moe" if cfg.moe else "attn"
        tree["mtp"] = {
            "norm_h": rmsnorm_p(d),
            "norm_e": rmsnorm_p(d),
            "proj": P((2 * d, d), (FSDP, None)),
            "block": block_p(cfg, mtp_kind),
        }
    return tree


# ---------------------------------------------------------------------------
# caches
# ---------------------------------------------------------------------------

def _kind_cache(cfg: ModelConfig, kind: str, b: int, max_len: int):
    """Zero cache pytree for one layer of the given kind."""
    dh = cfg.resolved_head_dim
    hkv = cfg.num_kv_heads
    if kind in ("attn", "moe"):
        if cfg.mla:
            m = cfg.mla
            return (
                jnp.zeros((b, max_len, m.kv_lora_rank), jnp.bfloat16),
                jnp.zeros((b, max_len, m.qk_rope_head_dim), jnp.bfloat16),
            )
        return (
            jnp.zeros((b, hkv, max_len, dh), jnp.bfloat16),
            jnp.zeros((b, hkv, max_len, dh), jnp.bfloat16),
        )
    if kind == "local":
        w = min(cfg.window, max_len)
        return (
            jnp.zeros((b, hkv, w, dh), jnp.bfloat16),
            jnp.zeros((b, hkv, w, dh), jnp.bfloat16),
        )
    if kind == "rec":
        m = cfg.rglru
        dr = m.width or cfg.d_model
        return (
            jnp.zeros((b, m.d_conv - 1, dr), jnp.bfloat16),
            jnp.zeros((b, dr), F32),
        )
    if kind == "ssm":
        m = cfg.ssm
        d_in = m.expand * cfg.d_model
        nheads = d_in // m.headdim
        conv_ch = d_in + 2 * m.ngroups * m.d_state
        return (
            jnp.zeros((b, m.d_conv - 1, conv_ch), jnp.bfloat16),
            jnp.zeros((b, nheads, m.headdim, m.d_state), F32),
        )
    raise ValueError(kind)


def init_cache(cfg: ModelConfig, b: int, max_len: int):
    """Nested cache: per segment, per pattern position, stacked over repeats."""
    out = []
    for pat, n in segments(cfg):
        per_pos = tuple(_kind_cache(cfg, kind, b, max_len) for kind in pat)
        out.append(jax.tree.map(
            lambda a: jnp.broadcast_to(a, (n,) + a.shape), per_pos
        ))
    return out


def cache_pspecs(cfg: ModelConfig):
    """PartitionSpec tree (logical axes) matching init_cache structure.

    Full-attention KV caches (and MLA latent caches) are *sequence-sharded*
    over the TENSOR axis: batch over DATA, context over TENSOR. Decode
    attention then reduces partial (max, sum, PV) terms across the tensor
    axis — tiny per-step collectives — instead of replicating a cache that is
    ~L·2·Hkv·S·Dh bytes (36 GiB/dev at 32k for qwen3; §Perf iteration 2).
    Rolling-window and recurrent caches are small and stay DATA-only (their
    modular scatter indexing doesn't shard cleanly over seq).
    """
    from jax.sharding import PartitionSpec
    w = min(cfg.window or 0, 1 << 30)

    def leaf_spec(a: jnp.ndarray):
        nd = a.ndim
        if cfg.mla and nd == 4 and a.shape[-1] in (
            cfg.mla.kv_lora_rank, cfg.mla.qk_rope_head_dim
        ):
            # (n, b, S, r) latent cache: shard S
            return PartitionSpec(None, DATA, TENSOR, None)
        if nd == 5 and (cfg.window is None or a.shape[3] != w):
            # (n, b, hkv, S, dh) full-attention cache: shard S
            return PartitionSpec(None, DATA, None, TENSOR, None)
        return PartitionSpec(None, DATA, *([None] * (nd - 2)))

    out = []
    for seg in init_cache(cfg, 1, 1 << 16):
        out.append(jax.tree.map(leaf_spec, seg))
    return out


# ---------------------------------------------------------------------------
# block application
# ---------------------------------------------------------------------------

def apply_block(p, kind: str, cfg: ModelConfig, x, pos, mode: str, cache):
    """Returns (x, new_cache, metrics)."""
    metrics: Dict[str, jnp.ndarray] = {}
    new_cache = cache
    if kind in ("attn", "moe", "local"):
        window = cfg.window if kind == "local" else None
        h = rmsnorm(p["ln1"], x, cfg.norm_eps)
        if mode == "decode":
            if cfg.mla and kind != "local":
                attn_out, new_cache = mla_decode(p["attn"], cfg, h, pos, cache)
            else:
                attn_out, new_cache = gqa_decode(
                    p["attn"], cfg, h, pos, cache, window=window
                )
        else:
            if cfg.mla and kind != "local":
                attn_out, kv = mla_forward(p["attn"], cfg, h, pos)
            else:
                attn_out, kv = gqa_forward(p["attn"], cfg, h, pos, window=window)
            if mode == "prefill":
                new_cache = _fill_cache(cfg, kind, cache, kv)
        x = x + attn_out
        h2 = rmsnorm(p["ln2"], x, cfg.norm_eps)
        if kind == "moe":
            ffn_out, metrics = moe_forward(p["mlp"], cfg, h2)
        else:
            ffn_out = mlp(p["mlp"], h2, cfg.mlp_style)
        x = x + ffn_out
    elif kind == "rec":
        h = rmsnorm(p["ln1"], x, cfg.norm_eps)
        rec_out, new_cache = rglru_forward(
            p["rec"], cfg, h, cache if mode == "decode" else None,
            want_cache=(mode == "prefill"),
        )
        if mode != "prefill" and mode != "decode":
            new_cache = cache
        x = x + rec_out
        h2 = rmsnorm(p["ln2"], x, cfg.norm_eps)
        x = x + mlp(p["mlp"], h2, cfg.mlp_style)
    elif kind == "ssm":
        h = rmsnorm(p["ln1"], x, cfg.norm_eps)
        ssm_out, new_cache = ssm_forward(
            p["ssm"], cfg, h, cache if mode == "decode" else None,
            want_cache=(mode == "prefill"),
        )
        if mode == "train":
            new_cache = cache
        x = x + ssm_out
    else:
        raise ValueError(kind)
    x = shard.constraint(x, "data_b", None, None)
    return x, new_cache, metrics


def _fill_cache(cfg: ModelConfig, kind: str, cache, kv):
    """Write prefill K/V into a (possibly rolling) cache."""
    if cfg.mla and kind != "local":
        c_kv, k_rope = kv                              # [B,S,r], [B,S,dr]
        c_cache, r_cache = cache
        s = c_kv.shape[1]
        c_cache = jax.lax.dynamic_update_slice_in_dim(
            c_cache, c_kv.astype(c_cache.dtype), 0, axis=1)
        r_cache = jax.lax.dynamic_update_slice_in_dim(
            r_cache, k_rope.astype(r_cache.dtype), 0, axis=1)
        return (c_cache, r_cache)
    k, v = kv                                          # [B,Hkv,S,Dh]
    k_cache, v_cache = cache
    buf = k_cache.shape[2]
    s = k.shape[2]
    if s <= buf:
        k_cache = jax.lax.dynamic_update_slice_in_dim(
            k_cache, k.astype(k_cache.dtype), 0, axis=2)
        v_cache = jax.lax.dynamic_update_slice_in_dim(
            v_cache, v.astype(v_cache.dtype), 0, axis=2)
    else:
        # rolling window: keep last `buf` positions at slot = pos % buf
        positions = s - buf + jnp.arange(buf)
        slots = positions % buf
        k_cache = k_cache.at[:, :, slots].set(
            k[:, :, positions].astype(k_cache.dtype))
        v_cache = v_cache.at[:, :, slots].set(
            v[:, :, positions].astype(v_cache.dtype))
    return (k_cache, v_cache)


# ---------------------------------------------------------------------------
# backbone
# ---------------------------------------------------------------------------

def _merge_metrics(acc, new):
    for k_, v_ in new.items():
        acc[k_] = acc.get(k_, 0.0) + v_
    return acc


def backbone(params, cfg: ModelConfig, x, pos, mode: str, caches=None):
    """x: [B,S,d] embedded input. Returns (h, new_caches, metrics)."""
    segs = segments(cfg)
    new_caches = []
    metrics: Dict[str, jnp.ndarray] = {}
    has_moe = any("moe" in pat for pat, _ in segs)

    for si, (pat, n) in enumerate(segs):
        seg_params = params["segments"][si]
        seg_cache = caches[si] if caches is not None else None

        def period(x, p_layer, cache_layer, pat=pat):
            mets: Dict[str, jnp.ndarray] = (
                {"router_dropped": jnp.zeros((), F32)} if has_moe else {}
            )
            outs = []
            for i, kind in enumerate(pat):
                c = cache_layer[i] if cache_layer is not None else None
                x, nc, m = apply_block(p_layer[f"b{i}"], kind, cfg, x, pos, mode, c)
                outs.append(nc)
                for mk, mv in m.items():
                    mets[mk] = mets.get(mk, jnp.zeros((), F32)) + mv
            return x, tuple(outs), mets

        if cfg.remat == "full" and mode == "train":
            period = jax.checkpoint(
                period, policy=jax.checkpoint_policies.nothing_saveable,
                static_argnums=(),
            )

        def body(carry, xs):
            x, acc = carry
            p_layer = xs[0]
            cache_layer = xs[1] if caches is not None else None
            x, ncache, mets = period(x, p_layer, cache_layer)
            for mk, mv in mets.items():
                acc = dict(acc); acc[mk] = acc[mk] + mv
            return (x, acc), ncache

        def body_in_place(carry, p_layer):
            (x, acc), cache, i = carry
            cache_layer = jax.tree.map(
                lambda c: jax.lax.dynamic_index_in_dim(c, i, keepdims=False),
                cache)
            (x, acc), ncache = body((x, acc), (p_layer, cache_layer))
            cache = jax.tree.map(
                lambda c, n: jax.lax.dynamic_update_index_in_dim(c, n, i, 0),
                cache, ncache)
            return ((x, acc), cache, i + 1), None

        acc0 = {"router_dropped": jnp.zeros((), F32)} if has_moe else {}
        if mode == "decode":
            # the caches ride the carry and each layer's is written back in
            # place: as scan outputs they would land in a fresh buffer, and
            # a caller that keeps its caches in a loop carry (the fused
            # serving step) would copy the whole cache back every step
            ((x, acc0), seg_cache_out, _), _ = jax.lax.scan(
                body_in_place, ((x, acc0), seg_cache, jnp.int32(0)),
                seg_params)
        else:
            xs = (seg_params,) if caches is None else (seg_params, seg_cache)
            (x, acc0), seg_cache_out = jax.lax.scan(body, (x, acc0), xs)
        metrics = _merge_metrics(metrics, acc0)
        new_caches.append(seg_cache_out)

    return x, (new_caches if caches is not None else None), metrics


def _embed_in(params, cfg: ModelConfig, batch):
    if cfg.input_mode == "tokens":
        x = embed(params["embed"], batch["tokens"])
    else:
        x = batch["embeds"].astype(jnp.bfloat16)
    b, s = x.shape[0], x.shape[1]
    if cfg.pos == "learned":
        x = x + params["pos_embed"][:s][None].astype(x.dtype)
    x = shard.constraint(x, "data_b", None, None)
    if cfg.pos == "mrope":
        pos = batch.get("positions")
        if pos is None:
            pos = jnp.broadcast_to(jnp.arange(s)[None, None], (3, b, s))
    else:
        pos = jnp.arange(s, dtype=jnp.int32)[None, :]
    return x, pos


def _head(params, cfg: ModelConfig):
    if cfg.tie_embeddings:
        return params["embed"].T
    return params["head"]


# ---------------------------------------------------------------------------
# entry points
# ---------------------------------------------------------------------------

def train_loss(params, cfg: ModelConfig, batch) -> Tuple[jnp.ndarray, dict]:
    x, pos = _embed_in(params, cfg, batch)
    h, _, metrics = backbone(params, cfg, x, pos, "train")
    h = rmsnorm(params["final_norm"], h, cfg.norm_eps)
    head = _head(params, cfg)
    labels = batch["labels"]
    tot, cnt = chunked_softmax_xent(head, h, labels, cfg.loss_chunk)
    loss = tot / jnp.maximum(cnt, 1.0)
    metrics = dict(metrics)
    metrics["ce_loss"] = loss

    if cfg.mtp and cfg.input_mode == "tokens":
        mtp = params["mtp"]
        # predict t+2: combine h_t with embedding of token t+1 (= labels)
        emb_next = embed(params["embed"], jnp.maximum(batch["labels"], 0))
        z = jnp.concatenate(
            [rmsnorm(mtp["norm_h"], h, cfg.norm_eps),
             rmsnorm(mtp["norm_e"], emb_next, cfg.norm_eps)], axis=-1
        ) @ mtp["proj"]
        kind = "moe" if cfg.moe else "attn"
        z, _, _ = apply_block(mtp["block"], kind, cfg, z, pos, "train", None)
        labels2 = jnp.concatenate(
            [labels[:, 1:], jnp.full_like(labels[:, :1], -1)], axis=1
        )
        tot2, cnt2 = chunked_softmax_xent(head, z, labels2, cfg.loss_chunk)
        mtp_loss = tot2 / jnp.maximum(cnt2, 1.0)
        metrics["mtp_loss"] = mtp_loss
        loss = loss + 0.3 * mtp_loss

    return loss, metrics


def prefill(params, cfg: ModelConfig, batch, max_len: int):
    """Forward over the prompt, building caches sized ``max_len``.
    Returns (last_logits [B, V], caches)."""
    x, pos = _embed_in(params, cfg, batch)
    b, s = x.shape[0], x.shape[1]
    caches = init_cache(cfg, b, max_len)
    h, caches, _ = backbone(params, cfg, x, pos, "prefill", caches)
    h = rmsnorm(params["final_norm"], h[:, -1:], cfg.norm_eps)
    logits = (h @ _head(params, cfg))[:, 0]
    return logits.astype(F32), caches


def decode_step(params, cfg: ModelConfig, caches, tokens, pos):
    """One decode step. tokens: [B] int32; pos: [B] positions being written.
    Returns (logits [B, V], new_caches)."""
    if cfg.input_mode == "tokens":
        x = embed(params["embed"], tokens[:, None])
    else:  # pragma: no cover - encoder archs have no decode
        raise ValueError("decode on encoder-only arch")
    x = shard.constraint(x, "data_b", None, None)
    h, caches, _ = backbone(params, cfg, x, pos, "decode", caches)
    h = rmsnorm(params["final_norm"], h, cfg.norm_eps)
    logits = (h @ _head(params, cfg))[:, 0]
    return logits.astype(F32), caches
