"""Qwen3 (dense, GQA, qk-norm, tied embeddings): weights and a plain forward.

Written from the published architecture (Qwen3 technical report,
arXiv:2505.09388, and the ``Qwen3ForCausalLM`` description of its
config.json): pre-norm decoder layers with RMSNorm; attention with
per-head RMSNorm on queries and keys before rotary embeddings (half-split
rotation), grouped key/value heads, causal softmax scaled by
head_dim^-1/2; a SwiGLU MLP; a final RMSNorm; logits from the tied
embedding matrix. The forward runs in float32 with every matrix product at
``Precision.HIGHEST``, over one padded sequence, no cache and no batching.

``make_weights`` lays the random weights out as the serving engine takes
them: ``embed``, ``final_norm`` and one segment of the layer stack, each
matrix stored ``[in, out]`` with the gate and up projections side by side
in ``mlp.wi``. Both the program and this reference get these weights; the
reference takes nothing from the program. The model FLOPs that
``serve_mfu`` counts are ``chipbench/flops.py``'s dense GQA decoder.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from chipbench import flops

F32 = jnp.float32
HI = jax.lax.Precision.HIGHEST


def _dims(cfg: dict):
    return (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["head_dim"],
            cfg["intermediate_size"], cfg["vocab_size"],
            cfg["num_hidden_layers"])


def weight_shapes(cfg: dict) -> dict:
    d, h, hkv, dh, ff, v, n = _dims(cfg)
    bf, f32 = jnp.bfloat16, F32
    return {
        "embed": ((v, d), bf, 0.02),
        "segments": [{"b0": {
            "ln1": ((n, d), f32, "norm"),
            "attn": {"wq": ((n, d, h * dh), bf, d ** -0.5),
                     "wk": ((n, d, hkv * dh), bf, d ** -0.5),
                     "wv": ((n, d, hkv * dh), bf, d ** -0.5),
                     "wo": ((n, h * dh, d), bf, (h * dh) ** -0.5),
                     "q_norm": ((n, dh), f32, "norm"),
                     "k_norm": ((n, dh), f32, "norm")},
            "ln2": ((n, d), f32, "norm"),
            "mlp": {"wi": ((n, d, 2 * ff), bf, d ** -0.5),
                    "wo": ((n, ff, d), bf, ff ** -0.5)},
        }}],
        "final_norm": ((d,), f32, "norm"),
    }


def _is_leaf(x):
    return isinstance(x, tuple) and len(x) == 3 and isinstance(x[0], tuple)


@functools.partial(jax.jit, static_argnums=(1,))
def _make(key, cfg_items):
    cfg = dict(cfg_items)
    shapes = weight_shapes(cfg)
    leaves, tree = jax.tree.flatten(shapes, is_leaf=_is_leaf)
    keys = jax.random.split(key, len(leaves))
    out = []
    for k, (shape, dtype, scale) in zip(keys, leaves):
        z = jax.random.normal(k, shape, F32)
        # norm gains near 1, not all 1, so that a norm the program skips
        # shows in the comparison
        x = 1.0 + 0.1 * z if scale == "norm" else z * scale
        out.append(x.astype(dtype))
    return jax.tree.unflatten(tree, out)


def make_weights(seed_word: int, cfg: dict):
    """Random weights in their serving dtypes, made on the device by one
    jitted call."""
    items = tuple(sorted((k, v) for k, v in cfg.items()
                         if isinstance(v, (int, float, str, bool))))
    return _make(jax.random.PRNGKey(seed_word), items)


# ---------------------------------------------------------------------------
# the plain forward
# ---------------------------------------------------------------------------

def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, theta):
    """x [S, H, D]; rotate the two halves of each head (half-split)."""
    s, _, d = x.shape
    freqs = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=F32) / d)
    ang = jnp.arange(s, dtype=F32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _fp8(w):
    """``w`` rounded to float8 e4m3 with one scale per matrix (the control:
    the step below bfloat16)."""
    w = w.astype(F32)
    scale = jnp.maximum(jnp.max(jnp.abs(w)), 1e-30) / 448.0
    return (w / scale).astype(jnp.float8_e4m3fn).astype(F32) * scale


def _logits(params, tokens, cfg: dict, low: bool):
    d, h, hkv, dh, ff, _v, _n = _dims(cfg)
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    cast = _fp8 if low else (lambda w: w.astype(F32))
    s = tokens.shape[0]
    emb = cast(params["embed"])
    x = emb[tokens]
    mask = jnp.tril(jnp.ones((s, s), bool))

    def layer(x, p):
        a = p["attn"]
        hid = _rms(x, p["ln1"], eps)
        q = jnp.matmul(hid, cast(a["wq"]), precision=HI).reshape(s, h, dh)
        k = jnp.matmul(hid, cast(a["wk"]), precision=HI).reshape(s, hkv, dh)
        v = jnp.matmul(hid, cast(a["wv"]), precision=HI).reshape(s, hkv, dh)
        q = _rope(_rms(q, a["q_norm"], eps), theta)
        k = _rope(_rms(k, a["k_norm"], eps), theta)
        k = jnp.repeat(k, h // hkv, axis=1)      # query head i reads kv i // g
        v = jnp.repeat(v, h // hkv, axis=1)
        sc = jnp.einsum("qhd,khd->hqk", q, k, precision=HI) * dh ** -0.5
        pr = jax.nn.softmax(jnp.where(mask, sc, -jnp.inf), axis=-1)
        o = jnp.einsum("hqk,khd->qhd", pr, v, precision=HI).reshape(s, h * dh)
        x = x + jnp.matmul(o, cast(a["wo"]), precision=HI)
        m = p["mlp"]
        gu = jnp.matmul(_rms(x, p["ln2"], eps), cast(m["wi"]), precision=HI)
        gate, up = gu[:, :ff], gu[:, ff:]
        x = x + jnp.matmul(jax.nn.silu(gate) * up, cast(m["wo"]),
                           precision=HI)
        return x, None

    x, _ = jax.lax.scan(layer, x, params["segments"][0]["b0"])
    x = _rms(x, params["final_norm"], eps)
    return jnp.matmul(x, emb.T, precision=HI)            # [S, V]


@functools.partial(jax.jit, static_argnums=(2, 3))
def token_gaps(params, tokens, cfg_items, control: bool):
    """At every position t of ``tokens`` [S]: how far the reference logit
    of ``tokens[t + 1]`` lies below the reference's best logit, and, with
    ``control``, how far the logit of the token that the float8 forward
    puts first lies below it. Returns (served_gap [S], control_gap [S])."""
    cfg = dict(cfg_items)
    ref = _logits(params, tokens, cfg, low=False)
    best = jnp.max(ref, axis=-1)
    nxt = jnp.concatenate([tokens[1:], tokens[-1:]])
    served = best - jnp.take_along_axis(ref, nxt[:, None], axis=1)[:, 0]
    if not control:
        return served, jnp.zeros_like(served)
    top = jnp.argmax(_logits(params, tokens, cfg, low=True), axis=-1)
    ctl = best - jnp.take_along_axis(ref, top[:, None], axis=1)[:, 0]
    return served, ctl


def cfg_items(cfg: dict) -> tuple:
    return tuple(sorted((k, v) for k, v in cfg.items()
                        if isinstance(v, (int, float, str, bool))))


flops_per_token = flops.transformer_flops_per_token
prefill_flops = flops.prefill_flops
