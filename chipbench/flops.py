"""Operations and bytes that the work needs, from its shapes alone.

These counts say what the algorithm has to do, not what the program's
compiled code happens to do, so a program that does the same work another
way is judged on the same count.
"""
from __future__ import annotations


def relaxed_topk_bytes(batch: int, n: int, places: int) -> float:
    """One stage-1 selection of ``places`` winners from each of ``batch``
    pools of ``n`` float32 priorities: read every priority once, write a
    float32 value and an int32 index per winner."""
    return batch * (4.0 * n + 8.0 * places)


def relaxed_topk_ops(batch: int, n: int) -> float:
    """One comparison per priority."""
    return float(batch * n)


def sssp_phase_bytes(batch: int, n: int, places: int) -> float:
    """One SSSP phase of ``batch`` graphs: read the popped rows of the
    float32 weight matrix (``places`` rows of ``n``), and read and write the
    ``n`` tentative distances."""
    return batch * 4.0 * (places * n + 2 * n)


def sssp_phase_ops(batch: int, n: int, places: int) -> float:
    """An add and a min per popped row entry."""
    return batch * 2.0 * places * n


def transformer_flops_per_token(cfg: dict, context: int) -> float:
    """Forward FLOPs of one token of a dense GQA decoder (Qwen3 layout,
    tied embeddings) attending to ``context`` positions: 2 per weight of
    the q, k, v, o, gate, up and down projections and of the output head,
    plus 4·heads·head_dim per attended position (scores and values).
    Norms, rotary embeddings and softmax are left out."""
    d = cfg["hidden_size"]
    h = cfg["num_attention_heads"]
    hkv = cfg["num_key_value_heads"]
    dh = cfg["head_dim"]
    ff = cfg["intermediate_size"]
    layers = cfg["num_hidden_layers"]
    proj = d * h * dh + 2 * d * hkv * dh + h * dh * d + 3 * d * ff
    attn = 2.0 * h * dh * context
    head = d * cfg["vocab_size"]
    return layers * (2.0 * proj + 2.0 * attn) + 2.0 * head


def prefill_flops(cfg: dict, prompt_len: int) -> float:
    """A causal prefill of ``prompt_len`` tokens: token t attends to t + 1
    positions. Only the last position's logits are computed."""
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    per_tok_no_head = transformer_flops_per_token(cfg, 0) - 2.0 * d * v
    layers = cfg["num_hidden_layers"]
    h, dh = cfg["num_attention_heads"], cfg["head_dim"]
    attended = prompt_len * (prompt_len + 1) / 2.0
    return (prompt_len * per_tok_no_head + layers * 4.0 * h * dh * attended
            + 2.0 * d * v)
