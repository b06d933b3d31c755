"""The operation and byte counts against hand counts."""
import json

import pytest

from chipbench import flops, spec

TINY = {"hidden_size": 4, "num_attention_heads": 2, "num_key_value_heads": 1,
        "head_dim": 2, "intermediate_size": 3, "vocab_size": 5,
        "num_hidden_layers": 1}


def test_transformer_flops_per_token_by_hand():
    # projections: q 4*4, k 4*2, v 4*2, o 4*4, gate+up+down 3*4*3 = 84
    # weights -> 168 FLOPs; attention 4*heads*head_dim*context = 16*3 = 48;
    # tied head 2*4*5 = 40
    assert flops.transformer_flops_per_token(TINY, 3) == 168 + 48 + 40
    cfg = dict(TINY, num_hidden_layers=2)
    assert flops.transformer_flops_per_token(cfg, 3) == 2 * (168 + 48) + 40


def test_prefill_flops_by_hand():
    # 3 tokens of weights (3*168), attention over 1+2+3 positions (4*2*2*6),
    # and one position's head (40)
    assert flops.prefill_flops(TINY, 3) == 3 * 168 + 16 * 6 + 40


def test_qwen3_1_7b_weights_per_token():
    cfg = {"hidden_size": 2048, "num_attention_heads": 16,
           "num_key_value_heads": 8, "head_dim": 128,
           "intermediate_size": 6144, "vocab_size": 151936,
           "num_hidden_layers": 28}
    # 28 layers of 50.3 M projection weights plus the 311 M tied head:
    # 1.72 G weights (the published 1.7 B), 3.4 GFLOP a token at no context
    per_tok = flops.transformer_flops_per_token(cfg, 0)
    assert per_tok == 2 * (28 * 50_331_648 + 2048 * 151936)


@pytest.mark.parametrize("context,prompt_len", [
    (0, 1), (1, 8), (129, 128), (767, 256), (1023, 512)])
def test_qwen3_model_file_counts_are_the_dense_decoders(context, prompt_len):
    """``serve_mfu`` counts the same FLOPs through the model's file as
    through ``flops.py``, bit for bit, at the cell's published widths."""
    cfg = json.loads(
        (spec.HERE / "configs" / "qwen3_1_7b_s8.json").read_text())
    ref = spec.model_files(cfg["model_type"], spec.HERE.parent).reference
    assert (ref.flops_per_token(cfg, context)
            == flops.transformer_flops_per_token(cfg, context))
    assert (ref.prefill_flops(cfg, prompt_len)
            == flops.prefill_flops(cfg, prompt_len))


def test_selection_and_phase_counts_by_hand():
    # read 10 f32 priorities, write 3 (value, index) pairs, per instance
    assert flops.relaxed_topk_bytes(2, 10, 3) == 2 * (40 + 24)
    assert flops.relaxed_topk_ops(2, 10) == 20
    # 3 rows of 10 f32 weights, plus the 10 distances read and written
    assert flops.sssp_phase_bytes(1, 10, 3) == 4 * (30 + 20)
    assert flops.sssp_phase_ops(2, 10, 3) == 2 * 2 * 30
