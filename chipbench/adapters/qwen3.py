"""Qwen3 config.json keys as the program's ``ModelConfig``: the program's
side of the model, kept apart from ``chipbench/reference/qwen3.py``, which
imports nothing of the program."""


def model_config(cfg: dict):
    """The program's ``ModelConfig`` for a Qwen3 config.json."""
    from repro.configs.base import ModelConfig

    return ModelConfig(
        name=cfg["name"], family="dense",
        num_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        d_ff=cfg["intermediate_size"], vocab_size=cfg["vocab_size"],
        qkv_bias=cfg["attention_bias"], qk_norm=True,
        rope_theta=float(cfg["rope_theta"]),
        tie_embeddings=cfg["tie_word_embeddings"],
        norm_eps=cfg["rms_norm_eps"])
