"""The program's model configuration for a benchmark configuration file."""

from __future__ import annotations


def model_config(c: dict):
    """``repro``'s ModelConfig with the sizes of configuration ``c``."""
    from repro.configs.base import ModelConfig
    mc = ModelConfig(
        name=c["name"], family="dense", n_layers=c["num_hidden_layers"],
        d_model=c["hidden_size"], n_heads=c["num_attention_heads"],
        n_kv_heads=c["num_key_value_heads"], d_ff=c["intermediate_size"],
        vocab_size=c["vocab_size"], mlp="swiglu", rope_theta=c["rope_theta"],
        norm_eps=c["rms_norm_eps"], tie_embeddings=c["tie_word_embeddings"],
        dtype=c["torch_dtype"])
    if mc.padded_heads != c["padded_heads"]:
        raise ValueError(f"the program pads {mc.n_heads} heads to "
                         f"{mc.padded_heads}, the configuration says "
                         f"{c['padded_heads']}")
    return mc
