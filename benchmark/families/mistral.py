"""Mistral-family configuration -> the program's model.

Keys map to constructor arguments as ``elephas_tpu/models/hf_import.py``
(``_from_llama_family``) maps them: SwiGLU, RMSNorm, rotary positions, no
biases, untied head, grouped-query attention. A published
``sliding_window`` below ``max_position_embeddings`` becomes
``attn_window`` (Mistral-7B-v0.1; the serving engine then refuses the
model, see PERF.md).
"""


def build_model(cfg):
    from elephas_tpu.models import TransformerLM

    window = cfg.get("sliding_window")
    if window is not None and window >= cfg["max_position_embeddings"]:
        window = None
    return TransformerLM(
        vocab=cfg["vocab_size"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"],
        n_layers=cfg["num_hidden_layers"], d_ff=cfg["intermediate_size"],
        max_len=cfg["max_position_embeddings"],
        compute_dtype=cfg["compute_dtype"], pos_encoding="rotary",
        rope_theta=cfg["rope_theta"],
        n_kv_heads=cfg["num_key_value_heads"],
        tie_embeddings=bool(cfg["tie_word_embeddings"]),
        activation="swiglu", norm="rmsnorm", norm_eps=cfg["rms_norm_eps"],
        attn_bias=False, ffn_bias=False, attn_window=window)
