"""Mixtral-family configuration -> the program's model.

Keys map as ``elephas_tpu/models/hf_import.py`` (``_from_mixtral``) maps
them: the Mistral block with the FFN replaced by ``num_local_experts``
SwiGLU experts, ``num_experts_per_tok`` of them per token, capacity factor
E/k (a slot for every token: no drops, which is what the published routing
computes), and the library's default ``moe_dispatch="slots"``.
"""


def build_model(cfg):
    from elephas_tpu.models import MoETransformerLM

    window = cfg.get("sliding_window")
    if window is not None and window >= cfg["max_position_embeddings"]:
        window = None
    experts, per_tok = cfg["num_local_experts"], cfg["num_experts_per_tok"]
    return MoETransformerLM(
        vocab=cfg["vocab_size"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"],
        n_layers=cfg["num_hidden_layers"], d_ff=cfg["intermediate_size"],
        max_len=cfg["max_position_embeddings"],
        n_experts=experts, k=per_tok, capacity_factor=experts / per_tok,
        aux_weight=cfg.get("router_aux_loss_coef", 0.0),
        compute_dtype=cfg["compute_dtype"], pos_encoding="rotary",
        rope_theta=cfg["rope_theta"],
        n_kv_heads=cfg["num_key_value_heads"],
        tie_embeddings=bool(cfg["tie_word_embeddings"]),
        activation="swiglu", norm="rmsnorm", norm_eps=cfg["rms_norm_eps"],
        attn_bias=False, ffn_bias=False, attn_window=window,
        param_dtype=cfg["weights"]["dtype"])
