"""``ouro``-family configuration (Ouro-2.6B, ByteDance's LoopLM) -> the
program's model.

Published keys map to ``TransformerLM``'s arguments: ``total_ut_steps``
is ``passes`` (the whole layer stack run that many times a token, a cache
layer a pass and layer); ``num_attention_heads`` = ``num_key_value_heads``
heads of ``head_dim``; ``intermediate_size`` the SwiGLU FFN's;
``rope_theta`` the half-split rotary's on every layer. A config whose
``early_exit_threshold`` is below 1 would let the exit gate stop a token
before the last pass, which is not in the program: refused.

What the published ``config.json`` has no key for is set HERE, one
constructor argument each, and listed under the configuration's
``assumed``: another reading is a one-place change of this file and of the
like-named argument of ``reference/ouro.py``'s ``forward``. Every
intermediate is bfloat16, as published: ``act_dtype="float32"`` was
measured at the published widths and not needed (PERF.md §6).
"""

NORM_ORDER = "sandwich"     # (1) h + N_2(Mixer(N_1(h))), four norms a layer
ATTN_BIAS = False           # (2) no bias on q, k, v, o
PASS_NORM = True            # (3) the final norm after every pass


def build_model(cfg):
    from elephas_tpu.models import TransformerLM

    if cfg["layer_types"] != ["full_attention"] * cfg["num_hidden_layers"]:
        raise ValueError("layer_types: full_attention, one a layer")
    if (cfg["rope_scaling"] is not None or cfg["use_sliding_window"]
            or cfg["hidden_act"] != "silu"):
        raise ValueError("rope_scaling, use_sliding_window or hidden_act: "
                         "not read")
    if cfg["early_exit_threshold"] < 1:
        raise ValueError("early_exit_threshold < 1: an exit before the last "
                         "pass is not in the program")
    return TransformerLM(
        vocab=cfg["vocab_size"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        n_layers=cfg["num_hidden_layers"], d_ff=cfg["intermediate_size"],
        max_len=cfg["max_position_embeddings"],
        compute_dtype=cfg["compute_dtype"], pos_encoding="rotary",
        rope_theta=cfg["rope_theta"],
        tie_embeddings=bool(cfg["tie_word_embeddings"]),
        activation="swiglu", norm="rmsnorm", norm_eps=cfg["rms_norm_eps"],
        attn_bias=ATTN_BIAS, ffn_bias=False, norm_order=NORM_ORDER,
        passes=cfg["total_ut_steps"], pass_norm=PASS_NORM)
