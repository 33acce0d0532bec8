"""``olmo_hybrid``-family configuration (Olmo-Hybrid-7B) -> the program's
model.

Published keys map to ``TransformerLM``'s arguments: ``layer_types``
(``linear_attention`` / ``full_attention``, one a layer) as it stands;
``linear_num_key_heads`` = ``linear_num_value_heads`` (``linear_heads``),
``linear_key_head_dim``, ``linear_value_head_dim``,
``linear_conv_kernel_dim``, ``linear_allow_neg_eigval`` the Gated DeltaNet
layer's; ``num_attention_heads`` = ``num_key_value_heads`` heads of
``hidden_size / num_attention_heads`` in the full layers;
``intermediate_size`` the SwiGLU FFN's; ``rope_parameters.rope_theta``
null.

What the published ``config.json`` has no key for is set HERE, one
constructor argument each, and listed under the configuration's
``assumed``: another reading is a one-place change of this file and of the
like-named argument of ``reference/olmo_hybrid.py``'s ``forward``. None
changes a shape, a kernel or an expected cost.
"""

NORM = "rmsnorm"            # RMSNorm with rms_norm_eps, no biases
NORM_ORDER = "post"         # (1) h + N_a(Mixer(h)), h + N_f(FFN(h))
QK_NORM = "whole"           # (2) one RMSNorm over the whole q / k projection
ROPE_LAYERS = "none"        # (3) rope_theta null: no layer rotates
LINEAR_GATE = "silu"        # (4) the output gate's activation
STATE_DTYPE = "float32"     # (5) the recurrent state (the tail: compute dtype)
ACT_DTYPE = "float32"       # a departure: matmuls take bf16 and GIVE float32


def build_model(cfg):
    from elephas_tpu.models import TransformerLM

    if cfg["linear_num_key_heads"] != cfg["linear_num_value_heads"]:
        raise ValueError("linear key heads != value heads: grouped linear "
                         "heads are not in the program")
    if len(cfg["layer_types"]) != cfg["num_hidden_layers"]:
        raise ValueError("layer_types does not follow num_hidden_layers")
    if (cfg["rope_parameters"]["rope_theta"] is not None
            or cfg["attention_bias"] or cfg["hidden_act"] != "silu"):
        raise ValueError("rope_theta, attention_bias or hidden_act: not read")
    return TransformerLM(
        vocab=cfg["vocab_size"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"],
        n_layers=cfg["num_hidden_layers"], d_ff=cfg["intermediate_size"],
        max_len=cfg["max_position_embeddings"],
        compute_dtype=cfg["compute_dtype"], pos_encoding="rotary",
        rope_layers=ROPE_LAYERS,
        tie_embeddings=bool(cfg["tie_word_embeddings"]),
        activation="swiglu", norm=NORM, norm_eps=cfg["rms_norm_eps"],
        attn_bias=False, ffn_bias=False, qk_norm=QK_NORM,
        norm_order=NORM_ORDER, layer_types=cfg["layer_types"],
        linear_heads=cfg["linear_num_value_heads"],
        linear_key_head_dim=cfg["linear_key_head_dim"],
        linear_value_head_dim=cfg["linear_value_head_dim"],
        linear_conv_kernel_dim=cfg["linear_conv_kernel_dim"],
        linear_allow_neg_eigval=bool(cfg["linear_allow_neg_eigval"]),
        linear_gate=LINEAR_GATE, state_dtype=STATE_DTYPE,
        act_dtype=ACT_DTYPE)
