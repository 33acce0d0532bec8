"""``exaone_moe``-family configuration (K-EXAONE) -> the program's model.

Published keys map to ``MoETransformerLM``'s arguments: ``head_dim`` (q is
``num_attention_heads * head_dim`` wide, not ``hidden_size``);
``layer_types`` with ``sliding_window`` -> one window per layer
(``attn_window``), each kind of layer with a cache of its own
(``window_cache="ring"``); ``first_k_dense_replace`` leading dense layers
of ``intermediate_size`` (``dense_layers``, ``d_ff_dense``);
``moe_intermediate_size`` the width of one expert (``d_ff``);
``scoring_func``, ``norm_topk_prob``, ``routed_scaling_factor``,
``num_shared_experts``, ``num_experts_per_tok`` the router's.

THE SHARE. The configuration's ``num_experts`` counts the experts HELD on
this chip, ``held_experts = [first, count]`` says which, and the router
keeps the published width, ``reduced.num_experts.published`` (the
configuration's own ``num_experts`` where it is not reduced).

What the published ``config.json`` has no key for is set HERE, one line
each, and listed under the configuration's ``assumed`` with the model
card's sentence: a correction is a one-line change of this file.
"""

QK_NORM = True             # RMSNorm on q and k per head, before rotary
ROPE_LAYERS = "windowed"   # "No Rotary Positional Embedding Used" on global
SELECT_BIAS = True         # e_score_correction_bias of sigmoid routers
NORM = "rmsnorm"           # pre-norm residual blocks, RMSNorm, no biases


def build_model(cfg, mtp_layers: int = 0):
    from elephas_tpu.models import MoETransformerLM

    windows = []
    for kind in cfg["layer_types"]:
        if kind not in ("sliding_attention", "full_attention"):
            raise ValueError(f"layer_types: {kind!r}")
        windows.append(cfg["sliding_window"]
                       if kind == "sliding_attention" else None)
    if len(windows) != cfg["num_hidden_layers"]:
        raise ValueError("layer_types does not follow num_hidden_layers")
    dense = cfg["first_k_dense_replace"]
    mlp = cfg.get("mlp_layer_types")
    if mlp is not None and mlp != ["dense"] * dense + ["sparse"] * (
            len(windows) - dense):
        raise ValueError("mlp_layer_types: dense layers must lead")
    if cfg["n_group"] != 1 or cfg["topk_group"] != 1:
        raise ValueError("group-limited routing is not in the program")
    reduced = cfg.get("reduced", {}).get("num_experts")
    router = reduced["published"] if reduced else cfg["num_experts"]
    held = tuple(cfg["held_experts"]) if reduced else None
    if held is not None and held[1] != cfg["num_experts"]:
        raise ValueError("held_experts does not hold num_experts experts")
    mixed = len(set(windows)) > 1
    return MoETransformerLM(
        vocab=cfg["vocab_size"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"],
        n_layers=cfg["num_hidden_layers"],
        d_ff=cfg["moe_intermediate_size"],
        max_len=cfg["max_position_embeddings"],
        n_experts=router, k=cfg["num_experts_per_tok"],
        aux_weight=0.0, compute_dtype=cfg["compute_dtype"],
        pos_encoding="rotary",
        rope_theta=cfg["rope_parameters"]["rope_theta"],
        n_kv_heads=cfg["num_key_value_heads"],
        tie_embeddings=bool(cfg["tie_word_embeddings"]),
        activation="swiglu", norm=NORM, norm_eps=cfg["rms_norm_eps"],
        attn_bias=False, ffn_bias=False, attn_window=windows,
        param_dtype=cfg["weights"]["dtype"],
        head_dim=cfg["head_dim"], qk_norm=QK_NORM,
        rope_layers=ROPE_LAYERS,
        window_cache="ring" if mixed else "horizon",
        dense_layers=dense, d_ff_dense=cfg["intermediate_size"],
        scoring=cfg["scoring_func"], select_bias=SELECT_BIAS,
        norm_topk=bool(cfg["norm_topk_prob"]),
        routed_scale=cfg["routed_scaling_factor"],
        n_shared=cfg["num_shared_experts"], held=held,
        mtp_layers=mtp_layers)
