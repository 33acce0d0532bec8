"""``axk1``-family configuration (A.X-K1) -> the program's model.

Published keys map to ``MoETransformerLM``'s arguments: latent attention
from ``q_lora_rank``, ``kv_lora_rank``, ``qk_nope_head_dim``,
``qk_rope_head_dim``, ``v_head_dim`` and the ``rope_scaling`` dictionary
(YaRN) with ``rope_theta``; ``first_k_dense_replace`` leading dense layers
of ``intermediate_size`` (``dense_layers``, ``d_ff_dense``);
``moe_intermediate_size`` the width of one expert (``d_ff``);
``scoring_func``, ``norm_topk_prob``, ``routed_scaling_factor``,
``n_shared_experts``, ``num_experts_per_tok`` the router's.

THE SHARE. The configuration's ``n_routed_experts`` counts the experts
HELD on this chip, ``held_experts = [first, count]`` says which, and the
router keeps the published width, ``reduced.n_routed_experts.published``
(the configuration's own ``n_routed_experts`` where it is not reduced).

What the published ``config.json`` has no key for, or a key whose value
the code it descends from does not define, is set HERE, one line each, and
listed under the configuration's ``assumed``: a correction is a one-line
change of this file (and of ``reference/axk1.py``'s ``select``).
"""

NORM = "rmsnorm"          # pre-norm residual blocks, RMSNorm, no biases
SELECT_BIAS = False       # topk_method "none": no e_score_correction_bias
GROUP_LIMITED = False     # ...and no choice of topk_group of n_group first


def build_model(cfg):
    from elephas_tpu.models import MoETransformerLM

    if cfg["topk_method"] != "none" or GROUP_LIMITED:
        raise ValueError(
            f"topk_method {cfg['topk_method']!r}: the program chooses the "
            "largest of all scores; group-limited selection is not in it")
    if cfg["moe_layer_freq"] != 1 or cfg["attention_bias"]:
        raise ValueError("moe_layer_freq != 1 or attention_bias: not read")
    reduced = cfg.get("reduced", {}).get("n_routed_experts")
    router = reduced["published"] if reduced else cfg["n_routed_experts"]
    held = tuple(cfg["held_experts"]) if reduced else None
    if held is not None and held[1] != cfg["n_routed_experts"]:
        raise ValueError("held_experts does not hold n_routed_experts")
    return MoETransformerLM(
        vocab=cfg["vocab_size"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"],
        n_layers=cfg["num_hidden_layers"],
        d_ff=cfg["moe_intermediate_size"],
        max_len=cfg["max_position_embeddings"],
        n_experts=router, k=cfg["num_experts_per_tok"],
        aux_weight=0.0, compute_dtype=cfg["compute_dtype"],
        pos_encoding="rotary", rope_theta=cfg["rope_theta"],
        tie_embeddings=bool(cfg["tie_word_embeddings"]),
        activation="swiglu", norm=NORM, norm_eps=cfg["rms_norm_eps"],
        attn_bias=False, ffn_bias=False,
        param_dtype=cfg["weights"]["dtype"],
        dense_layers=cfg["first_k_dense_replace"],
        d_ff_dense=cfg["intermediate_size"],
        scoring=cfg["scoring_func"], select_bias=SELECT_BIAS,
        norm_topk=bool(cfg["norm_topk_prob"]),
        routed_scale=cfg["routed_scaling_factor"],
        n_shared=cfg["n_shared_experts"], held=held,
        q_lora_rank=cfg["q_lora_rank"], kv_lora_rank=cfg["kv_lora_rank"],
        qk_nope_head_dim=cfg["qk_nope_head_dim"],
        qk_rope_head_dim=cfg["qk_rope_head_dim"],
        v_head_dim=cfg["v_head_dim"], rope_scaling=cfg["rope_scaling"])
