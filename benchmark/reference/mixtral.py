"""Plain reference for the Mixtral family: the Mistral reference with each
layer's FFN replaced by sparse experts, as published: router logits
``x @ wg`` over the experts, softmax, the top ``num_experts_per_tok``
probabilities renormalised to sum to one, and the token's output the
weighted sum of those experts' SwiGLU outputs. No capacity, no dropping,
no dispatch: every expert is applied to every token and weighted by zero
where it was not chosen, one expert at a time so that only one expert is
ever widened to float32. Imports nothing from ``elephas_tpu``.

The parameter layout is the program's: ``wg`` ``[L, D, E]``, expert stacks
``w1``/``w3``/``w2`` ``[L, E, ...]`` (gate/up/down).
"""

import functools

import jax
import jax.numpy as jnp

from benchmark.reference import mistral

# Where the second and third router probabilities tie within bfloat16
# rounding, the program (bfloat16 activations) and this reference (float32)
# choose different experts and that position's logits part by an expert's
# whole output; with random weights a few percent of (token, layer) pairs
# are that close. Most positions must agree, not all.
MIN_SHARE = 0.75
ATTN_KEYS = ("ln1_s", "wq", "wk", "wv", "wo", "ln2_s", "wg")


def gates(cfg, x, wg):
    """``[T, E]`` combine weights: zero outside each token's top k."""
    probs = jax.nn.softmax(x @ wg, axis=-1)
    k = cfg["num_experts_per_tok"]
    kth = jnp.sort(probs, axis=-1)[:, -k][:, None]
    kept = jnp.where(probs >= kth, probs, 0.0)
    return kept / jnp.sum(kept, axis=-1, keepdims=True)


@functools.partial(jax.jit, static_argnums=0)
def _attn_jit(cfg_items, h, lw):
    cfg = dict(cfg_items)
    lw = {k: v.astype(jnp.float32) for k, v in lw.items()}
    h = h + mistral.attention(
        cfg, lw, mistral.rms_norm(h, lw["ln1_s"], cfg["rms_norm_eps"]))
    x = mistral.rms_norm(h, lw["ln2_s"], cfg["rms_norm_eps"])
    return h, x, gates(cfg, x, lw["wg"])


@jax.jit
def _expert_jit(acc, x, gate, w1, w3, w2):
    f32 = jnp.float32
    return acc + gate[:, None] * mistral.swiglu(
        x, w1.astype(f32), w3.astype(f32), w2.astype(f32))


def forward(cfg, weights, tokens):
    """Logits ``[T, V]`` float32 of one sequence ``tokens`` ``[T]``."""
    items = mistral._frozen(cfg)
    n, tokens = len(tokens), mistral.padded(tokens)
    with jax.default_matmul_precision("highest"):
        h = weights["tok"][tokens].astype(jnp.float32)
        for i in range(cfg["num_hidden_layers"]):
            h, x, g = _attn_jit(items, h,
                                {k: weights[k][i] for k in ATTN_KEYS})
            for e in range(cfg["num_local_experts"]):
                h = _expert_jit(h, x, g[:, e], weights["w1"][i, e],
                                weights["w3"][i, e], weights["w2"][i, e])
        return mistral._head_jit(items, weights["tok"], weights["lnf_s"],
                                 weights.get("head"), h)[:n]
