"""Plain reference for the Mistral family: float32 ``jax.numpy`` at highest
matmul precision, one sequence at a time, no kernel, no cache, no batching.
Imports nothing from ``elephas_tpu``.

Written from the published description: token embedding; per layer
pre-RMSNorm, grouped-query causal attention with rotary positions (and a
sliding window where the config has one), residual, pre-RMSNorm, SwiGLU
(``down(silu(gate(x)) * up(x))``), residual; final RMSNorm; untied head.

Departures from the published code, forced by the program's parameter
layout (the arrays are the program's own): matrices are ``[in, out]``
(``x @ w``) where the checkpoint stores ``[out, in]``; the rotation pairs
dimension ``i`` with ``i + head_dim/2`` (the half-split form that
Hugging Face's conversion produces; Mistral's own code interleaves pairs);
``w1``/``w3``/``w2`` are gate/up/down. Layers are stacked on a leading
axis, and ``forward`` walks them one at a time so that only one layer is
ever widened to float32.
"""

import functools
import math

import jax
import jax.numpy as jnp

# the share of positions that must agree with the program (checks.py)
MIN_SHARE = 1.0
LAYER_KEYS = ("ln1_s", "wq", "wk", "wv", "wo", "ln2_s", "w1", "w3", "w2")


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * scale


def rotate(x, theta):
    """``x`` ``[T, H, Dh]`` at positions ``0..T-1``."""
    t, _, dh = x.shape
    half = dh // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(cfg, lw, x):
    t = x.shape[0]
    heads, kv_heads = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    dh = cfg["hidden_size"] // heads
    q = rotate((x @ lw["wq"]).reshape(t, heads, dh), cfg["rope_theta"])
    k = rotate((x @ lw["wk"]).reshape(t, kv_heads, dh), cfg["rope_theta"])
    v = (x @ lw["wv"]).reshape(t, kv_heads, dh)
    k = jnp.repeat(k, heads // kv_heads, axis=1)
    v = jnp.repeat(v, heads // kv_heads, axis=1)
    scores = jnp.einsum("thd,shd->hts", q, k) / math.sqrt(dh)
    qi, ki = jnp.arange(t)[:, None], jnp.arange(t)[None, :]
    seen = ki <= qi
    window = cfg.get("sliding_window")
    if window is not None:
        seen = seen & (ki > qi - window)
    probs = jax.nn.softmax(jnp.where(seen[None], scores, -jnp.inf), axis=-1)
    out = jnp.einsum("hts,shd->thd", probs, v).reshape(t, heads * dh)
    return out @ lw["wo"]


def swiglu(x, gate, up, down):
    return (jax.nn.silu(x @ gate) * (x @ up)) @ down


def layer(cfg, h, lw):
    lw = {k: v.astype(jnp.float32) for k, v in lw.items()}
    h = h + attention(cfg, lw, rms_norm(h, lw["ln1_s"], cfg["rms_norm_eps"]))
    x = rms_norm(h, lw["ln2_s"], cfg["rms_norm_eps"])
    return h + swiglu(x, lw["w1"], lw["w3"], lw["w2"])


def head(cfg, weights, h):
    h = rms_norm(h, weights["lnf_s"].astype(jnp.float32), cfg["rms_norm_eps"])
    out = (weights["tok"].T if cfg.get("tie_word_embeddings")
           else weights["head"])
    return h @ out.astype(jnp.float32)


def _frozen(cfg):
    return tuple(sorted((k, v) for k, v in cfg.items()
                        if isinstance(v, (int, float, bool, type(None)))))


@functools.partial(jax.jit, static_argnums=0)
def _layer_jit(cfg_items, h, lw):
    return layer(dict(cfg_items), h, lw)


@functools.partial(jax.jit, static_argnums=0)
def _head_jit(cfg_items, tok, lnf_s, head_w, h):
    return head(dict(cfg_items), {"lnf_s": lnf_s, "head": head_w, "tok": tok},
                h)


PAD_TO = 256


def padded(tokens):
    """``tokens`` right-padded with zeros to a multiple of ``PAD_TO``, so
    that sequences of any length compile a handful of programs. Attention
    is causal and everything else is per token, so no real position sees
    the padding; callers cut the result back to the real length."""
    tokens = jnp.asarray(tokens, jnp.int32)
    return jnp.pad(tokens, (0, -len(tokens) % PAD_TO))


def forward(cfg, weights, tokens):
    """Logits ``[T, V]`` float32 of one sequence ``tokens`` ``[T]``."""
    items = _frozen(cfg)
    n, tokens = len(tokens), padded(tokens)
    with jax.default_matmul_precision("highest"):
        h = weights["tok"][tokens].astype(jnp.float32)
        for i in range(cfg["num_hidden_layers"]):
            h = _layer_jit(items, h, {k: weights[k][i] for k in LAYER_KEYS})
        return _head_jit(items, weights["tok"], weights["lnf_s"],
                         weights.get("head"), h)[:n]


def mean_loss(cfg, weights, tokens, targets):
    """Mean next-token cross-entropy of one sequence; a pure function of
    ``weights`` (jit and differentiate it whole: training weights are
    float32 already, so nothing is widened)."""
    with jax.default_matmul_precision("highest"):
        h = weights["tok"][tokens].astype(jnp.float32)
        for i in range(cfg["num_hidden_layers"]):
            h = layer(cfg, h, {k: weights[k][i] for k in LAYER_KEYS})
        logits = head(cfg, weights, h)
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, targets[:, None], axis=-1))


def loss_and_grads(cfg, weights, tokens, targets, leaves):
    """``(loss, {leaf: gradient})`` for the named leaves only."""
    frozen = {k: v for k, v in cfg.items()
              if isinstance(v, (int, float, bool, type(None)))}

    def f(part, rest, tokens, targets):
        return mean_loss(frozen, {**rest, **part}, tokens, targets)

    part = {k: weights[k] for k in leaves}
    rest = {k: v for k, v in weights.items() if k not in leaves}
    return jax.jit(jax.value_and_grad(f))(part, rest, tokens, targets)
