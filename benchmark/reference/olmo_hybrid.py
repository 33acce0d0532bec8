"""Plain reference for the ``olmo_hybrid`` family (Olmo-Hybrid-7B): float32
``jax.numpy`` at highest matmul precision, one sequence at a time, no
kernel, no cache, no batching, no chunkwise form. Imports nothing from
``elephas_tpu``.

Written from the catalog row's keys (``layer_types``, the ``linear_*``
sizes, which are the argument names of the published Gated DeltaNet layer)
and the layer equations of ISSUE 34:

- *linear layer*: ``x W_qkv`` -> per channel a causal depthwise convolution
  of ``linear_conv_kernel_dim`` taps (no bias), as that many shifted adds,
  and SiLU; per head ``q, k`` of ``linear_key_head_dim`` scaled to unit
  length, ``q`` times ``dk ** -0.5``; ``beta = sigmoid(x W_b)`` (doubled
  under ``linear_allow_neg_eigval``); ``g = -exp(A_log) softplus(x W_a +
  dt_bias)``; THE RECURRENCE, one position at a time under ``lax.scan``,
  state ``S`` ``[dk, dv]`` a head from zero: ``S~ = exp(g_t) S``, ``u =
  beta_t (v_t - S~^T k_t)``, ``S = S~ + k_t u^T``, ``o_t = S^T q_t``; then
  ``RMSNorm_dv(o) * gate(x W_z)`` and the output projection. The program
  runs the chunkwise (WY) form over prompts and a Pallas kernel a decode
  step, so agreement with this proves both;
- *full layer*: ``q, k, v = x W_q, x W_k, x W_v``, causal softmax attention
  at ``head_dim ** -0.5`` over ``num_attention_heads`` heads, ``W_o``;
- SwiGLU FFN, final RMSNorm, untied head.

What the published config has no key for is an ARGUMENT of ``forward``
with the configuration's ``assumed`` reading as its default, so another
reading is a one-place change here (and one constructor argument in
``families/olmo_hybrid.py``):

- ``norm_order="post"``: the Olmo 2 / Olmo 3 family's reordered norm, ``h +
  N_a(Mixer(h))`` then ``h + N_f(FFN(h))`` (``"pre"``: ``h + Mixer(N(h))``);
- ``qk_norm="whole"``: the full layers norm q and k with one RMSNorm over
  the whole projection before the heads are split (``"head"``: per head;
  ``None``: none);
- ``rope_theta=None``: ``rope_parameters.rope_theta`` is null and read as
  written, the full layers rotate nothing (a number: half-split rotary);
- ``gate="silu"``: the linear layer's output gate (``"sigmoid"``);
- ``state_dtype=float32``: what the recurrent state is carried in from one
  position to the next (``bfloat16``: the nearest precision below it).

``lower="linear"`` is the CONTROL the comparison must refuse: the linear
layers' matmul inputs (what the projections read, and what the output
projection reads) rounded through ``LOW``, the nearest precision below the
bfloat16 the configuration computes in.

Departures forced by the program's parameter layout (the arrays are the
program's own): matrices are ``[in, out]``; ``lin_qkv`` holds the q, k and
v projections side by side and ``lin_conv`` ``[taps, channels]`` the
convolution over those columns, ``lin_conv[taps - 1]`` weighing the
position itself; ``lin_ab`` holds ``W_b`` then ``W_a``; the linear layers'
leaves are stacked over the linear layers alone, the full layers'
``wq``..``wo``, ``qn_s``, ``kn_s`` over the full layers alone, norms and
FFN over all layers. The L2 norms have 1e-6 under the root, as the
published layer has.
"""

import functools
import math

import jax
import jax.numpy as jnp

# The share of checked positions whose logits must lie within
# ``checks.LOGIT_RTOL`` (6% of the largest logit) of this reference. A dense
# model, no router whose ties could part the two, and still not 1.0, because
# the chip showed why not (PERF.md §6, PR 34): eight reordered-norm layers of
# RANDOM weights amplify bfloat16's rounding noise, so the program's error is
# no outlier but a band, 4-5% of the largest logit at most positions and
# 6.2-7.7% at the worst, with the Pallas kernel and the chunkwise form each
# exact to 1e-6 beside their own references on the chip and the program in
# float32 compute within 3e-5. The limit lies between two readings: the
# program's lowest share over twelve seeds, **0.922** (0.922-0.984; every
# matmul takes bfloat16 and gives float32, ``act_dtype``), and what must
# fail: this reference with the linear layers' matmul inputs in the next
# precision below bfloat16 (``lower="linear"``), **0.000** of 256 positions
# (worst 1.7 times the largest logit), and the program as it was first
# written, every intermediate rounded to bfloat16, **0.121** (worst 0.088).
# (The state alone in bfloat16 reads 1.000, worst 0.017: these seeded decays
# have a median of 0.5 and forget in a few positions, so a run cannot show
# that one; the CPU tests hold it at their own tolerance.)
MIN_SHARE = 0.8

NORM_ORDER = "post"
QK_NORM = "whole"
GATE = "silu"
STATE_DTYPE = "float32"

FULL = ("wq", "wk", "wv", "wo", "qn_s", "kn_s")
LINEAR = ("lin_qkv", "lin_conv", "lin_ab", "A_log", "dt_bias", "lin_z",
          "lin_norm_s", "lin_o")
EVERY = ("ln1_s", "ln2_s", "w1", "w3", "w2")
PAD_TO = 256
HEAD_BLOCK = 12544          # vocabulary columns widened to float32 at a time
LOW = jnp.float8_e4m3fn     # "the nearest precision below" bfloat16


def _low(x, on: bool):
    """``x`` rounded through the lower precision, for the control."""
    return x.astype(LOW).astype(jnp.float32) if on else x


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * scale


def unit(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


def rotate(x, theta):
    """``x`` ``[T, H, Dh]`` at positions ``0..T-1``, half-split pairs."""
    t, _, dh = x.shape
    half = dh // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def full_attention(cfg, lw, x, qk_norm, rope_theta):
    t = x.shape[0]
    heads, kv_heads = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    dh = cfg["hidden_size"] // heads
    eps = cfg["rms_norm_eps"]
    q, k, v = x @ lw["wq"], x @ lw["wk"], x @ lw["wv"]
    if qk_norm == "whole":
        q, k = rms_norm(q, lw["qn_s"], eps), rms_norm(k, lw["kn_s"], eps)
    q, k = q.reshape(t, heads, dh), k.reshape(t, kv_heads, dh)
    if qk_norm == "head":
        q, k = rms_norm(q, lw["qn_s"], eps), rms_norm(k, lw["kn_s"], eps)
    if rope_theta is not None:
        q, k = rotate(q, rope_theta), rotate(k, rope_theta)
    v = v.reshape(t, kv_heads, dh)
    k = jnp.repeat(k, heads // kv_heads, axis=1)
    v = jnp.repeat(v, heads // kv_heads, axis=1)
    scores = jnp.einsum("thd,shd->hts", q, k) / math.sqrt(dh)
    seen = jnp.arange(t)[None, :] <= jnp.arange(t)[:, None]
    probs = jax.nn.softmax(jnp.where(seen[None], scores, -jnp.inf), axis=-1)
    out = jnp.einsum("hts,shd->thd", probs, v).reshape(t, heads * dh)
    return out @ lw["wo"]


def short_conv(x, w):
    """``y_t = silu(sum_j w_j x_{t - (W - 1) + j})``, zeros before position
    0, as ``W`` shifted adds. ``x`` ``[T, C]``, ``w`` ``[W, C]``."""
    taps, t = w.shape[0], x.shape[0]
    padded_x = jnp.pad(x, ((taps - 1, 0), (0, 0)))
    return jax.nn.silu(sum(padded_x[j:j + t] * w[j] for j in range(taps)))


def delta_rule(q, k, v, g, beta, state_dtype):
    """THE recurrence, a position a step: ``q``/``k`` ``[T, H, dk]``, ``v``
    ``[T, H, dv]``, ``g``/``beta`` ``[T, H]`` -> ``o`` ``[T, H, dv]``. No
    matrix product: multiplies and sums in float32, the state rounded to
    ``state_dtype`` between positions."""
    def step(s, xs):
        q_t, k_t, v_t, g_t, b_t = xs
        s = s.astype(jnp.float32) * jnp.exp(g_t)[:, None, None]
        u = b_t[:, None] * (v_t - jnp.sum(s * k_t[:, :, None], axis=1))
        s = s + k_t[:, :, None] * u[:, None, :]
        return s.astype(state_dtype), jnp.sum(s * q_t[:, :, None], axis=1)

    s0 = jnp.zeros((q.shape[1], q.shape[2], v.shape[2]), state_dtype)
    return jax.lax.scan(step, s0, (q, k, v, g, beta))[1]


def linear_attention(cfg, lw, x, gate, state_dtype, low=False):
    t = x.shape[0]
    x = _low(x, low)
    heads = cfg["linear_num_value_heads"]
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    y = short_conv(x @ lw["lin_qkv"], lw["lin_conv"])
    q = unit(y[:, :heads * dk].reshape(t, heads, dk)) * dk ** -0.5
    k = unit(y[:, heads * dk:2 * heads * dk].reshape(t, heads, dk))
    v = y[:, 2 * heads * dk:].reshape(t, heads, dv)
    ab = x @ lw["lin_ab"]
    beta = jax.nn.sigmoid(ab[:, :heads])
    if cfg["linear_allow_neg_eigval"]:
        beta = 2.0 * beta
    g = -jnp.exp(lw["A_log"]) * jax.nn.softplus(ab[:, heads:]
                                                + lw["dt_bias"])
    o = delta_rule(q, k, v, g, beta, jnp.dtype(state_dtype))
    act = {"silu": jax.nn.silu, "sigmoid": jax.nn.sigmoid}[gate]
    z = (x @ lw["lin_z"]).reshape(t, heads, dv)
    o = rms_norm(o, lw["lin_norm_s"], cfg["rms_norm_eps"]) * act(z)
    return _low(o.reshape(t, heads * dv), low) @ lw["lin_o"]


def layer(cfg, kind, h, lw, norm_order=NORM_ORDER, qk_norm=QK_NORM,
          rope_theta=None, gate=GATE, state_dtype=STATE_DTYPE, lower=None):
    lw = {k: v.astype(jnp.float32) for k, v in lw.items()}
    eps = cfg["rms_norm_eps"]

    def mixer(x):
        if kind == "linear_attention":
            return linear_attention(cfg, lw, x, gate, state_dtype,
                                    lower == "linear")
        return full_attention(cfg, lw, x, qk_norm, rope_theta)

    def ffn(x):
        return (jax.nn.silu(x @ lw["w1"]) * (x @ lw["w3"])) @ lw["w2"]

    if norm_order == "post":
        h = h + rms_norm(mixer(h), lw["ln1_s"], eps)
        return h + rms_norm(ffn(h), lw["ln2_s"], eps)
    h = h + mixer(rms_norm(h, lw["ln1_s"], eps))
    return h + ffn(rms_norm(h, lw["ln2_s"], eps))


def _frozen(cfg):
    return tuple(sorted((k, v) for k, v in cfg.items()
                        if isinstance(v, (int, float, bool, type(None)))))


@functools.partial(jax.jit, static_argnums=(0, 1, 2))
def _layer_jit(cfg_items, kind, readings, h, lw):
    return layer(dict(cfg_items), kind, h, lw, **dict(readings))


@functools.partial(jax.jit, static_argnums=0)
def _norm_jit(eps, h, scale):
    return rms_norm(h, scale.astype(jnp.float32), eps)


@jax.jit
def _head_block_jit(h, w):
    return h @ w.astype(jnp.float32)


def padded(tokens):
    """``tokens`` right-padded with zeros to a multiple of ``PAD_TO``:
    every layer is causal (the convolution and the recurrence too), so no
    real position sees the padding; callers cut the result back."""
    tokens = jnp.asarray(tokens, jnp.int32)
    return jnp.pad(tokens, (0, -len(tokens) % PAD_TO))


def forward(cfg, weights, tokens, norm_order=NORM_ORDER, qk_norm=QK_NORM,
            rope_theta=None, gate=GATE, state_dtype=STATE_DTYPE, lower=None):
    """Logits ``[T, V]`` float32 of one sequence ``tokens`` ``[T]``. The
    keyword arguments are the ``assumed`` readings (module docstring);
    ``rope_theta`` defaults to the configuration's
    ``rope_parameters.rope_theta``, which is null. ``lower="linear"`` is
    the control: the linear layers' matmul inputs in the next precision
    below bfloat16, which the check must refuse."""
    if rope_theta is None:
        rope_theta = (cfg.get("rope_parameters") or {}).get("rope_theta")
    readings = (("gate", gate), ("norm_order", norm_order),
                ("qk_norm", qk_norm), ("rope_theta", rope_theta),
                ("state_dtype", str(state_dtype)), ("lower", lower))
    items = _frozen(cfg)
    n, tokens = len(tokens), padded(tokens)
    seen = {"full_attention": 0, "linear_attention": 0}
    with jax.default_matmul_precision("highest"):
        h = weights["tok"][tokens].astype(jnp.float32)
        for i, kind in enumerate(cfg["layer_types"]):
            own = LINEAR if kind == "linear_attention" else FULL
            lw = {k: weights[k][seen[kind]] for k in own if k in weights}
            lw.update({k: weights[k][i] for k in EVERY})
            seen[kind] += 1
            h = _layer_jit(items, kind, readings, h, lw)
        h = _norm_jit(cfg["rms_norm_eps"], h, weights["lnf_s"])
        head = weights["head"]
        return jnp.concatenate(
            [_head_block_jit(h, head[:, a:a + HEAD_BLOCK])
             for a in range(0, head.shape[1], HEAD_BLOCK)], axis=1)[:n]
