"""Plain reference for the ``axk1`` family (A.X-K1): float32 ``jax.numpy``
at highest matmul precision, one sequence at a time, no kernel, no cache,
no batching, and the PUBLISHED form of latent attention only: keys and
values are multiplied out of the latent for every position, nothing is
absorbed into the query or the output. Imports nothing from
``elephas_tpu``; the program's decode step attends the latent rows
themselves, so the comparison proves that identity.

Written from the published ``config.json`` keys, which are the
DeepSeek-V3 family's; what they do not fix is under the configuration's
``assumed``. With ``x = N1(h)`` (RMSNorm, ``rms_norm_eps``), a layer:

- ``c_q = RMSNorm(x Wq_a)`` [q_lora_rank]; ``q = c_q Wq_b`` as ``heads`` of
  ``qk_nope_head_dim + qk_rope_head_dim`` = ``q_nope | q_pe``;
- ``[c | k_pe] = x Wkv_a`` [kv_lora_rank | qk_rope_head_dim]; ``c_kv =
  RMSNorm(c)``; ``q_pe`` and ``k_pe`` rotated (``k_pe`` is ONE key for all
  heads);
- ``[k_nope_h | v_h] = c_kv Wkv_b`` (``heads`` of ``qk_nope_head_dim +
  v_head_dim``); ``s_h(t, u) = (q_nope_h . k_nope_h,u + q_pe_h . k_pe,u) *
  scale``, causal softmax, ``o_h = sum p v_h,u``, ``h += concat(o_h) Wo``;
- YaRN (``rope_scaling``): each of the 32 frequencies ``theta^(-2i/64)`` a
  blend of itself and itself / ``factor`` by a linear ramp between the
  dimensions that turn ``beta_fast`` and ``beta_slow`` times in
  ``original_max_position_embeddings`` positions; ``scale = 192^-0.5 *
  m^2``, ``m = 0.1 * mscale_all_dim * ln(factor) + 1``; the tables are
  multiplied by the ratio of ``m(mscale)`` to ``m(mscale_all_dim)`` (1);
- layer 0: ``h += W2(silu(W1 N2(h)) * W3 N2(h))`` of ``intermediate_size``;
  the others, ``u = N2(h)``: ``s = sigmoid(u Wg)`` [192], :func:`select`
  chooses 8, ``w = routed_scaling_factor * s_e / sum of the chosen s``,
  ``h += sum over the chosen of w_e E_e(u) + S(u)``, SwiGLU of
  ``moe_intermediate_size``.

THE SHARE. As ``reference/exaone_moe.py``: the sum runs over the chosen
experts that are HELD (``held_experts``), the shared expert is added once.

Departures forced by the program's parameter layout (the arrays are the
program's own): matrices are ``[in, out]``; the rotation pairs dimension
``i`` with ``i + 32``; leading dense layers' leaves are ``dense_<leaf>``.
Attention walks the queries ``ROWS`` at a time and one matrix or one
expert is widened to float32 at a time, so a sequence of several thousand
tokens fits beside a 13 GiB program.
"""

import functools
import math

import jax
import jax.numpy as jnp

from benchmark.reference.mistral import PAD_TO, padded, rms_norm

# The share of checked positions whose logits must lie within
# ``checks.LOGIT_RTOL`` of this reference. Not 1.0, for the reason
# ``reference/exaone_moe.py`` gives: the program's residual stream is
# bfloat16, so where the 8th and 9th of a token's 192 scores lie within
# bfloat16's rounding the two choose different experts, and if one of them
# is held (one in sixteen is) the position's logits part by that expert's
# output. The limit lies between two readings at the published widths
# (PERF.md section 6; my chip run, PR 32): the program's lowest share over
# its 16 seeds, 0.906 of 128 positions, and this reference with the part this
# family adds, the cached latent rows, in the next precision below bfloat16
# (``lower="latent"``): 0.316 and 0.325 of 640 positions, which must fail
# (its expert matmuls there, ``lower="experts"``: 0.000);
# tests/benchmark/test_axk1.py holds the control to it at tiny widths.
MIN_SHARE = 0.8
ROWS = 128
FFN_COLS = 4608
ATTN = ("ln1_s", "wq_a", "q_a_norm", "wq_b", "wkv_a", "kv_a_norm", "wkv_b",
        "wo")
LOW = jnp.float8_e4m3fn      # "the nearest precision below" bfloat16


def _low(x, on: bool):
    """``x`` rounded through the lower precision, for the control."""
    return x.astype(LOW).astype(jnp.float32) if on else x


def yarn(cfg):
    """``(inv_freq tuple, table factor, softmax scale)`` of the published
    ``rope_scaling`` (type ``yarn``), ``rope_theta`` and head sizes."""
    rs, dim, theta = (cfg["rope_scaling"], cfg["qk_rope_head_dim"],
                      float(cfg["rope_theta"]))
    if rs["type"] != "yarn":
        raise ValueError(f"rope_scaling type {rs['type']!r}")
    factor, orig = float(rs["factor"]), rs["original_max_position_embeddings"]

    def dim_of(turns):
        return dim * math.log(orig / (turns * 2 * math.pi)) / (
            2 * math.log(theta))

    low = max(math.floor(dim_of(rs["beta_fast"])), 0)
    high = min(math.ceil(dim_of(rs["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    inv = []
    for i in range(dim // 2):
        own = theta ** (-2.0 * i / dim)
        ramp = min(max((i - low) / (high - low), 0.0), 1.0)
        inv.append(own * (1.0 - ramp) + own / factor * ramp)

    def m(s):
        return 0.1 * s * math.log(factor) + 1.0 if factor > 1 else 1.0

    width = cfg["qk_nope_head_dim"] + dim
    return (tuple(inv), m(rs["mscale"]) / m(rs["mscale_all_dim"]),
            width ** -0.5 * m(rs["mscale_all_dim"]) ** 2)


def rotate(x, inv_freq, table: float):
    """``x`` ``[T, H, d]`` at positions ``0..T-1``, pairs ``(i, i + d/2)``."""
    t, half = x.shape[0], x.shape[-1] // 2
    ang = (jnp.arange(t, dtype=jnp.float32)[:, None]
           * jnp.asarray(inv_freq, jnp.float32))
    cos, sin = (jnp.cos(ang)[:, None, :] * table,
                jnp.sin(ang)[:, None, :] * table)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(x, lw, static):
    """``x`` ``[T, D]`` (already normed) → ``[T, D]``, the published form."""
    heads, rank, nope, rope, vdim, eps, inv_freq, table, scale, low = static
    t = x.shape[0]
    f32 = jnp.float32
    cq = rms_norm(x @ lw["wq_a"].astype(f32), lw["q_a_norm"].astype(f32), eps)
    q = (cq @ lw["wq_b"].astype(f32)).reshape(t, heads, nope + rope)
    ckv = x @ lw["wkv_a"].astype(f32)
    c = rms_norm(ckv[:, :rank], lw["kv_a_norm"].astype(f32), eps)
    q_pe = rotate(q[..., nope:], inv_freq, table)
    k_pe = rotate(ckv[:, None, rank:], inv_freq, table)[:, 0]
    # the control: what the program caches of a position, a precision lower
    c, k_pe = _low(c, low), _low(k_pe, low)
    kv = (c @ lw["wkv_b"].astype(f32)).reshape(t, heads, nope + vdim)
    k_nope, v = kv[..., :nope], kv[..., nope:]
    ki = jnp.arange(t)[None, :]

    def rows(r0):
        qi = r0 + jnp.arange(ROWS)[:, None]
        qn = jax.lax.dynamic_slice_in_dim(q[..., :nope], r0, ROWS, axis=0)
        qr = jax.lax.dynamic_slice_in_dim(q_pe, r0, ROWS, axis=0)
        scores = (jnp.einsum("thd,shd->hts", qn, k_nope)
                  + jnp.einsum("thd,sd->hts", qr, k_pe)) * scale
        probs = jax.nn.softmax(
            jnp.where((ki <= qi)[None], scores, -jnp.inf), -1)
        return jnp.einsum("hts,shd->thd", probs, v).reshape(
            ROWS, heads * vdim)

    out = jax.lax.map(rows, jnp.arange(0, t, ROWS)).reshape(t, heads * vdim)
    return out @ lw["wo"].astype(f32)


@functools.partial(jax.jit, static_argnums=(0,))
def _attn_jit(static, h, lw):
    x = rms_norm(h, lw["ln1_s"].astype(jnp.float32), static[5])
    return h + attention(x, lw, static)


@functools.partial(jax.jit, static_argnums=(0,))
def _norm_jit(eps, h, scale):
    return rms_norm(h, scale.astype(jnp.float32), eps)


@functools.partial(jax.jit, static_argnums=(0,))
def _swiglu_jit(low, acc, x, gate, w1, w3, w2):
    """``acc + gate * SwiGLU(x)``; ``gate`` ``[T]`` or a scalar."""
    f32 = jnp.float32
    w1, w3, w2 = (_low(w.astype(f32), low) for w in (w1, w3, w2))
    u = jax.nn.silu(x @ w1) * (x @ w3)
    return acc + jnp.reshape(gate, (-1, 1)) * (_low(u, low) @ w2)


def select(scores, per_tok: int):
    """Which experts a token takes, as a mask over all of them. THE
    ``topk_method`` READING (the configuration's ``assumed``): ``"none"``,
    taken as it is written, is the ``per_tok`` largest of all scores, no
    group limit, no selection bias. The other reading, the best
    ``topk_group`` of ``n_group`` groups first, would be written here and
    nowhere else."""
    kth = jnp.sort(scores, axis=-1)[:, -per_tok][:, None]
    return scores >= kth


@functools.partial(jax.jit, static_argnums=(0, 1, 2))
def _gates_jit(per_tok, scale, norm, x, wg):
    """``[T, E]`` combine weights over ALL experts: zero outside
    :func:`select`'s choice of ``sigmoid(x wg)``."""
    s = jax.nn.sigmoid(x @ wg.astype(jnp.float32))
    w = jnp.where(select(s, per_tok), s, 0.0)
    if norm:
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    return w * scale


@jax.jit
def _head_jit(eps, lnf_s, head_w, h):
    return rms_norm(h, lnf_s.astype(jnp.float32), eps) @ head_w.astype(
        jnp.float32)


def held(cfg, weights):
    """``(first, count)`` of the router's experts whose weights are here."""
    first, count = cfg.get("held_experts") or (0, weights["wg"].shape[-1])
    if weights["w1"].shape[1] != count:
        raise ValueError(f"held_experts {count} but w1 holds "
                         f"{weights['w1'].shape[1]} experts")
    return int(first), int(count)


def _attn_static(cfg, low: bool):
    inv_freq, table, scale = yarn(cfg)
    return (cfg["num_attention_heads"], cfg["kv_lora_rank"],
            cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
            cfg["v_head_dim"], cfg["rms_norm_eps"], inv_freq, table, scale,
            low)


def sparse_ffn(cfg, weights, h, i, lower=None):
    """``h + sum over the chosen held experts of w_e E_e(u) + S(u)`` for
    sparse layer ``i``."""
    w = lambda k: weights[k][i]
    u = _norm_jit(cfg["rms_norm_eps"], h, w("ln2_s"))
    gates = _gates_jit(cfg["num_experts_per_tok"],
                       float(cfg["routed_scaling_factor"]),
                       bool(cfg["norm_topk_prob"]), u, w("wg"))
    first, count = held(cfg, weights)
    low = lower == "experts"
    u = _low(u, low)
    for e in range(count):
        # (one expert's slices of the stacks, not a layer's twelve: what is
        # queued ahead of the device stays small beside the engine)
        h = _swiglu_jit(low, h, u, gates[:, first + e],
                        *(weights[k][i, e] for k in ("w1", "w3", "w2")))
    if cfg.get("n_shared_experts"):
        h = _swiglu_jit(low, h, u, jnp.ones(()), w("ws1"), w("ws3"),
                        w("ws2"))
    return h


def hidden(cfg, weights, tokens, lower=None):
    """The residual stream ``[T_padded, D]`` after the last layer, before
    the final norm."""
    static = _attn_static(cfg, lower == "latent")
    h = weights["tok"][tokens].astype(jnp.float32)
    n_dense = cfg.get("first_k_dense_replace", 0)
    for l in range(cfg["num_hidden_layers"]):
        prefix, i = ("dense_", l) if l < n_dense else ("", l - n_dense)
        h = _attn_jit(static, h, {k: weights[prefix + k][i] for k in ATTN})
        if l < n_dense:
            # FFN_COLS of the 18,432 intermediate columns at a time (a
            # SwiGLU is a sum over them): all three matrices widened at
            # once are 1.6 GB, beside an engine that holds 12.8 GiB
            u = _norm_jit(cfg["rms_norm_eps"], h, weights["dense_ln2_s"][i])
            w1, w3, w2 = (weights[k][i] for k in ("dense_w1", "dense_w3",
                                                  "dense_w2"))
            for lo in range(0, w1.shape[-1], FFN_COLS):
                h = _swiglu_jit(False, h, u, jnp.ones(()),
                                w1[:, lo:lo + FFN_COLS],
                                w3[:, lo:lo + FFN_COLS],
                                w2[lo:lo + FFN_COLS])
        else:
            h = sparse_ffn(cfg, weights, h, i, lower=lower)
    return h


def forward(cfg, weights, tokens, lower=None):
    """Logits ``[T, V]`` float32 of one sequence ``tokens`` ``[T]``.
    ``lower`` (``"latent"`` or ``"experts"``) is the control: that part in
    the next precision below bfloat16, which the check must refuse."""
    assert PAD_TO % ROWS == 0
    n, tokens = len(tokens), padded(tokens)
    with jax.default_matmul_precision("highest"):
        h = hidden(cfg, weights, tokens, lower)
        return _head_jit(cfg["rms_norm_eps"], weights["lnf_s"],
                         weights["head"], h)[:n]
