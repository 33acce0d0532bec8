"""Latent (MLA) attention: low-rank q, one joint latent with its norm and
one shared rotary key a position, keys and values of unequal widths, YaRN
rotary with its softmax scale; the published form on the uncached and the
chunk forward, the ABSORBED form on the decode step, a cache of latent
rows. CPU, seeded weights, tiny widths, float32."""

import math

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from elephas_tpu.models.transformer import (MoETransformerLM, TransformerLM,
                                            yarn_rope)
from elephas_tpu.ops.ring_attention import attention_reference

YARN = {"beta_fast": 32, "beta_slow": 1, "factor": 32, "mscale": 1,
        "mscale_all_dim": 1, "original_max_position_embeddings": 64,
        "type": "yarn"}
LATENT = dict(q_lora_rank=24, kv_lora_rank=128, qk_nope_head_dim=16,
              qk_rope_head_dim=8, v_head_dim=12, rope_scaling=YARN)
BASE = dict(vocab=97, d_model=48, n_heads=4, n_layers=3, d_ff=64,
            max_len=600, pos_encoding="rotary", activation="swiglu",
            norm="rmsnorm", ffn_bias=False, norm_eps=1e-6)


def _model(**kw):
    return TransformerLM(**{**BASE, **LATENT, **kw})


def _moe(**kw):
    return MoETransformerLM(
        n_experts=12, k=4, dense_layers=1, d_ff_dense=80, scoring="sigmoid",
        routed_scale=2.5, n_shared=1, held=(3, 3), aux_weight=0.0,
        **{**BASE, **LATENT, "d_ff": 16, **kw})


def _params(model, seed=0):
    rng = np.random.default_rng(seed + 1)
    return {k: jnp.asarray(
        v + 0.2 * rng.standard_normal(v.shape).astype(np.float32)
        if k.endswith(("_s", "_norm")) else v)
        for k, v in model.init(seed).items()}


def _tokens(shape, seed=2):
    return np.random.default_rng(seed).integers(0, 97, shape).astype(np.int32)


def _full(model, params, toks):
    t = toks.shape[1]
    return np.asarray(model.apply(
        params, jnp.asarray(toks), jnp.broadcast_to(jnp.arange(t),
                                                    toks.shape)))


def test_leaves_and_the_cache_of_latent_rows():
    m = _model()
    shapes = {k: v.shape for k, v in m.param_shapes().items()}
    assert shapes["wq_a"] == (3, 48, 24) and shapes["q_a_norm"] == (3, 24)
    assert shapes["wq_b"] == (3, 24, 4 * (16 + 8))
    assert shapes["wkv_a"] == (3, 48, 128 + 8)
    assert shapes["kv_a_norm"] == (3, 128)
    assert shapes["wkv_b"] == (3, 128, 4 * (16 + 12))   # stored once
    assert shapes["wo"] == (3, 4 * 12, 48)
    assert not {"wq", "wk", "wv"} & set(shapes)
    assert set(m._block_keys()) <= set(shapes)
    p = m.init(0)
    assert (p["q_a_norm"] == 1).all() and (p["kv_a_norm"] == 1).all()
    # ONE stack, keys alone: the row is the latent, the shared rotary key
    # and zeros up to whole lanes (128 + 8 -> 256), T in whole blocks
    cache = m.init_cache(5, length=300)
    assert {k: v.shape for k, v in cache.items()} == {
        "k": (3, 5, 1, 512, 256)}
    # (a visit of the latent kernel covers the whole 512-row cache)
    assert m.latent_row == 256 and m.decode_walks(cache) == [
        (512, None, False, 512, 3)]
    assert m._cache_slots() == ([], [(("k",), 0, 1)])
    # bytes a position a layer at the published sizes: 512 + 64 -> 640
    big = TransformerLM(**{**BASE, "d_model": 64, "compute_dtype": "bfloat16",
                           **LATENT, "kv_lora_rank": 512,
                           "qk_rope_head_dim": 64})
    row = jax.eval_shape(lambda: big.init_cache(1, 256))["k"]
    assert row.shape[-1] * row.dtype.itemsize == 1280
    # a dense GQA model's cache is what it was
    assert set(TransformerLM(**BASE).init_cache(1, 64)) == {"k", "v"}


def test_yarn_frequencies_and_the_scale_by_hand():
    """The published A.X-K1 numbers: 64 rotary dimensions, theta 10000,
    factor 32, beta 32 / 1 at 4096: dimensions 0-10 keep their frequency,
    23-31 are divided by 32, a linear ramp between; scale 192^-0.5 *
    1.8133."""
    rs = {**YARN, "original_max_position_embeddings": 4096}
    inv, table, soft = yarn_rope(64, 10000.0, rs)
    own = 10000.0 ** (-np.arange(32) / 32)
    low = math.floor(64 * math.log(4096 / (32 * 2 * math.pi))
                     / (2 * math.log(10000)))
    high = math.ceil(64 * math.log(4096 / (1 * 2 * math.pi))
                     / (2 * math.log(10000)))
    assert (low, high) == (10, 23)
    np.testing.assert_allclose(inv[:11], own[:11], rtol=1e-6)
    np.testing.assert_allclose(inv[23:], own[23:] / 32, rtol=1e-6)
    ramp = (15 - 10) / (23 - 10)
    np.testing.assert_allclose(
        inv[15], own[15] * (1 - ramp) + own[15] / 32 * ramp, rtol=1e-6)
    m = 0.1 * math.log(32) + 1
    assert table == 1.0 and soft == pytest.approx(m * m)
    assert m * m == pytest.approx(1.8133, abs=1e-4)
    big = TransformerLM(**{**BASE, "d_model": 64, **LATENT,
                           "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
                           "rope_scaling": rs})
    assert big.attn_scale == pytest.approx(192 ** -0.5 * 1.8133, rel=1e-4)
    np.testing.assert_allclose(big._inv_freq, inv)
    # without rope_scaling: plain frequencies, plain scale
    plain = _model(rope_scaling=None)
    assert plain._inv_freq is None and plain.attn_scale == 24 ** -0.5
    with pytest.raises(ValueError, match="only 'yarn'"):
        yarn_rope(64, 1e4, {**rs, "type": "linear"})
    with pytest.raises(ValueError, match="scales the rotary tables"):
        _model(rope_scaling={**YARN, "mscale": 0.5})


def test_the_published_form_by_hand():
    """One layer's attention written out from the equations (numpy,
    float64) against the program's uncached forward."""
    m = _model(n_layers=1)
    p = _params(m)
    toks = _tokens((1, 40))
    x = np.asarray(p["tok"], np.float64)[toks[0]]

    def rms(v, s):
        return v / np.sqrt((v * v).mean(-1, keepdims=True) + 1e-6) * s

    lp = {k: np.asarray(v[0], np.float64) for k, v in p.items()
          if v.ndim > 1 and k not in ("tok", "head")}
    h = rms(x, lp["ln1_s"])
    q = (rms(h @ lp["wq_a"], lp["q_a_norm"]) @ lp["wq_b"]).reshape(40, 4, 24)
    ckv = h @ lp["wkv_a"]
    c, k_pe = rms(ckv[:, :128], lp["kv_a_norm"]), ckv[:, 128:]
    kv = (c @ lp["wkv_b"]).reshape(40, 4, 28)
    inv = np.asarray(m._inv_freq, np.float64)
    ang = np.arange(40)[:, None] * inv

    def rot(v):                       # [T, H, 8], pairs (i, i + 4)
        a, b = v[..., :4], v[..., 4:]
        cos, sin = np.cos(ang)[:, None], np.sin(ang)[:, None]
        return np.concatenate([a * cos - b * sin, b * cos + a * sin], -1)

    q_pe, k_pe = rot(q[..., 16:]), rot(k_pe[:, None])[:, 0]
    s = (np.einsum("thd,uhd->htu", q[..., :16], kv[..., :16])
         + np.einsum("thd,ud->htu", q_pe, k_pe)) * m.attn_scale
    s = np.where(np.tril(np.ones((40, 40), bool))[None], s, -np.inf)
    pr = np.exp(s - s.max(-1, keepdims=True))
    pr /= pr.sum(-1, keepdims=True)
    o = np.einsum("htu,uhd->thd", pr, kv[..., 16:]).reshape(40, 48)
    x = x + o @ lp["wo"]
    u = rms(x, lp["ln2_s"])
    g = u @ lp["w1"]
    x = x + (g / (1 + np.exp(-g)) * (u @ lp["w3"])) @ lp["w2"]
    want = rms(x, np.asarray(p["lnf_s"], np.float64)) @ np.asarray(
        p["head"], np.float64)
    np.testing.assert_allclose(_full(m, p, toks)[0], want, atol=2e-5)


@pytest.mark.parametrize("build", [_model, _moe])
def test_prefill_then_absorbed_decode_equals_the_full_forward(build):
    """``prefill`` writes the latent rows; ``decode_step`` attends them in
    the ABSORBED form (W_UK into the query, W_UV into the output): the same
    logits as the published form over the whole sequence."""
    m = build()
    p = _params(m)
    toks = _tokens((2, 306))
    full = _full(m, p, toks)
    cache = m.init_cache(2, length=400)
    lg, cache = m.prefill(p, jnp.asarray(toks[:, :300]), cache)
    np.testing.assert_allclose(np.asarray(lg), full[:, :300], atol=3e-5)
    step = jax.jit(m.decode_step)
    for t in range(300, 306):
        lg, cache = step(p, jnp.asarray(toks[:, t]), t, cache)
        np.testing.assert_allclose(np.asarray(lg), full[:, t], atol=3e-5)
    # per-row positions: row 1 steps back and repairs its own row
    lg, cache = step(p, jnp.asarray(toks[:, [305, 200]].diagonal() * 0
                                    + toks[[0, 1], [305, 200]]),
                     jnp.asarray([305, 200]), cache)
    np.testing.assert_allclose(np.asarray(lg)[0], full[0, 305], atol=3e-5)
    np.testing.assert_allclose(np.asarray(lg)[1], full[1, 200], atol=3e-5)


def test_decode_chunk_past_a_start_and_per_row():
    m = _model()
    p = _params(m)
    toks = _tokens((2, 310))
    full = _full(m, p, toks)
    cache = m.init_cache(2, length=520)
    a, cache = m.decode_chunk(p, jnp.asarray(toks[:, :256]), 0, cache)
    b, cache = m.decode_chunk(p, jnp.asarray(toks[:, 256:300]), 256, cache)
    np.testing.assert_allclose(np.asarray(a), full[:, :256], atol=3e-5)
    np.testing.assert_allclose(np.asarray(b), full[:, 256:300], atol=3e-5)
    # rows at different positions, one of them re-scoring what it holds
    rows = np.stack([toks[0, 300:304], toks[1, 290:294]])
    c, cache = m.decode_chunk(p, jnp.asarray(rows), jnp.asarray([300, 290]),
                              cache)
    np.testing.assert_allclose(np.asarray(c)[0], full[0, 300:304], atol=3e-5)
    np.testing.assert_allclose(np.asarray(c)[1], full[1, 290:294], atol=3e-5)
    # into one slot of a multi-slot cache, in chunks
    cache = m.init_cache(3, length=520)
    a, cache = m.prefill_slot(p, jnp.asarray(toks[:1, :128]), 1, cache)
    b, cache = m.prefill_slot(p, jnp.asarray(toks[:1, 128:256]), 1, cache,
                              pos0=128)
    np.testing.assert_allclose(np.asarray(b), full[:1, 128:256], atol=3e-5)
    assert not np.asarray(cache["k"][:, 0]).any()
    assert not np.asarray(cache["k"][:, 2]).any()


def test_the_chunk_forward_leaves_the_dead_horizon_alone():
    """A chunk at the start of a long horizon multiplies keys and values
    out of a static bucket of rows, not of the whole horizon: the program
    holds one branch a bucket (the cache's length, its half, quarter,
    eighth, never under the chunk) and picks one by ``pos0 + S`` as it
    runs."""
    m = _model(n_layers=1)
    p = _params(m)
    cache = m.init_cache(1, length=2048)
    toks = jnp.zeros((1, 256), jnp.int32)

    def widths(s):
        txt = str(jax.make_jaxpr(
            lambda c, pos0: m.decode_chunk(p, toks[:, :s], pos0, c))(
                cache, 0))
        # keys multiplied out: [1, n, 4 * (16 + 12)] products, one a branch
        return sorted({n for n in (256, 512, 1024, 2048)
                       if f"f32[1,{n},112]" in txt})

    assert widths(256) == [256, 512, 1024, 2048]
    assert widths(8) == [256, 512, 1024, 2048]
    short = m.init_cache(1, length=256)
    assert "cond" not in str(jax.make_jaxpr(
        lambda c: m.decode_chunk(p, toks, 0, c))(short))
    # the branch taken is the smallest that holds pos0 + S
    full = _full(m, p, _tokens((1, 300)))
    cache = m.init_cache(1, length=2048)
    lg, cache = m.decode_chunk(p, jnp.asarray(_tokens((1, 300))[:, :250]), 0,
                               cache)
    lg, cache = m.decode_chunk(p, jnp.asarray(_tokens((1, 300))[:, 250:300]),
                               250, cache)          # 300 > 256: next bucket
    np.testing.assert_allclose(np.asarray(lg), full[:, 250:300], atol=3e-5)


def test_generate_runs_through_the_latent_cache():
    m = _model()
    p = _params(m)
    pr = _tokens((2, 9))
    out = np.asarray(m.generate(p, jnp.asarray(pr), 6))
    full = _full(m, p, out[:, :-1])
    assert (out[:, 9:] == full[:, 8:].argmax(-1)).all()


def test_every_path_that_cannot_run_it_says_why():
    m, moe = _model(), _moe()
    p = _params(m)
    toks = jnp.zeros((1, 8), jnp.int32)
    pos = jnp.arange(8)[None]
    for attn in ("flash", "ring", "ulysses"):
        with pytest.raises(NotImplementedError,
                           match="keys and values of one head size"):
            m.apply(p, toks, pos, attn=attn)
    with pytest.raises(NotImplementedError, match="no latent page pool"):
        m._refuse_paged("decode_step_paged")
    with pytest.raises(NotImplementedError, match="no latent page pool"):
        m.decode_step_paged(p, toks[0, :1], 0, {}, None, 16)
    from elephas_tpu.serving import ServingEngine
    with pytest.raises(NotImplementedError, match="one stack of latent"):
        ServingEngine(m, p, n_slots=2, max_len=64, paged=True)
    with pytest.raises(NotImplementedError, match="one stack of latent"):
        ServingEngine(m, p, n_slots=2, max_len=64, mesh=object())
    from elephas_tpu.models import sharded_generate, tensor_lm, moe_tp
    with pytest.raises(NotImplementedError, match="serve it unsharded"):
        sharded_generate._check_mesh_and_specs(m, None)
    with pytest.raises(NotImplementedError, match="no head owns"):
        tensor_lm._validate_tp(m, None)
    with pytest.raises(NotImplementedError, match="no head owns"):
        moe_tp._validate_moe_tp(moe, None)
    # the arguments that do not go together, at construction
    with pytest.raises(ValueError, match="no window mask or ring"):
        _model(attn_window=8)
    with pytest.raises(ValueError, match="needs q_lora_rank"):
        TransformerLM(**BASE, kv_lora_rank=16)
    with pytest.raises(ValueError, match="give kv_lora_rank"):
        TransformerLM(**BASE, rope_scaling=YARN)


def test_dense_path_scale_and_unequal_widths():
    rng = np.random.default_rng(0)
    q, k = (jnp.asarray(rng.standard_normal((1, 6, 2, 8)), jnp.float32)
            for _ in range(2))
    v = jnp.asarray(rng.standard_normal((1, 6, 2, 5)), jnp.float32)
    out = attention_reference(q, k, v, causal=True, scale=0.5)
    assert out.shape == (1, 6, 2, 5)
    same = attention_reference(q * (0.5 * 8 ** 0.5), k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(same), atol=1e-6)
