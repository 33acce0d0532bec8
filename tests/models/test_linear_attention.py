"""A model with linear-attention (Gated DeltaNet) layers beside its full
ones: ``apply`` (dense and flash), prefill + ``decode_step``,
``decode_chunk`` continuation and ``generate`` against the plain reference
``benchmark/reference/olmo_hybrid.py`` (the recurrence one position at a
time; nothing of ``elephas_tpu``), logits compared; the two-stack parameter
layout under the layer scan against the layers run one by one; the
``assumed`` readings as constructor arguments; the refusals. CPU, seeded
weights, tiny widths, float32.

Tolerance. Program and reference are both float32 here, the same
mathematics in another order (the chunkwise form, the packed state, fused
projections), so logits of order 1-4 agree to a few hundred float32
roundings: ``ATOL`` 5e-4 (seen: 1e-4 over 150 positions, 2e-4 after a
continuation chunk; eight reordered-norm layers of random weights amplify
a float32 rounding a few thousand times, so two orders of the same sums in
the residual stream, rms 4, part by up to 1e-3). The reference with its
state rounded to bfloat16 every position is 5e-3 or more away: the control
that the tolerance tells the configuration's float32 state from the nearest
precision below."""

import importlib.util
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from elephas_tpu.models.transformer import TransformerLM

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
_spec = importlib.util.spec_from_file_location(
    "_ref_olmo_hybrid",
    os.path.join(ROOT, "benchmark", "reference", "olmo_hybrid.py"))
ref = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(ref)

ATOL = 5e-4
PERIOD = ["linear_attention"] * 3 + ["full_attention"]
KW = dict(vocab=97, d_model=48, n_heads=4, n_layers=8, d_ff=64, max_len=256,
          pos_encoding="rotary", activation="swiglu", norm="rmsnorm",
          ffn_bias=False, norm_eps=1e-6, qk_norm="whole", rope_layers="none",
          norm_order="post", layer_types=PERIOD * 2, linear_heads=4,
          linear_key_head_dim=8, linear_value_head_dim=16,
          linear_allow_neg_eigval=True)
# the reference reads the PUBLISHED keys
CFG = {"hidden_size": 48, "num_attention_heads": 4, "num_key_value_heads": 4,
       "num_hidden_layers": 8, "layer_types": PERIOD * 2,
       "rms_norm_eps": 1e-6, "linear_num_key_heads": 4,
       "linear_num_value_heads": 4, "linear_key_head_dim": 8,
       "linear_value_head_dim": 16, "linear_conv_kernel_dim": 4,
       "linear_allow_neg_eigval": True,
       "rope_parameters": {"rope_theta": None}}


def _model(**kw):
    return TransformerLM(**{**KW, **kw})


def _params(model, seed=0):
    """Seeded weights with every norm scale perturbed and decays from fast
    to slow over the heads (``A_log`` -6 .. 1: alpha 0.998 .. 0.15 at the
    median gate)."""
    rng = np.random.default_rng(seed + 1)
    out = {}
    for k, v in model.init(seed).items():
        if k.endswith("_s"):
            v = v + 0.2 * rng.standard_normal(v.shape).astype(np.float32)
        elif k == "A_log":
            v = rng.uniform(-6.0, 1.0, v.shape).astype(np.float32)
        elif k == "dt_bias":
            v = rng.uniform(-1.0, 1.0, v.shape).astype(np.float32)
        out[k] = jnp.asarray(v)
    return out


def _tokens(shape, seed=2):
    return np.random.default_rng(seed).integers(0, 97, shape).astype(np.int32)


def _reference(params, toks, cfg=CFG, **readings):
    return np.stack([np.asarray(ref.forward(cfg, params, row, **readings))
                     for row in toks])


@pytest.fixture(scope="module")
def hybrid():
    m = _model()
    p = _params(m)
    toks = _tokens((2, 150))
    return m, p, toks, _reference(p, toks)


def test_leaves_are_stacked_by_kind_and_the_cache_holds_states():
    m = _model()
    shapes = {k: v.shape for k, v in m.param_shapes().items()}
    # norms and FFN over all 8 layers, the mixers over their own kind
    assert shapes["ln1_s"] == (8, 48) and shapes["w1"] == (8, 48, 64)
    assert shapes["wq"] == shapes["wo"] == (2, 48, 48)
    assert shapes["qn_s"] == shapes["kn_s"] == (2, 48)     # whole: 4 x 12
    assert shapes["lin_qkv"] == (6, 48, 4 * (8 + 8 + 16))
    assert shapes["lin_conv"] == (6, 4, 128)
    assert shapes["lin_ab"] == (6, 48, 8)
    assert shapes["A_log"] == shapes["dt_bias"] == (6, 4)
    assert shapes["lin_z"] == (6, 48, 64) and shapes["lin_o"] == (6, 64, 48)
    assert shapes["lin_norm_s"] == (6, 16)
    assert set(m._block_keys()) <= set(shapes)
    p = m.init(0)
    assert not p["A_log"].any() and not p["dt_bias"].any()
    assert (p["lin_norm_s"] == 1).all()
    assert (m.n_linear, m.hybrid, m._window_period()) == (6, True, 4)
    cache = jax.eval_shape(lambda: m.init_cache(5, length=256))
    assert {k: (v.shape, v.dtype) for k, v in cache.items()} == {
        "k": ((2, 5, 4, 256, 12), jnp.float32),
        "v": ((2, 5, 4, 256, 12), jnp.float32),
        "s": ((6, 5, 4, 8, 16), jnp.float32),      # 16 columns: no packing
        "conv": ((6, 5, 3 * 128), jnp.float32)}
    # the full layers alone are walked by the decode kernel
    assert m.decode_walks(m.init_cache(2, 256)) == [
        (256, None, False, 256, 2)]
    # where each layer's memory lives: by its number among its kind
    assert m._cache_slots() == ([], [
        (("s", "conv"), 0, 3), (("s", "conv"), 1, 3), (("s", "conv"), 2, 3),
        (("k", "v"), 0, 1)])
    # at the published sizes two heads share a tile of whole lanes
    big = _model(linear_heads=30, linear_key_head_dim=96,
                 linear_value_head_dim=192, compute_dtype="bfloat16")
    c = jax.eval_shape(lambda: big.init_cache(3, length=64))
    assert (c["s"].shape, c["s"].dtype) == ((6, 3, 15, 96, 384), jnp.float32)
    assert (c["conv"].shape, c["conv"].dtype) == ((6, 3, 3 * 11520),
                                                  jnp.bfloat16)


@pytest.mark.parametrize("attn", ["dense", "flash"])
def test_apply_is_the_reference(hybrid, attn):
    m, p, toks, want = hybrid
    pos = jnp.broadcast_to(jnp.arange(toks.shape[1]), toks.shape)
    got = np.asarray(m.apply(p, jnp.asarray(toks), pos, attn=attn))
    np.testing.assert_allclose(got, want, atol=ATOL)


def test_prefill_decode_steps_and_a_continuation_chunk(hybrid):
    """Prefill 100 positions, ten ``decode_step``s through cache and
    state, then the rest as ONE ``decode_chunk`` that continues from the
    state the steps left: logits against the reference's full forward."""
    m, p, toks, want = hybrid
    cache = m.init_cache(2, 256)
    logits, cache = m.prefill(p, jnp.asarray(toks[:, :100]), cache)
    np.testing.assert_allclose(logits, want[:, :100], atol=ATOL)
    step = jax.jit(lambda c, t, ps: m.decode_step(p, t, ps, c))
    for t in range(100, 110):
        logits, cache = step(cache, jnp.asarray(toks[:, t]), t)
        np.testing.assert_allclose(logits, want[:, t], atol=ATOL)
    logits, cache = m.decode_chunk(p, jnp.asarray(toks[:, 110:]), 110, cache)
    np.testing.assert_allclose(logits, want[:, 110:], atol=ATOL)
    # the same through per-row positions (a batch of slots)
    logits, _ = m.decode_step(p, jnp.asarray(toks[:, 149]),
                              jnp.asarray([149, 149]), cache)
    assert logits.shape == (2, 97)


def test_a_bfloat16_state_is_refused_by_the_tolerance(hybrid):
    """The control: the reference with its state carried in bfloat16, the
    nearest precision below the configuration's, is outside the tolerance
    the program passes."""
    m, p, toks, want = hybrid
    low = _reference(p, toks[:1], state_dtype="bfloat16")
    assert np.abs(low - want[:1]).max() > 10 * ATOL


def test_chunked_prefill_with_padding_equals_whole(hybrid):
    """``prefill_slot`` in chunks of 16 padded to 32 (``n_valid``), into
    slot 1 of three, equals the whole prompt at once; the other slots'
    state stays zero."""
    m, p, toks, want = hybrid
    cache = m.init_cache(3, 256)
    fn = jax.jit(lambda c, t, pos0, n: m.prefill_slot(p, t, 1, c, pos0=pos0,
                                                      n_valid=n))
    for a in range(0, 80, 16):
        chunk = np.zeros((1, 32), np.int32)
        chunk[0, :16] = toks[0, a:a + 16]
        chunk[0, 16:] = 5                      # padding that must not count
        logits, cache = fn(cache, jnp.asarray(chunk), a, 16)
        np.testing.assert_allclose(logits[0, :16], want[0, a:a + 16],
                                   atol=ATOL)
    whole, ref_cache = m.prefill_slot(p, jnp.asarray(toks[:1, :80]), 1,
                                      m.init_cache(3, 256))
    # (what the deeper layers keep carries the residual stream's float32
    # noise, a few 1e-4 of values of order 1: module docstring)
    np.testing.assert_allclose(cache["s"], ref_cache["s"], atol=2e-3)
    np.testing.assert_allclose(cache["conv"], ref_cache["conv"], atol=2e-3)
    assert not np.asarray(cache["s"][:, [0, 2]]).any()
    logits, _ = m.decode_step(p, jnp.asarray(toks[[0, 0, 0], 80]),
                              jnp.asarray([0, 80, 0]), cache)
    np.testing.assert_allclose(logits[1], want[0, 80], atol=ATOL)


def test_generate_follows_the_reference_greedily(hybrid):
    m, p, toks, _ = hybrid
    out = np.asarray(m.generate(p, jnp.asarray(toks[:, :20]), 8))
    assert out.shape == (2, 28)
    for row in out:
        logits = np.asarray(ref.forward(CFG, p, row[:-1]))[19:]
        top = logits.max(-1)
        chosen = logits[np.arange(8), row[20:]]
        # greedy tokens are the reference's best, or tie with it
        assert (top - chosen).max() < 1e-3


def test_scan_over_two_periods_equals_the_layers_one_by_one(hybrid):
    """The two-stack layout under the layer scan (two steps of four
    sub-layers, each leaf sliced by the layer's number among its kind)
    against ``_block_fwd`` called layer by layer with hand-made slices."""
    m, p, toks, _ = hybrid
    pos = jnp.broadcast_to(jnp.arange(toks.shape[1]), toks.shape)
    want, _ = m.apply_hidden(p, jnp.asarray(toks), pos, final_norm=False)
    h = m._embed(p, jnp.asarray(toks), pos)
    seen = {"full": 0, "linear": 0}
    for i, kind in enumerate(m.layer_kinds):
        own = m._LINEAR_KEYS if kind == "linear" else m._full_keys()
        lp = {k: p[k][seen[kind]] for k in own}
        lp.update({k: p[k][i] for k in ("ln1_s", "ln2_s", "w1", "w2", "w3")})
        seen[kind] += 1
        h, _, _, _ = m._block_fwd(
            h, lp, lambda q, k, v, rp=None: m._attend(
                q, k, v, "dense", "seq", window=None), "dense", "seq")
    # (a residual stream of rms 4 after eight reordered-norm layers: the
    # two orders of the same float32 sums part by up to 1e-3, and a leaf
    # sliced from the wrong layer by its own size)
    assert float(jnp.sqrt(jnp.mean(want * want))) > 2.0
    np.testing.assert_allclose(h, want, atol=5e-3)
    # ...and a single period (no scan at all: static slices) is the
    # reference too
    one = _model(n_layers=4, layer_types=PERIOD)
    p1 = _params(one, seed=3)
    cfg1 = {**CFG, "num_hidden_layers": 4, "layer_types": PERIOD}
    got = np.asarray(one.apply(p1, jnp.asarray(toks[:1, :70]), pos[:1, :70]))
    np.testing.assert_allclose(got, _reference(p1, toks[:1, :70], cfg1),
                               atol=ATOL)
    cache = one.init_cache(1, 128)
    logits, cache = one.prefill(p1, jnp.asarray(toks[:1, :69]), cache)
    logits, _ = one.decode_step(p1, jnp.asarray(toks[:1, 69]), 69, cache)
    np.testing.assert_allclose(logits, got[:, 69], atol=ATOL)


# each ``assumed`` reading is ONE constructor argument and one argument of
# the reference's ``forward``: the other reading agrees with the other
# reading and not with the default
READINGS = {
    "norm_order": (dict(norm_order="pre"), dict(norm_order="pre")),
    "qk_norm_per_head": (dict(qk_norm=True), dict(qk_norm="head")),
    "rope": (dict(rope_layers="all", rope_theta=1e4), dict(rope_theta=1e4)),
    "gate": (dict(linear_gate="sigmoid"), dict(gate="sigmoid")),
}


@pytest.mark.parametrize("model_kw,ref_kw", READINGS.values(),
                         ids=READINGS.keys())
def test_each_assumed_reading_is_one_argument(hybrid, model_kw, ref_kw):
    _, _, toks, _ = hybrid
    m = _model(**model_kw)
    p = _params(m)
    toks = toks[:1, :80]
    pos = jnp.arange(80)[None]
    got = np.asarray(m.apply(p, jnp.asarray(toks), pos))
    np.testing.assert_allclose(got, _reference(p, toks, **ref_kw), atol=ATOL)
    if "qk_norm" not in model_kw:      # (another leaf shape: no default run)
        assert np.abs(got - _reference(p, toks)).max() > 100 * ATOL
    cache = m.init_cache(1, 128)
    _, cache = m.prefill(p, jnp.asarray(toks[:, :79]), cache)
    logits, _ = m.decode_step(p, jnp.asarray(toks[:, 79]), 79, cache)
    np.testing.assert_allclose(logits, got[:, 79], atol=ATOL)


def test_state_dtype_is_the_cached_state():
    m = _model(state_dtype="bfloat16")
    assert m.init_cache(2, 64)["s"].dtype == jnp.bfloat16


REFUSALS = {
    "ring": lambda m, p: m.apply(p, jnp.zeros((1, 8), jnp.int32),
                                 jnp.arange(8)[None], attn="ring"),
    "ulysses": lambda m, p: m.apply(p, jnp.zeros((1, 8), jnp.int32),
                                    jnp.arange(8)[None], attn="ulysses"),
    "paged": lambda m, p: m._refuse_paged("decode_step_paged"),
    "speculative": lambda m, p: m.generate_speculative(
        p, jnp.zeros((1, 4), jnp.int32), 4, m, p),
    "layout": lambda m, p: m._refuse_layout("tensor parallelism"),
}
SENTENCES = {
    "ring": "no scan split over a sequence axis",
    "ulysses": "no scan split over a sequence axis",
    "paged": "no state pool beside the pages",
    "speculative": "cannot be rolled back",
    "layout": "stacks its mixer leaves by kind of layer",
}


@pytest.mark.parametrize("what", REFUSALS)
def test_what_cannot_run_it_refuses_by_name(what):
    m = _model()
    p = {k: jnp.asarray(v) for k, v in m.init(0).items()}
    with pytest.raises(NotImplementedError, match=SENTENCES[what]):
        REFUSALS[what](m, p)


def test_the_parallel_builders_refuse(monkeypatch):
    from elephas_tpu.models import fsdp_lm, pipeline_lm, sharded_generate
    from elephas_tpu.models import tensor_lm
    from elephas_tpu.models.transformer import build_mesh_sp

    m = _model()
    mesh = build_mesh_sp(data=1, seq=1, devices=jax.devices()[:1])
    with pytest.raises(NotImplementedError, match="by kind of layer"):
        sharded_generate._check_mesh_and_specs(m, mesh)
    with pytest.raises(NotImplementedError, match="by kind of layer"):
        fsdp_lm.build_lm_fsdp_train_step(m, mesh, None)
    with pytest.raises(NotImplementedError, match="by kind of layer"):
        pipeline_lm.build_lm_pp_train_step(m, mesh, None, n_micro=2)
    with pytest.raises(NotImplementedError, match="by kind of layer"):
        pipeline_lm.build_lm_pp_tp_train_step(m, mesh, None, n_micro=2)
    with pytest.raises(NotImplementedError, match="by kind of layer"):
        tensor_lm._validate_tp(m, tensor_lm.build_mesh_tp(
            data=1, model=1, devices=jax.devices()[:1]))
    # a plain model with the reordered norm: the builders have their own
    # pre-norm block
    post = TransformerLM(vocab=97, d_model=48, n_heads=4, n_layers=2,
                         d_ff=64, max_len=64, norm_order="post")
    with pytest.raises(NotImplementedError, match="norm_order='post'"):
        fsdp_lm.build_lm_fsdp_train_step(post, mesh, None)


def test_constructor_refuses_what_it_cannot_build():
    with pytest.raises(ValueError, match="layer_types"):
        _model(layer_types=PERIOD)                      # 4 entries, 8 layers
    with pytest.raises(ValueError, match="layer_types"):
        _model(layer_types=["sliding_attention"] * 8)
    with pytest.raises(ValueError, match="linear_heads"):
        _model(linear_heads=None)
    with pytest.raises(ValueError, match="at least one full_attention"):
        _model(layer_types=["linear_attention"] * 8)
    with pytest.raises(ValueError, match="no attn_window"):
        _model(attn_window=16)
    with pytest.raises(ValueError, match="linear_gate"):
        _model(linear_gate="tanh")
    with pytest.raises(ValueError, match="norm_order"):
        _model(norm_order="middle")
    with pytest.raises(ValueError, match="qk_norm"):
        _model(qk_norm="all")


def test_act_dtype_keeps_the_accumulators_and_halves_the_rounding_noise():
    """``act_dtype="float32"`` under bfloat16 compute: the matmuls take
    bfloat16 and give float32, so a sublayer rounds its matmuls' inputs and
    nothing else. Against the float32 reference on the same bfloat16-valued
    weights the logits are markedly closer than with every intermediate
    rounded (at these widths 0.55-0.7 of the distance, seed by seed; at the
    published widths on the chip 7.1% -> under 3% of the largest logit:
    PERF.md §6, PR 34), and the cached forwards still follow the uncached
    one."""
    wide = dict(d_model=128, d_ff=320, linear_heads=4, n_heads=4,
                linear_key_head_dim=16, linear_value_head_dim=32,
                compute_dtype="bfloat16")
    cfg = {**CFG, "hidden_size": 128, "linear_key_head_dim": 16,
           "linear_value_head_dim": 32}
    plain, kept = _model(**wide), _model(**wide, act_dtype="float32")
    assert (kept.init_cache(1, 64)["conv"].dtype,
            plain.init_cache(1, 64)["conv"].dtype) == (jnp.float32,
                                                       jnp.bfloat16)
    toks = _tokens((1, 96), seed=5)
    pos = jnp.arange(96)[None]
    ratios = []
    for seed in (0, 1, 2):
        p = {k: (v.astype(jnp.bfloat16) if v.ndim > 1 and not k.endswith("_s")
                 and k not in ("A_log", "dt_bias") else v)
             for k, v in _params(plain, seed).items()}
        want = _reference(p, toks, cfg)
        scale = np.abs(want).max(-1)
        err = [float((np.abs(np.asarray(m.apply(p, jnp.asarray(toks), pos),
                                        np.float32) - want).max(-1)
                      / scale).mean()) for m in (plain, kept)]
        ratios.append(err[1] / err[0])
    assert max(ratios) < 0.85 and np.mean(ratios) < 0.75, ratios
    cache = kept.init_cache(1, 128)
    logits, cache = kept.prefill(p, jnp.asarray(toks[:, :95]), cache)
    step, _ = kept.decode_step(p, jnp.asarray(toks[:, 95]), 95, cache)
    full = np.asarray(kept.apply(p, jnp.asarray(toks), pos), np.float32)
    assert np.abs(np.asarray(step, np.float32) - full[:, 95]).max() < 0.05 * \
        np.abs(full[:, 95]).max()
