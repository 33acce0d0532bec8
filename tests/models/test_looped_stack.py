"""A LOOPED stack (``passes``: Ouro's ``total_ut_steps``, the whole layer
stack run several times a token, a cache layer a pass and layer) and the
sandwich norm (``norm_order="sandwich"``): ``apply``, prefill +
``decode_step``, a ``decode_chunk`` continuation, ``generate`` and the
serving engine against the plain reference ``benchmark/reference/ouro.py``
(the whole sequence a pass, no cache; nothing of ``elephas_tpu``), logits
compared; ``passes=1`` is the stack walked once, program and bits; the
cache index ``u * L + l`` pinned; the engine's counters and spans; the
refusals. CPU, seeded weights, tiny widths, float32.

Tolerance. Program and reference are both float32 here, the same
mathematics in another order (a cache, blocked attention, a scan), so
logits of order 1 agree to a few float32 roundings amplified by 12 layer
applications: ``ATOL`` 1e-4 (seen: under 2e-5). Another reading of the
block (pre-norm, no norm between passes, one pass fewer) moves them by
0.1 or more."""

import importlib.util
import os
import types

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from elephas_tpu.models.transformer import TransformerLM
from elephas_tpu.serving import ServingEngine
from elephas_tpu.serving import engine as engine_mod

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
_spec = importlib.util.spec_from_file_location(
    "_ref_ouro", os.path.join(ROOT, "benchmark", "reference", "ouro.py"))
ref = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(ref)

ATOL = 1e-4
L, U = 3, 4
BASE = dict(vocab=97, d_model=48, n_heads=4, n_kv_heads=4, head_dim=12,
            n_layers=L, d_ff=64, max_len=256, pos_encoding="rotary",
            rope_theta=1e6, activation="swiglu", norm="rmsnorm",
            ffn_bias=False, norm_eps=1e-6)
KW = dict(BASE, norm_order="sandwich", passes=U)
# the reference reads the PUBLISHED keys
CFG = {"hidden_size": 48, "num_attention_heads": 4, "num_key_value_heads": 4,
       "head_dim": 12, "num_hidden_layers": L, "rms_norm_eps": 1e-6,
       "rope_theta": 1e6, "total_ut_steps": U}


def _model(**kw):
    return TransformerLM(**{**KW, **kw})


def _params(m, seed=0):
    """``init``'s weights with norm scales drawn around 1, so that no
    reading of the norms hides behind a unit scale."""
    rng = np.random.default_rng(seed + 100)
    return {k: jnp.asarray(v * (1.0 + 0.2 * rng.standard_normal(v.shape))
                           if k.endswith("_s") else v)
            for k, v in m.init(seed).items()}


def _tokens(n, seed=3):
    return np.random.default_rng(seed).integers(0, 97, n).astype(np.int32)


def _reference(p, toks, **kw):
    return np.asarray(ref.forward(CFG, p, toks, **kw))


@pytest.fixture(scope="module")
def looped():
    m = _model()
    return m, _params(m)


READINGS = {
    "sandwich": ({}, {}),
    "pre": (dict(norm_order="pre"), dict(norm_order="pre")),
    "no_pass_norm": (dict(pass_norm=False), dict(pass_norm=False)),
    "two_passes": (dict(passes=2), dict(passes=2)),
    "one_pass": (dict(passes=1), dict(passes=1)),
}


@pytest.mark.parametrize("kw,ref_kw", READINGS.values(), ids=READINGS.keys())
def test_forward_against_the_reference(kw, ref_kw):
    m = _model(**kw)
    p = _params(m)
    toks = _tokens(40)
    got = np.asarray(m.apply(p, jnp.asarray(toks)[None],
                             jnp.arange(40)[None])[0])
    np.testing.assert_allclose(got, _reference(p, toks, **ref_kw), atol=ATOL)


def test_another_reading_moves_the_logits(looped):
    m, p = looped
    toks = _tokens(40)
    want = _reference(p, toks)
    for kw in (dict(norm_order="pre"), dict(pass_norm=False),
               dict(passes=U - 1)):
        assert np.abs(_reference(p, toks, **kw) - want).max() > 0.1, kw


def test_prefill_decode_and_a_chunk_against_the_reference(looped):
    m, p = looped
    toks = _tokens(48)
    want = _reference(p, toks)
    cache = m.init_cache(1, 64)
    assert cache["k"].shape == (U * L, 1, 4, 64, 12)
    logits, cache = m.prefill(p, jnp.asarray(toks[None, :20]), cache)
    np.testing.assert_allclose(logits[0], want[:20], atol=ATOL)
    chunk_cache = cache
    for t in range(20, 32):
        step, cache = m.decode_step(p, jnp.asarray(toks[t:t + 1]), t, cache)
        np.testing.assert_allclose(step[0], want[t], atol=ATOL)
    chunk, _ = m.decode_chunk(p, jnp.asarray(toks[None, 20:48]), 20,
                              chunk_cache)
    np.testing.assert_allclose(chunk[0], want[20:48], atol=ATOL)


def test_generate_is_the_references_greedy_rollout(looped):
    m, p = looped
    prompt = _tokens(9)
    out = np.asarray(m.generate(p, jnp.asarray(prompt)[None], 8))[0]
    seq = list(prompt)
    for _ in range(8):
        seq.append(int(_reference(p, np.asarray(seq, np.int32))[-1].argmax()))
    assert out.tolist() == seq


def test_one_pass_is_the_stack_walked_once():
    """``passes=1`` is the program of a model built without the argument,
    to the lowered text, and its logits to the bit; a looped stack adds
    one loop around the same walk."""
    once, plain = TransformerLM(**BASE, passes=1), TransformerLM(**BASE)
    p = _params(plain)
    tok, pos = jnp.asarray(_tokens(2)), jnp.asarray([5, 7])
    cache = plain.init_cache(2, 64)
    assert jax.tree.map(jnp.shape, once.init_cache(2, 64)) == \
        jax.tree.map(jnp.shape, cache)

    def lowered(m):
        return jax.jit(lambda p, t, q, c: m.decode_step(p, t, q, c)).lower(
            p, tok, pos, cache).as_text()

    assert lowered(once) == lowered(plain)
    a, _ = once.decode_step(p, tok, pos, cache)
    b, _ = plain.decode_step(p, tok, pos, cache)
    assert np.array_equal(np.asarray(a), np.asarray(b))
    toks, at = jnp.asarray(_tokens(16))[None], jnp.arange(16)[None]
    assert np.array_equal(np.asarray(once.apply(p, toks, at)),
                          np.asarray(plain.apply(p, toks, at)))
    looped = TransformerLM(**BASE, passes=3)
    n = jax.jit(lambda p, t, q, c: looped.decode_step(p, t, q, c)).lower(
        p, tok, pos, looped.init_cache(2, 64)).as_text().count(
        "stablehlo.while")
    assert n == lowered(plain).count("stablehlo.while") + 1


def test_pass_u_layer_l_is_cache_layer_u_times_L_plus_l(looped):
    """Pass 0 reads the embedding as a stack walked once does, so its
    cache layers are that model's, at 0..L-1; the passes' layers are
    distinct, and exchanging two passes' layers changes the logits."""
    m, p = looped
    toks = jnp.asarray(_tokens(20))[None]
    _, cache = m.prefill(p, toks, m.init_cache(1, 64))
    once = _model(passes=1)
    _, first = once.prefill(p, toks, once.init_cache(1, 64))
    np.testing.assert_allclose(cache["k"][:L], first["k"], atol=1e-5)
    np.testing.assert_allclose(cache["v"][:L], first["v"], atol=1e-5)
    for u in range(1, U):
        assert np.abs(np.asarray(cache["k"][u * L:(u + 1) * L, :, :, :20]
                                 - cache["k"][:L, :, :, :20])).max() > 1e-2
    tok = jnp.asarray(_tokens(1, seed=9))
    want, _ = m.decode_step(p, tok, 20, cache)
    order = np.r_[L:2 * L, 0:L, 2 * L:U * L]          # passes 0 and 1
    swapped = {k: v[order] for k, v in cache.items()}
    got, _ = m.decode_step(p, tok, 20, swapped)
    assert np.abs(np.asarray(got - want)).max() > 1e-2
    back = {k: v[order] for k, v in swapped.items()}
    again, _ = m.decode_step(p, tok, 20, back)
    np.testing.assert_array_equal(np.asarray(again), np.asarray(want))


class Recorder:
    """Stands in for ``TraceAnnotation``: each span's name and arguments."""

    log = []

    def __init__(self, name, **kwargs):
        self.row = (name, dict(kwargs))
        Recorder.log.append(self.row)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set_metadata(self, **kwargs):
        self.row[1].update(kwargs)


def test_the_engine_serves_it_and_counts_every_cache_layer(looped,
                                                            monkeypatch):
    """Through ``SlotKVCache`` and ``ServingEngine``: greedy streams equal
    the reference's rollout; the decode kernel's walks, the spans and the
    ``work`` counters count all ``U * L`` cache layers."""
    m, p = looped
    Recorder.log = []
    monkeypatch.setattr(engine_mod, "_span", Recorder)
    eng = ServingEngine(m, p, n_slots=2, max_len=64)
    assert eng._decode_walks == [(64, None, False, 64, U * L)]
    prompts = [_tokens(7, seed=21), _tokens(12, seed=22)]
    rids = [eng.submit(q, 6) for q in prompts]
    eng.drain(max_steps=1000)
    for q, rid in zip(prompts, rids):
        seq = list(q)
        for _ in range(6):
            seq.append(int(_reference(p, np.asarray(seq, np.int32))[-1]
                           .argmax()))
        assert eng.result(rid).tokens == seq[len(q):]
    decodes = [a for n, a in Recorder.log if n == "elephas.engine.decode"]
    prefills = [a for n, a in Recorder.log if n == "elephas.engine.prefill"]
    assert decodes and len(prefills) == 2
    for a in decodes + prefills:
        assert (a["passes"], a["cache_layers"]) == (U, U * L)
    # one block of 64 rows a live row and cache layer, every step, copied
    # up to its live rows in granules of 16: every row lies under 32, so
    # one or two granules a row, the same in every cache layer
    for a in decodes:
        assert a["kv_blocks_live"] == a["kv_blocks_walked"] == \
            a["n_active"] * U * L
        assert a["kv_positions_copied"] % (16 * U * L) == 0
        assert 16 <= a["kv_positions_copied"] / (a["n_active"] * U * L) <= 32
    work = eng.snapshot()["work"]
    assert work["decode_cache_layer_positions"] == \
        work["decode_kv_positions"] * U * L > 0
    assert work["decode_kv_blocks_live"] == work["decode_kv_blocks_walked"]
    assert work["decode_kv_positions_copied"] == sum(
        a["kv_positions_copied"] for a in decodes)


def test_a_stack_walked_once_says_nothing_of_passes(monkeypatch):
    Recorder.log = []
    monkeypatch.setattr(engine_mod, "_span", Recorder)
    m = TransformerLM(**BASE)
    eng = ServingEngine(m, _params(m), n_slots=2, max_len=64)
    eng.submit(_tokens(5), 2)
    eng.drain(max_steps=100)
    assert "decode_cache_layer_positions" not in eng.snapshot()["work"]
    for name, args in Recorder.log:
        assert "passes" not in args and "cache_layers" not in args, name


def _mesh():
    from elephas_tpu.models.transformer import build_mesh_sp

    return build_mesh_sp(data=1, seq=1, devices=jax.devices()[:1])


def _refuse_import(m, p):
    from elephas_tpu.models import hf_import

    hf_import._convert(types.SimpleNamespace(model_type="ouro",
                                             total_ut_steps=4), {}, "float32")


def _refuse_tensor(m, p):
    from elephas_tpu.models import tensor_lm

    tensor_lm._validate_tp(m, tensor_lm.build_mesh_tp(
        data=1, model=1, devices=jax.devices()[:1]))


def _refuse_paged_cache(m, p):
    from elephas_tpu.serving.memory import PagedKVCache

    PagedKVCache(m, p, 2, max_len=64)


REFUSALS = {
    "paged_engine": (lambda m, p: ServingEngine(m, p, n_slots=2, max_len=64,
                                                paged=True),
                     "dense-slot engine only"),
    "paged_cache": (_refuse_paged_cache, "no looped page pool"),
    "paged_forward": (lambda m, p: m.decode_step_paged(
        p, jnp.zeros(2, jnp.int32), 0, {}, jnp.zeros((2, 4), jnp.int32), 16),
        "no looped page pool"),
    "mesh_engine": (lambda m, p: ServingEngine(m, p, n_slots=2, max_len=64,
                                               mesh=object()),
                    "dense-slot engine only"),
    "sharded": (lambda m, p: __import__(
        "elephas_tpu.models.sharded_generate", fromlist=["x"]
    )._check_mesh_and_specs(m, _mesh()), "looped stack"),
    "tensor": (_refuse_tensor, "looped stack"),
    "pipeline": (lambda m, p: __import__(
        "elephas_tpu.models.pipeline_lm", fromlist=["x"]
    ).build_lm_pp_train_step(m, _mesh(), None, n_micro=2), "looped stack"),
    "pipeline_tensor": (lambda m, p: __import__(
        "elephas_tpu.models.pipeline_lm", fromlist=["x"]
    ).build_lm_pp_tp_train_step(m, _mesh(), None, n_micro=2),
        "looped stack"),
    "fsdp": (lambda m, p: __import__(
        "elephas_tpu.models.fsdp_lm", fromlist=["x"]
    ).build_lm_fsdp_train_step(m, _mesh(), None), "looped stack"),
    "speculate_k": (lambda m, p: ServingEngine(m, p, n_slots=2, max_len=64,
                                               speculate_k=2),
                    "not a looped one"),
    "speculative": (lambda m, p: m.generate_speculative(
        p, jnp.zeros((1, 4), jnp.int32), 4, m, p), "looped stack"),
    "import": (_refuse_import, "total_ut_steps=4 times a token"),
}


@pytest.mark.parametrize("what", REFUSALS)
def test_what_cannot_run_it_refuses_in_a_sentence(looped, what):
    m, p = looped
    call, sentence = REFUSALS[what]
    with pytest.raises(NotImplementedError, match=sentence):
        call(m, p)


def test_constructor_refuses_what_it_cannot_build():
    for bad in (0, 1.5, -2):
        with pytest.raises(ValueError, match="passes"):
            _model(passes=bad)
    with pytest.raises(ValueError, match="norm_order"):
        _model(norm_order="middle")
    with pytest.raises(ValueError, match="rmsnorm"):
        _model(norm="layernorm")
    assert {"ln1_out_s", "ln2_out_s"} <= set(_model().param_shapes())
