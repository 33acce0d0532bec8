"""A model with linear-attention layers behind the serving engine: the slot
cache's fourth kind (a recurrent state and a convolution tail a slot beside
the K/V of the full layers) and the three rules that take the place of the
staleness-repair invariant (``serving/cache.py``), each with a test that
fails when the rule is removed: zero state at an insert at position 0;
nothing past ``n_valid`` touches state or tail; a row that is not live
keeps both, in the single and the fused decode program. Chunked prefill,
preemption and resume, weight rollover, the counters, the refusals. CPU,
seeded weights, tiny widths, float32 (logits agree to ``ATOL``:
``tests/models/test_linear_attention.py`` says why 5e-4)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from elephas_tpu.models.transformer import TransformerLM
from elephas_tpu.serving import ServingEngine
from elephas_tpu.serving.cache import SlotKVCache, _insert_kernel
from elephas_tpu.serving.engine import (ModelDrafter, _decode_kernel,
                                        _fused_decode_kernel)

ATOL = 5e-4
PERIOD = ["linear_attention"] * 3 + ["full_attention"]
KW = dict(vocab=97, d_model=48, n_heads=4, n_layers=4, d_ff=64, max_len=256,
          pos_encoding="rotary", activation="swiglu", norm="rmsnorm",
          ffn_bias=False, norm_eps=1e-6, qk_norm="whole", rope_layers="none",
          norm_order="post", layer_types=PERIOD, linear_heads=4,
          linear_key_head_dim=8, linear_value_head_dim=16,
          linear_allow_neg_eigval=True)


def _tokens(n, seed=0):
    return np.random.default_rng(seed).integers(0, 97, n).astype(np.int32)


@pytest.fixture(scope="module")
def served():
    m = TransformerLM(**KW)
    rng = np.random.default_rng(1)
    p = {}
    for k, v in m.init(0).items():
        if k == "A_log":      # slow decays: what a slot held long matters
            v = rng.uniform(-6.0, -1.0, v.shape).astype(np.float32)
        p[k] = jnp.asarray(v)
    return m, p


def _forward(m, p, toks):
    return np.asarray(m.apply(p, jnp.asarray(toks)[None],
                              jnp.arange(len(toks))[None])[0])


def _serve(m, p, kv, slot, toks, t0, steps):
    """Insert ``toks[:t0]`` into ``slot``, decode ``steps`` more with every
    row live, compare each logit row with the uncached forward."""
    want = _forward(m, p, toks)
    np.testing.assert_allclose(kv.insert(slot, toks[:t0]), want[t0 - 1],
                               atol=ATOL)
    dec = jax.jit(lambda c, t, ps: m.decode_step(p, t, ps, c),
                  donate_argnums=(0,))
    for j in range(steps):
        tok = np.zeros(kv.n_slots, np.int32)
        pos = np.zeros(kv.n_slots, np.int32)
        tok[slot], pos[slot] = toks[t0 + j], t0 + j
        logits, kv.cache = dec(kv.cache, jnp.asarray(tok), jnp.asarray(pos))
        kv.advance(slot)
        np.testing.assert_allclose(np.asarray(logits)[slot], want[t0 + j],
                                   atol=ATOL)


def test_a_reused_slot_starts_from_a_zero_state(served):
    """Rule 1. A slot is filled by one request, released (no device work)
    and taken by another: the second one's logits are those of a fresh
    cache, though the first one's state and tail still lie in the slot."""
    m, p = served
    kv = SlotKVCache(m, p, n_slots=3, max_len=200)
    assert set(kv.cache) == {"k", "v", "s", "conv"} and kv.capacity == 200
    slot = kv.allocate()
    _serve(m, p, kv, slot, _tokens(120, seed=1), 100, 6)
    left = np.asarray(kv.cache["s"][:, slot])
    assert np.abs(left).max() > 1e-2
    kv.release(slot)
    np.testing.assert_array_equal(kv.cache["s"][:, slot], left)
    assert kv.allocate() == slot
    _serve(m, p, kv, slot, _tokens(40, seed=2), 21, 8)   # bucket 32 > 21
    # the rule is what does it: a continuation at position 0 of the SAME
    # tokens from the state the slot held gives other logits
    toks = _tokens(40, seed=2)
    kv.release(slot), kv.allocate()
    stale = {**kv.cache, "s": kv.cache["s"].at[:, slot].set(left)}
    logits, _ = m.prefill_slot(p, jnp.asarray(toks[None, :21]), slot, stale,
                               pos0=1)
    fresh, _ = m.prefill_slot(p, jnp.asarray(toks[None, :21]), slot, stale,
                              pos0=0)
    np.testing.assert_allclose(fresh[0, -1], _forward(m, p, toks)[20],
                               atol=ATOL)
    assert np.abs(np.asarray(logits - fresh)).max() > 100 * ATOL


def test_bucket_padding_touches_neither_state_nor_tail(served):
    """Rule 2. The insert program pads 21 tokens to a bucket of 32 and
    tells the model how many are real: state and tail are those of the 21
    alone. Without ``n_valid`` the padding is folded in."""
    m, p = served
    toks = _tokens(21, seed=3)
    kv = SlotKVCache(m, p, n_slots=2, max_len=128)
    kv.insert(1, toks)
    _, want = m.prefill_slot(p, jnp.asarray(toks[None]), 1,
                             m.init_cache(2, 128))
    np.testing.assert_allclose(kv.cache["s"], want["s"], atol=1e-5)
    np.testing.assert_allclose(kv.cache["conv"], want["conv"], atol=1e-5)
    assert not np.asarray(kv.cache["s"][:, 0]).any()
    padded = np.zeros((1, 32), np.int32)
    padded[0, :21] = toks
    _, unmasked = m.prefill_slot(p, jnp.asarray(padded), 1,
                                 m.init_cache(2, 128))
    assert np.abs(np.asarray(unmasked["s"] - want["s"])).max() > 1e-2
    assert np.abs(np.asarray(unmasked["conv"] - want["conv"])).max() > 1e-3
    # the compiled insert is the one the engine and the benchmark call
    last, cache = _insert_kernel(m, p, m.init_cache(2, 128),
                                 jnp.asarray(padded), 20, 1, 0)
    np.testing.assert_allclose(cache["s"], want["s"], atol=1e-5)


@pytest.mark.parametrize("fused", [False, True], ids=["single", "fused"])
def test_a_row_that_is_not_live_keeps_state_and_tail(served, fused):
    """Rule 3. Through the engine's own decode programs: rows 0 and 2 are
    live, row 1 is a parked partial prefill (at its write head, 37) and
    row 3 a free slot (dummy token at position 0). The live rows' state
    moves; the others' state and tail are bit for bit what they were."""
    m, p = served
    kv = SlotKVCache(m, p, n_slots=4, max_len=128)
    for slot, n in ((0, 30), (1, 37), (2, 9)):
        kv.insert(slot, _tokens(n, seed=slot))
    before = {k: np.asarray(v) for k, v in kv.cache.items()}
    tok = jnp.asarray([5, 0, 7, 0], jnp.int32)
    pos = jnp.asarray([30, 37, 9, 0], jnp.int32)
    live = jnp.asarray([True, False, True, False])
    temps, keys = jnp.zeros(4), jnp.zeros((4, 2), jnp.uint32)
    if fused:
        _, _, pos2, cache = _fused_decode_kernel(
            m, p, kv.cache, tok, pos, temps, keys, live, n_steps=3)
    else:
        _, _, pos2, cache = _decode_kernel(m, p, kv.cache, tok, pos, temps,
                                           keys, live)
    assert list(np.asarray(pos2)) == [33 if fused else 31, 37,
                                      12 if fused else 10, 0]
    for name in ("s", "conv"):
        after = np.asarray(cache[name])
        np.testing.assert_array_equal(after[:, [1, 3]],
                                      before[name][:, [1, 3]])
        assert np.abs(after[:, [0, 2]] - before[name][:, [0, 2]]).max() > 1e-3
    # ``live=None`` is every row (how the benchmark's check calls it)
    _, every = m.decode_step(p, tok, pos, {k: jnp.asarray(v)
                                           for k, v in before.items()})
    assert np.abs(np.asarray(every["s"])[:, 1] - before["s"][:, 1]).max() > 1e-3


def _streams(m, p, prompts, n_new, **engine_kw):
    eng = ServingEngine(m, p, **engine_kw)
    ids = [eng.submit(pr, n_new) for pr in prompts]
    eng.drain(max_steps=20_000)
    return [eng.result(rid).tokens for rid in ids], eng


def _generate(m, p, prompt, n_new):
    out = np.asarray(m.generate(p, jnp.asarray(prompt)[None], n_new))
    return [int(t) for t in out[0, len(prompt):]]


def test_engine_streams_equal_generate_whole_chunked_and_fused(served):
    """Five requests on two slots (slots are reused), whole-prompt prefill,
    chunked prefill (a parked partial rides the interleaved decode steps)
    and fused decode: every stream is ``generate``'s."""
    m, p = served
    prompts = [_tokens(n, seed=10 + n) for n in (9, 70, 5, 33, 64)]
    want = [_generate(m, p, pr, 12) for pr in prompts]
    whole, eng = _streams(m, p, prompts, 12, n_slots=2, max_len=128)
    assert whole == want
    work = eng.snapshot()["work"]
    # 3 linear layers: a state a live row a step; blocks of 64 positions
    # of every padded insert (buckets 16, 128, 8, 64, 64)
    # (a request's first token is its insert's; 11 decode steps each)
    assert work["decode_state_rows"] == 3 * 5 * 11
    assert work["prefill_state_blocks"] == 3 * (1 + 2 + 1 + 1 + 1)
    chunked, eng = _streams(m, p, prompts, 12, n_slots=2, max_len=128,
                            prefill_chunk=16)
    assert chunked == want and eng.metrics.prefill_chunks > 5
    fused, eng = _streams(m, p, prompts, 12, n_slots=2, max_len=128,
                          prefill_chunk=16, fuse_k=4)
    assert fused == want and eng.metrics.fused_blocks > 0


def test_decode_span_carries_state_rows(served):
    m, p = served
    eng = ServingEngine(m, p, n_slots=3, max_len=128)
    eng.submit(_tokens(20), 4)
    eng.submit(_tokens(3), 4)
    while eng.step() != "decode":
        pass
    args = eng._kv_span_args(1)
    assert args["state_rows"] == 2 * 3
    assert args["kv_blocks_live"] == args["kv_blocks_walked"] == 2  # 1 layer
    assert eng._kv_span_args(4)["state_rows"] == 2 * 3 * 4
    assert "state_rows" not in eng._kv_span_args(2, chunk=True)
    dense = TransformerLM(vocab=97, d_model=48, n_heads=4, n_layers=2,
                          d_ff=64, max_len=64)
    other = ServingEngine(dense, {k: jnp.asarray(v) for k, v in
                                  dense.init(0).items()}, n_slots=2)
    assert "decode_state_rows" not in other.snapshot()["work"]
    assert "state_rows" not in other._kv_span_args(1)


def test_preemption_and_resume_reproduce_the_stream(served):
    """A live request is evicted mid-answer, requeued, and re-prefilled
    from prompt + generated into a slot whose state is another request's
    leftovers: its stream is the unpreempted one."""
    m, p = served
    prompts = [_tokens(30, seed=21), _tokens(11, seed=22)]
    want = [_generate(m, p, pr, 16) for pr in prompts]
    eng = ServingEngine(m, p, n_slots=2, max_len=128)
    ids = [eng.submit(pr, 16) for pr in prompts]
    while eng.metrics.decode_steps < 5:
        eng.step()
    victim = eng._slot_req[0]
    assert 0 < len(victim.generated) < 16
    eng._preempt(victim)
    assert eng.kv.preemptions == 1 and victim.preemptions == 1
    eng.drain(max_steps=10_000)
    assert [eng.result(rid).tokens for rid in ids] == want


def test_weight_rollover_keeps_serving(served):
    """``swap_params`` between rounds: tokens after the swap are the new
    weights' continuation of the state the old ones built (no retrace, no
    reset), and a request admitted after it is the new weights' alone."""
    m, p = served
    p2 = {k: (v * 1.05 if k == "lin_o" else v) for k, v in p.items()}
    eng = ServingEngine(m, p, n_slots=2, max_len=128)
    rid = eng.submit(_tokens(12, seed=30), 10)
    while eng.metrics.decode_steps < 3:
        eng.step()
    eng.swap_params(p2, version=1)
    late = eng.submit(_tokens(7, seed=31), 6)
    eng.drain(max_steps=10_000)
    assert eng.result(late).tokens == _generate(m, p2, _tokens(7, seed=31), 6)
    fin = eng.result(rid)
    assert len(fin.tokens) == 10 and set(fin.token_versions) == {0, 1}


REFUSED = {
    "speculate_k": (dict(speculate_k=3), "cannot be rolled"),
    "paged": (dict(paged=True), "no\n?.*pool beside them|pool beside"),
    "mesh": (dict(mesh=object()), "know no recurrent state"),
}


@pytest.mark.parametrize("kw,sentence", REFUSED.values(), ids=REFUSED.keys())
def test_what_cannot_serve_it_refuses_in_a_sentence(served, kw, sentence):
    m, p = served
    with pytest.raises(NotImplementedError, match=sentence):
        ServingEngine(m, p, n_slots=2, max_len=64, **kw)


def test_a_hybrid_draft_model_is_refused(served):
    m, p = served
    with pytest.raises(NotImplementedError, match="recurrent state"):
        ModelDrafter(m, p)
