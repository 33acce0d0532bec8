"""A head size of its own, q/k norms, rotary on the window layers only,
leading dense layers outside the layer scan, and TWO kinds of cache side
by side (a ring of the window's length for the window layers, the horizon
for the full ones), through ``decode_step``, ``prefill`` and the serving
engine. CPU, seeded weights, tiny widths; the dense and Mixtral-shaped
models' defaults stay what they were."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from elephas_tpu.models.transformer import (MOE_COUNTS, MoETransformerLM,
                                            TransformerLM)
from elephas_tpu.serving import ServingEngine
from elephas_tpu.serving.cache import SlotKVCache

WINDOWS = [6, 6, 6, None, 6]          # L L L G L: layer 0 dense, 1-4 sparse
BASE = dict(vocab=97, d_model=32, n_heads=4, n_kv_heads=2, n_layers=5,
            d_ff=16, max_len=512, pos_encoding="rotary", norm="rmsnorm",
            activation="swiglu", ffn_bias=False)
NEW = dict(head_dim=12, qk_norm=True, rope_layers="windowed",
           window_cache="ring", attn_window=WINDOWS)


def _moe(**kw):
    return MoETransformerLM(
        n_experts=8, k=2, dense_layers=1, d_ff_dense=40, scoring="sigmoid",
        select_bias=True, routed_scale=2.5, n_shared=1, held=(2, 4),
        **{**BASE, **NEW, **kw})


def _params(model, seed=1):
    rng = np.random.default_rng(seed)
    out = {}
    for k, v in model.init(seed).items():
        if k.endswith("_s"):           # norm scales that are not all ones
            v = 1.0 + 0.3 * rng.standard_normal(v.shape)
        out[k] = jnp.asarray(v, jnp.float32)
    return out


def _tokens(n, seed=0):
    return np.random.default_rng(seed).integers(0, 97, n).astype(np.int32)


def _forward(model, params, toks):
    pos = jnp.arange(len(toks))[None]
    return np.asarray(model.apply(params, jnp.asarray(toks)[None], pos)[0])


def test_shapes_follow_head_dim_and_the_leading_dense_layer():
    m = _moe()
    s = m.param_shapes()
    assert s["wq"].shape == (4, 32, 48) and s["wo"].shape == (4, 48, 32)
    assert s["wk"].shape == (4, 32, 24)            # 2 KV heads of 12
    assert s["qn_s"].shape == (4, 12) == s["kn_s"].shape
    assert s["dense_w1"].shape == (1, 32, 40)      # its own FFN width
    assert s["dense_wq"].shape == (1, 32, 48)
    assert s["w1"].shape == (4, 4, 32, 16)         # 4 sparse layers x 4 held
    assert s["wg"].shape == (4, 32, 8) and s["wg_b"].shape == (4, 8)
    assert not any(k.startswith("mtp_") for k in s)
    assert m._window_period() == 4 and m._two_kind and not m._ring_cache
    cache = m.init_cache(3, length=100)
    assert cache["k"].shape == (1, 3, 2, 104, 12) == cache["v"].shape
    assert cache["kw"].shape == (4, 3, 2, 128, 12) == cache["vw"].shape
    assert cache["moe_counts"].shape == (2, len(MOE_COUNTS))
    lead, scan = m._cache_slots()
    assert lead == [(("kw", "vw"), 0)]
    assert scan == [(("kw", "vw"), 1, 3), (("kw", "vw"), 2, 3),
                    (("k", "v"), 0, 1), (("kw", "vw"), 3, 3)]


def test_defaults_are_the_models_of_before():
    d = TransformerLM(**BASE)
    assert (d.head_dim, d.d_attn, d.qk_norm, d.n_lead) == (8, 32, False, 0)
    assert not d._two_kind and d._rope_on(None) and d._stacked_keys() == ()
    assert "qn_s" not in d.param_shapes()
    assert set(d.init_cache(2, 16)) == {"k", "v"}
    x = MoETransformerLM(n_experts=4, k=2, **BASE)
    assert not x.moe.dropless and x._stacked_keys() == ()
    assert set(x.init_cache(2, 16)) == {"k", "v"}
    assert x._block_keys()[-4:] == ("wg", "w1", "w2", "w3")
    # a mixed model keeps ONE horizon stack unless it asks for the ring
    mixed = TransformerLM(**{**BASE, "n_layers": 4},
                          attn_window=[None, 6, None, 6])
    assert not mixed._two_kind and set(mixed.init_cache(2, 16)) == {"k", "v"}


@pytest.mark.parametrize("what", ["qk_norm", "rope_layers", "head_dim"])
def test_each_new_argument_changes_the_forward(what):
    """...and each is what it says: the q/k norm scales matter only under
    ``qk_norm``; a full layer's output does not depend on where it sits in
    the sequence without rotary."""
    toks = _tokens(20)
    m = TransformerLM(**{**BASE, "n_layers": 2}, head_dim=12, qk_norm=True,
                      rope_layers="windowed", attn_window=[None, None])
    p = _params(m)
    base = _forward(m, p, toks)
    if what == "qk_norm":
        p2 = {**p, "qn_s": p["qn_s"] * 2.0}
        assert np.abs(_forward(m, p2, toks) - base).max() > 1e-3
        off = TransformerLM(**{**BASE, "n_layers": 2}, head_dim=12,
                            rope_layers="windowed",
                            attn_window=[None, None])
        assert "qn_s" not in off.param_shapes()
    elif what == "rope_layers":
        # one full layer that does not rotate carries no position: the
        # last position's logits do not change when the earlier tokens are
        # shuffled; with rotary on every layer they do
        one = dict(**{**BASE, "n_layers": 1}, head_dim=12, qk_norm=True,
                   attn_window=[None])
        p1 = {k: (v[:1] if v.ndim and v.shape[0] == 2 else v)
              for k, v in p.items()}
        shuffled = np.concatenate([toks[:-1][::-1], toks[-1:]])
        flat = TransformerLM(rope_layers="windowed", **one)
        np.testing.assert_allclose(_forward(flat, p1, toks)[-1],
                                   _forward(flat, p1, shuffled)[-1],
                                   atol=1e-5)
        rot = TransformerLM(**one)
        assert np.abs(_forward(rot, p1, toks)[-1]
                      - _forward(rot, p1, shuffled)[-1]).max() > 1e-3
    else:
        assert m.param_shapes()["wq"].shape == (2, 32, 48)
        assert base.shape == (20, 97) and np.isfinite(base).all()


@pytest.mark.parametrize("t0", [3, 70, 200])
def test_prefill_and_decode_through_both_caches(t0):
    """Prefill-insert (bucket-padded, so the ring must take the REAL
    tokens only) then decode steps, past the window and past a ring wrap
    (ring 128: the 200-token prompt wraps it at once, the 70-token one
    while decoding... its 58 steps pass slot 127), against the
    teacher-forced forward."""
    m = _moe()
    p = _params(m)
    steps = 62 if t0 == 70 else 5
    toks = _tokens(t0 + steps + 1)
    want = _forward(m, p, toks)
    kv = SlotKVCache(m, p, n_slots=3, max_len=320)
    kv.allocate()                               # slot 0 stays a free rider
    slot = kv.allocate()
    last = np.asarray(kv.insert(slot, toks[:t0]))
    np.testing.assert_allclose(last, want[t0 - 1], atol=3e-5)
    dec = jax.jit(lambda c, t, ps: m.decode_step(p, t, ps, c))
    for j in range(t0, t0 + steps):
        tok = np.zeros(3, np.int32)
        pos = np.zeros(3, np.int32)
        tok[slot], pos[slot] = toks[j], j
        logits, kv.cache = dec(kv.cache, jnp.asarray(tok), jnp.asarray(pos))
        np.testing.assert_allclose(np.asarray(logits)[slot], want[j],
                                   atol=3e-5)
    counts = np.asarray(kv.cache["moe_counts"])
    assert counts[0, 4] == 4 * steps and counts[1, 4] == 4   # layer calls
    assert 0 < counts[0, 0] <= counts[0, 1]                   # pairs <= rows


def test_chunked_prefill_through_the_ring_equals_the_whole_prompt():
    """Chunks continue where the last stopped: a chunk reads the ring in
    position order, never in place."""
    m = _moe()
    p = _params(m)
    toks = _tokens(301, seed=3)
    want = _forward(m, p, toks)
    kv = SlotKVCache(m, p, n_slots=2, max_len=400)
    slot = kv.allocate()
    pos0 = 0
    for n in (64, 64, 128, 44):                 # the last one is padded
        last = np.asarray(kv.insert(slot, toks[pos0:pos0 + n], pos0=pos0))
        pos0 += n
        np.testing.assert_allclose(last, want[pos0 - 1], atol=3e-5)
    assert pos0 == 300
    logits, _ = m.decode_step(
        p, jnp.zeros(2, jnp.int32).at[slot].set(int(toks[300])),
        jnp.zeros(2, jnp.int32).at[slot].set(300), kv.cache)
    np.testing.assert_allclose(np.asarray(logits)[slot], want[300],
                               atol=3e-5)


def test_generate_through_prefill_equals_the_engine():
    """``generate`` (batched ``prefill`` writing both stacks, then the
    decode scan) and the serving engine (prefill-insert, batched decode
    over slots) emit the same greedy tokens."""
    m = _moe()
    p = _params(m)
    prompts = [_tokens(n, seed=n) for n in (5, 40, 150)]
    eng = ServingEngine(m, p, n_slots=2, max_len=256)
    ids = [eng.submit(pr, 12) for pr in prompts]
    done = eng.drain(max_steps=2000)
    for rid, pr in zip(ids, prompts):
        want = np.asarray(m.generate(p, pr[None], 12))[0, len(pr):]
        assert list(want) == done[rid].tokens
    work = eng.snapshot()["work"]
    assert work["moe_pairs_held"] <= work["moe_rows_computed"]
    assert work["moe_rows_max_expert"] >= 1
    assert work["moe_decode_layer_calls"] % 4 == 0
    assert 0 < work["decode_kv_positions_windowed"] < \
        work["decode_kv_positions"]
    # two kinds of cache, one block each at this size: a visit a row and
    # layer, all five layers
    assert work["decode_kv_blocks_live"] == work["decode_kv_blocks_walked"]
    assert work["decode_kv_blocks_live"] % 5 == 0 < work["decode_kv_blocks_live"]


def test_decode_span_says_what_the_window_layers_attend():
    m = _moe()
    eng = ServingEngine(m, _params(m), n_slots=2, max_len=64)
    eng.submit(_tokens(9), 4)
    eng.submit(_tokens(3), 4)
    while eng.step() != "decode":
        pass
    # after one decode step the rows sit at next_pos 10 and 4
    args = eng._kv_span_args(1)
    # both rows in the one block of the horizon stack's layer (64 rows)
    # and of each of the four rings (128 rows); the horizon layer copies
    # each row's block up to its live rows (11 and 5: one granule of 16),
    # a ring every block whole
    assert args == {"kv_positions": 11 + 5, "kv_positions_windowed": 6 + 5,
                    "kv_blocks_live": 2 * 5, "kv_blocks_walked": 2 * 5,
                    "kv_positions_copied": 2 * 16 + 4 * 2 * 128}
    # the decode step that ran had its rows at 9 and 3: the same copies
    assert eng.snapshot()["work"]["decode_kv_positions_copied"] == \
        2 * 16 + 4 * 2 * 128
    assert sorted(m.decode_walks(eng.kv.cache)) == [
        (64, None, False, 64, 1), (128, 6, True, 128, 4)]
    assert eng._kv_span_args(2)["kv_positions_windowed"] == 6 + 6 + 5 + 6
    assert eng._kv_span_args(2)["kv_blocks_walked"] == 2 * 2 * 5
    dense = ServingEngine(TransformerLM(**BASE), _params(
        TransformerLM(**BASE)), n_slots=2, max_len=64)
    assert set(dense._kv_span_args(1)) == {
        "kv_positions", "kv_blocks_live", "kv_blocks_walked",
        "kv_positions_copied"}
    assert set(dense.snapshot()["work"]) == {
        "programs_launched", "decode_kv_positions", "decode_kv_blocks_live",
        "decode_kv_blocks_walked", "decode_kv_positions_copied",
        "prefill_tokens", "prefill_padded_tokens"}


def test_a_decode_step_fetches_nothing_but_its_tokens(monkeypatch):
    """The expert layer's counters ride the donated cache; only
    ``snapshot()`` brings them to the host. (The CPU has no transfer to
    guard, so the engine's fetches are counted where it makes them.)"""
    from elephas_tpu.serving import engine as engine_module

    m = _moe()
    eng = ServingEngine(m, _params(m), n_slots=2, max_len=64)
    eng.submit(_tokens(6), 8)
    while eng.step() != "decode":
        pass
    fetched = []
    real = np.asarray

    class Counting:
        def __getattr__(self, name):
            return getattr(np, name)

        @staticmethod
        def asarray(x, *a, **kw):
            if isinstance(x, jax.Array):
                fetched.append(tuple(x.shape))
            return real(x, *a, **kw)

    monkeypatch.setattr(engine_module, "np", Counting())
    assert eng.step() == "decode"
    assert fetched == [(2,)]                    # the step's tokens, [S]
    assert set(eng.kv.cache) == {"k", "v", "kw", "vw", "moe_counts"}
    eng.snapshot()
    assert fetched == [(2,), (2, len(MOE_COUNTS))]


def test_what_cannot_run_yet_is_refused_by_mechanism():
    m = _moe()
    p = _params(m)
    with pytest.raises(NotImplementedError, match="paged pool"):
        ServingEngine(m, p, n_slots=2, max_len=64, paged=True)
    with pytest.raises(NotImplementedError, match="no paged pool"):
        m._refuse_paged("decode_step_paged")
    with pytest.raises(ValueError, match="dense-FFN target"):
        ServingEngine(m, p, n_slots=2, max_len=64, speculate_k=2)
    with pytest.raises(ValueError, match="mtp_layers=0"):
        m.mtp_logits(p, jnp.zeros((1, 4, 32)), jnp.zeros((1, 4), jnp.int32),
                     jnp.arange(4)[None])
