"""A latent-attention model behind the serving engine: the slot cache's
third kind (one stack of latent rows), insert through the published form,
decode through the absorbed form, release and reuse of a slot whose rows
are stale, the decode span's and ``snapshot()``'s counts, and one fetch a
decode step. CPU, seeded weights, tiny widths, float32."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from elephas_tpu.models.transformer import (MOE_COUNTS, MoETransformerLM,
                                            TransformerLM)
from elephas_tpu.serving import ServingEngine
from elephas_tpu.serving.cache import SlotKVCache

YARN = {"beta_fast": 32, "beta_slow": 1, "factor": 32, "mscale": 1,
        "mscale_all_dim": 1, "original_max_position_embeddings": 64,
        "type": "yarn"}
BASE = dict(vocab=97, d_model=48, n_heads=4, n_layers=3, d_ff=16,
            max_len=600, pos_encoding="rotary", activation="swiglu",
            norm="rmsnorm", ffn_bias=False, norm_eps=1e-6, q_lora_rank=24,
            kv_lora_rank=128, qk_nope_head_dim=16, qk_rope_head_dim=8,
            v_head_dim=12, rope_scaling=YARN)


def _moe():
    return MoETransformerLM(
        n_experts=12, k=4, dense_layers=1, d_ff_dense=80, scoring="sigmoid",
        routed_scale=2.5, n_shared=1, held=(3, 3), aux_weight=0.0, **BASE)


def _params(model, seed=0):
    rng = np.random.default_rng(seed + 1)
    return {k: jnp.asarray(
        v + 0.2 * rng.standard_normal(v.shape).astype(np.float32)
        if k.endswith(("_s", "_norm")) else v)
        for k, v in model.init(seed).items()}


def _tokens(n, seed=0):
    return np.random.default_rng(seed).integers(0, 97, n).astype(np.int32)


def _forward(model, params, toks):
    pos = jnp.arange(len(toks))[None]
    return np.asarray(model.apply(params, jnp.asarray(toks)[None], pos)[0])


@pytest.fixture(scope="module")
def served():
    m = _moe()
    return m, _params(m)


def test_insert_decode_release_and_reuse_over_stale_rows(served):
    """A slot is filled by a long request, released, and taken by a SHORT
    one: the long one's latent rows lie stale beyond the new prompt (and
    under its bucket padding) and no query of the new occupant reads one
    before its own decode step has written that position."""
    m, p = served
    kv = SlotKVCache(m, p, n_slots=3, max_len=400)
    assert set(kv.cache) == {"k", "moe_counts"}
    assert kv.cache["k"].shape == (3, 3, 1, 512, 256) and kv.capacity == 512
    dec = jax.jit(lambda c, t, ps: m.decode_step(p, t, ps, c),
                  donate_argnums=(0,))

    def serve(slot, toks, t0, steps):
        want = _forward(m, p, toks)
        last = np.asarray(kv.insert(slot, toks[:t0]))
        np.testing.assert_allclose(last, want[t0 - 1], atol=3e-5)
        for j in range(steps):
            tok = np.zeros(3, np.int32)
            pos = np.zeros(3, np.int32)
            tok[slot], pos[slot] = toks[t0 + j], t0 + j
            logits, kv.cache = dec(kv.cache, jnp.asarray(tok),
                                   jnp.asarray(pos))
            kv.advance(slot)
            np.testing.assert_allclose(np.asarray(logits)[slot],
                                       want[t0 + j], atol=3e-5)

    slot = kv.allocate()
    serve(slot, _tokens(300, seed=1), 290, 6)
    stale = np.asarray(kv.cache["k"][:, slot, 0, 100:290])
    assert np.abs(stale).max() > 0
    kv.release(slot)
    assert kv.allocate() == slot and kv.pos[slot] == 0
    serve(slot, _tokens(40, seed=2), 21, 12)     # bucket 32 > 21: padded
    # the rows past the new occupant's head are the old request's still
    np.testing.assert_array_equal(
        np.asarray(kv.cache["k"][:, slot, 0, 100:290]), stale)
    # the other slots: an idle row of the batched step writes its own
    # position 0 (dead: an occupant's insert starts there) and no other
    others = [s for s in range(3) if s != slot]
    assert not np.asarray(kv.cache["k"][:, others, :, 1:]).any()
    # both executors counted their work on the device
    counts = np.asarray(kv.cache["moe_counts"])
    assert counts.shape == (2, len(MOE_COUNTS)) and (counts[:, 4] > 0).all()


def test_engine_streams_equal_generate(served):
    m, p = served
    eng = ServingEngine(m, p, n_slots=2, max_len=128)
    prompts = [_tokens(9, seed=3), _tokens(70, seed=4), _tokens(5, seed=5)]
    ids = [eng.submit(pr, 10) for pr in prompts]
    eng.drain(max_steps=10_000)
    for rid, pr in zip(ids, prompts):
        want = np.asarray(m.generate(p, jnp.asarray(pr)[None], 10))[0, len(pr):]
        assert eng.result(rid).tokens == [int(t) for t in want]


@pytest.mark.parametrize("max_len,walk,visits", [
    # 768 rows are whole blocks of 256, not of 512 or 1,024: the row at
    # 301 attends two blocks, the row at 4 one
    (600, (768, None, False, 256, 3), 2 + 1),
    # a cache of 512 rows is ONE block of the latent kernel's
    (512, (512, None, False, 512, 3), 1 + 1)])
def test_decode_span_and_snapshot_count_the_latent_rows(served, max_len, walk,
                                                        visits):
    m, p = served
    eng = ServingEngine(m, p, n_slots=2, max_len=max_len)
    eng.submit(_tokens(300), 4)
    eng.submit(_tokens(3), 4)
    while eng.step() != "decode":
        pass
    # after one decode step the rows sit at next_pos 301 and 4; the engine
    # counts the kernel's visits in the kernel's own blocks, a layer
    assert m.decode_walks(eng.kv.cache) == [walk]
    # (the latent kernel copies whole blocks)
    assert eng._kv_span_args(1) == {
        "kv_positions": 302 + 5, "kv_blocks_live": 3 * visits,
        "kv_blocks_walked": 3 * visits,
        "kv_positions_copied": 3 * visits * walk[3]}
    work = eng.snapshot()["work"]
    # one decode step so far: rows at 300 and 3 attended 301 + 4 positions
    assert work["decode_kv_positions"] == 301 + 4
    assert work["decode_latent_positions"] == 3 * (301 + 4)
    assert work["decode_kv_blocks_live"] == work["decode_kv_blocks_walked"] \
        == 3 * visits
    eng.drain(max_steps=1000)
    work = eng.snapshot()["work"]
    assert work["decode_latent_positions"] == 3 * work["decode_kv_positions"]
    assert work["moe_decode_layer_calls"] == 2 * eng.metrics.decode_steps
    # a model without latent rows has no such counter
    dense = TransformerLM(**{k: v for k, v in BASE.items() if k not in (
        "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim",
        "qk_rope_head_dim", "v_head_dim", "rope_scaling")})
    other = ServingEngine(dense, {k: jnp.asarray(v) for k, v in
                                  dense.init(0).items()}, n_slots=2,
                          max_len=64)
    assert "decode_latent_positions" not in other.snapshot()["work"]


def test_a_decode_step_fetches_nothing_but_its_tokens(served, monkeypatch):
    from elephas_tpu.serving import engine as engine_module

    m, p = served
    eng = ServingEngine(m, p, n_slots=2, max_len=64)
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
    assert set(eng.kv.cache) == {"k", "moe_counts"}
    eng.snapshot()
    assert fetched == [(2,), (2, len(MOE_COUNTS))]
