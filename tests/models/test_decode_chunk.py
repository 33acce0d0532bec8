"""``decode_chunk``, the one cached chunk forward: for every kind of model
and cache it must give what the teacher-forced forward gives at the chunk's
positions and what ``S`` single ``decode_step``s give, and leave the cache
those steps leave. CPU, seeded weights, tiny widths."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from elephas_tpu.models.transformer import MoETransformerLM, TransformerLM

_BASE = dict(vocab=61, d_model=32, n_heads=4, n_kv_heads=2, n_layers=4,
             d_ff=64, max_len=64, pos_encoding="rotary", norm="rmsnorm",
             activation="swiglu", ffn_bias=False)


def _dense(**kw):
    return TransformerLM(**{**_BASE, **kw})


def _mixtral():
    # capacity E/k: no token is dropped, however the chunk is grouped
    return MoETransformerLM(n_experts=4, k=2, capacity_factor=2.0, **_BASE)


# name: (model, prefix length of each row, chunk length S, init_cache's
# length and chunk margin, queries a block or None for the class's own)
CASES = {
    "dense_one_stack": (
        lambda: _dense(pos_encoding="learned", norm="layernorm",
                       activation="gelu", ffn_bias=True, n_kv_heads=4),
        [5, 5], 6, 16, 1, None),
    "mixtral_moe": (_mixtral, [5, 5], 6, 16, 1, None),
    "period_2_with_a_full_layer": (
        lambda: _dense(attn_window=[None, 6, None, 6]), [9, 9], 7, 24, 1,
        None),
    # no shorter period than the stack: the walk is one unrolled step
    "pattern_one_period_long": (
        lambda: _dense(attn_window=[4, None, 8, None]), [9, 9], 7, 24, 1,
        None),
    # 16 ring rows, 20 tokens, no margin: an in-place write would clobber
    # rows the chunk's own earlier queries attend
    "ring_shorter_than_the_chunk": (
        lambda: _dense(attn_window=8), [11, 11], 20, 40, 1, None),
    "ring_of_mixed_windows": (
        lambda: _dense(attn_window=[4, 8, 4, 8]), [13, 13], 6, 40, 6, None),
    # the window never binds: the ring holds the whole rollout
    "horizon_bounded_ring": (
        lambda: _dense(attn_window=300), [30, 30], 9, 48, 1, None),
    "per_row_pos0_on_a_ring": (
        lambda: _dense(attn_window=8), [3, 11, 20], 6, 40, 6, None),
    # a bucket above _CHUNK_Q_BLOCK, cut to size: two blocks of queries
    "two_query_blocks": (_dense, [5, 2], 8, 16, 1, 4),
    "two_query_blocks_on_a_ring": (
        lambda: _dense(attn_window=[4, 8, 4, 8]), [13, 7], 8, 40, 1, 4),
}


@pytest.mark.parametrize("case", list(CASES))
def test_chunk_equals_teacher_forced_and_single_steps(case):
    make, prefix, S, length, margin, q_block = CASES[case]
    model = make()
    params = {k: jnp.asarray(v) for k, v in model.init(seed=3).items()}
    prefix = np.asarray(prefix)
    B, T = len(prefix), int(prefix.max()) + S
    tokens = np.random.default_rng(5).integers(0, 61, (B, T)).astype(np.int32)
    full = np.asarray(model.apply(
        params, jnp.asarray(tokens),
        jnp.broadcast_to(jnp.arange(T), (B, T)), attn="dense"))
    rows = np.arange(B)
    step = jax.jit(model.decode_step)

    # each row's prefix, a token a step; a row whose prefix has ended
    # writes its last position again, which changes nothing
    cache = model.init_cache(B, length, chunk=margin)
    if case == "ring_shorter_than_the_chunk":
        assert cache["k"].shape[3] < S
    for t in range(int(prefix.max())):
        pos = np.minimum(t, prefix - 1)
        _, cache = step(params, jnp.asarray(tokens[rows, pos]),
                        jnp.asarray(pos, jnp.int32), cache)

    chunk = jnp.asarray(np.stack([tokens[b, p:p + S]
                                  for b, p in enumerate(prefix)]))
    same = len(set(prefix.tolist())) == 1
    pos0 = int(prefix[0]) if same else jnp.asarray(prefix, jnp.int32)
    if q_block is not None:
        whole, _ = jax.jit(model.decode_chunk)(params, chunk, pos0, cache)
        model._CHUNK_Q_BLOCK = q_block          # this instance only
    got, after = jax.jit(model.decode_chunk)(params, chunk, pos0, cache)
    got = np.asarray(got)
    if q_block is not None and model._ring_cache:
        # a block's band of the ring is as wide as the block: the same
        # keys, summed in another order
        np.testing.assert_allclose(got, np.asarray(whole), atol=5e-6)
    elif q_block is not None:       # a horizon's blocks change no number
        np.testing.assert_array_equal(got, np.asarray(whole))

    want = np.stack([full[b, p:p + S] for b, p in enumerate(prefix)])
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)

    stepped = []
    for s in range(S):
        lg, cache = step(params, chunk[:, s],
                         jnp.asarray(prefix + s, jnp.int32), cache)
        stepped.append(np.asarray(lg))
    np.testing.assert_allclose(got, np.stack(stepped, axis=1),
                               atol=2e-5, rtol=2e-5)
    assert set(after) == set(cache)
    for name in after:
        np.testing.assert_allclose(np.asarray(after[name]),
                                   np.asarray(cache[name]),
                                   atol=2e-5, rtol=2e-5, err_msg=name)
