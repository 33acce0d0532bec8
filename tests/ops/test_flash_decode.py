"""Flash-decode kernel vs the einsum oracle (interpret mode on CPU).

Covers GQA group sizes (G=1 multi-query up to G=H), padding-sensitive head
dims and cache lengths, positions in every T-block (incl. block boundaries),
traced positions under scan (the generate() usage), bf16 caches, the stacked
``[L, B, Hkv, T, Dh]`` cache with a layer index (the decode step's form) and
the row-write kernel that updates that stack in place.
"""

import collections
import functools
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from elephas_tpu.ops import decode_attention_reference, flash_decode

# the module (``elephas_tpu.ops.flash_decode`` names the function)
fd = importlib.import_module("elephas_tpu.ops.flash_decode")


def rand(rng, *shape):
    return jnp.asarray(rng.normal(size=shape), jnp.float32)


def fused(q, k, v, pos):
    return flash_decode(q, k, v, pos, interpret=True)


@pytest.mark.parametrize("hkv,g", [(1, 4), (2, 2), (4, 1), (2, 5)])
def test_gqa_group_shapes(hkv, g):
    rng = np.random.default_rng(0)
    B, T, Dh = 3, 40, 16
    q = rand(rng, B, hkv, g, Dh)
    k = rand(rng, B, hkv, T, Dh)
    v = rand(rng, B, hkv, T, Dh)
    for pos in (0, 17, T - 1):
        got = fused(q, k, v, pos)
        want = decode_attention_reference(q, k, v, pos)
        np.testing.assert_allclose(got, want, atol=1e-5, rtol=1e-5,
                                   err_msg=f"pos={pos}")


def test_multi_block_cache_and_boundaries():
    """Cache longer than one T-block: online softmax must merge blocks, and
    positions at/around block edges must mask exactly."""
    rng = np.random.default_rng(1)
    B, Hkv, G, Dh, T = 2, 2, 3, 8, 700  # > 2 blocks of 256
    q = rand(rng, B, Hkv, G, Dh)
    k = rand(rng, B, Hkv, T, Dh) * 3
    v = rand(rng, B, Hkv, T, Dh)
    for pos in (0, 255, 256, 511, 512, 699):
        got = fused(q, k, v, pos)
        want = decode_attention_reference(q, k, v, pos)
        np.testing.assert_allclose(got, want, atol=1e-4, rtol=1e-4,
                                   err_msg=f"pos={pos}")


def test_traced_position_under_scan():
    """pos advances inside lax.scan in generate(): the kernel must accept a
    traced scalar (scalar prefetch) and stay exact at every step."""
    rng = np.random.default_rng(2)
    B, Hkv, G, Dh, T = 2, 1, 2, 8, 20
    q = rand(rng, B, Hkv, G, Dh)
    k = rand(rng, B, Hkv, T, Dh)
    v = rand(rng, B, Hkv, T, Dh)

    def step(_, pos):
        return None, fused(q, k, v, pos)

    _, outs = jax.lax.scan(step, None, jnp.arange(T))
    for pos in range(T):
        want = decode_attention_reference(q, k, v, pos)
        np.testing.assert_allclose(outs[pos], want, atol=1e-5, rtol=1e-5,
                                   err_msg=f"pos={pos}")


def test_bf16_cache_f32_softmax():
    rng = np.random.default_rng(3)
    B, Hkv, G, Dh, T = 2, 2, 2, 16, 33
    q32 = rand(rng, B, Hkv, G, Dh)
    k32 = rand(rng, B, Hkv, T, Dh)
    v32 = rand(rng, B, Hkv, T, Dh)
    got = fused(q32.astype(jnp.bfloat16), k32.astype(jnp.bfloat16),
                v32.astype(jnp.bfloat16), 20)
    assert got.dtype == jnp.float32
    want = decode_attention_reference(q32, k32, v32, 20)
    np.testing.assert_allclose(got, want, atol=2e-2, rtol=2e-2)


# (q dtype, Hkv, G, T of the cache, Dh, pos of the rows, kwargs); "stacked"
# hands the kernel the decode step's whole stack and a traced layer index
BF16_EXACT = {
    "g8_hkv8_multi_block_per_row": (
        jnp.bfloat16, 8, 8, 3 * 256, 128, [0, 255, 256, 333, 767], {}),
    "g4_hkv8_multi_block_per_row": (
        jnp.bfloat16, 8, 4, 3 * 256, 128, [0, 255, 256, 333, 767], {}),
    "g8_hkv1": (jnp.bfloat16, 1, 8, 300, 64, [0, 17, 299], {}),
    "g4_hkv1_scalar_pos": (jnp.bfloat16, 1, 4, 300, 64, 257, {}),
    "window_on_a_horizon": (
        jnp.bfloat16, 8, 8, 3 * 256, 128, [0, 255, 256, 600, 868],
        {"window": 300}),
    "ring_that_has_wrapped": (
        jnp.bfloat16, 8, 8, 256, 128, [0, 127, 128, 255, 9 * 256 + 7],
        {"window": 128, "ring": True}),
    "ring_of_two_blocks_wrapped": (
        jnp.bfloat16, 2, 4, 300, 16, [3, 299, 5 * 300 + 7],
        {"window": 296, "ring": True}),
    "stacked_traced_layer": (
        jnp.bfloat16, 8, 8, 2 * 256, 128, [0, 256, 511], {"stacked": True}),
    "stacked_traced_layer_g4_window": (
        jnp.bfloat16, 2, 4, 2 * 256, 128, [5, 256, 511],
        {"stacked": True, "window": 70}),
    "f32_q_beside_bf16_cache": (
        jnp.float32, 8, 8, 3 * 256, 128, [0, 255, 256, 333, 767], {}),
    "f32_q_g12_beside_bf16_cache": (
        jnp.float32, 2, 12, 300, 16, [0, 255, 299], {}),
    "bf16_q_g12": (jnp.bfloat16, 2, 12, 300, 16, [0, 255, 299], {}),
}


@pytest.mark.parametrize("case", BF16_EXACT)
def test_bf16_cache_equals_reference_on_the_same_operands(case):
    """A bf16 cache is multiplied as bf16 and nothing of the float32 result
    is given up: out and lse equal the float32 ``HIGHEST`` reference's ON
    THE SAME bf16 ARRAYS to 1e-5 (summation order; ``p`` cast to bf16, one
    piece, would be off by 1e-3), for every way the kernel is called."""
    q_dtype, hkv, g, T, dh, pos, kw = BF16_EXACT[case]
    kw = dict(kw)
    stacked = kw.pop("stacked", False)
    rng = np.random.default_rng(31)
    B = np.size(pos)
    q = rand(rng, B, hkv, g, dh).astype(q_dtype)
    k, v = (rand(rng, 2, B, hkv, T, dh).astype(jnp.bfloat16) for _ in "kv")
    pos = jnp.asarray(pos, jnp.int32)
    want_o, want_lse = fd.decode_attention_reference_lse(q, k[1], v[1], pos,
                                                         **kw)
    if stacked:
        got_o, got_lse = jax.jit(lambda l: fd.flash_decode_lse(
            q, k, v, pos, interpret=True, layer=l, **kw))(1)
    else:
        got_o, got_lse = fd.flash_decode_lse(q, k[1], v[1], pos,
                                             interpret=True, **kw)
    assert got_o.dtype == got_lse.dtype == jnp.float32
    np.testing.assert_allclose(got_o, want_o, atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(got_lse, want_lse, atol=1e-5, rtol=1e-5)


def _kernel_eqns(jaxpr):
    """Every equation of ``jaxpr`` and of the jaxprs nested in it (loops,
    branches)."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _kernel_eqns(sub)


@pytest.mark.parametrize("q_dtype,cache_dtype", [
    (jnp.bfloat16, jnp.bfloat16), (jnp.float32, jnp.bfloat16),
    (jnp.float32, jnp.float32), (jnp.bfloat16, jnp.float32)])
def test_visit_multiplies_the_cache_in_its_dtype(q_dtype, cache_dtype):
    """How often the bf16 arithmetic engages: always or never, by the
    cache's dtype at trace time. In the kernel's jaxpr for a bf16 cache no
    ``[bt, Dp]`` tile is converted to float32 and no product that reads
    one carries ``HIGHEST``; for a float32 cache both products carry it."""
    B, Hkv, G, T, Dh = 2, 2, 4, 2 * BT, 128
    q = jnp.zeros((B, Hkv, G, Dh), q_dtype)
    k = v = jnp.zeros((1, B, Hkv, T, Dh), cache_dtype)
    outer = jax.make_jaxpr(lambda q, k, v: fd.flash_decode_lse(
        q, k, v, jnp.arange(B), layer=0))(q, k, v)
    (call,) = [e for e in outer.eqns if e.primitive.name == "pallas_call"]
    assert call.params["name"] == "flash_decode"
    tile_dots, tile_converts = [], []
    for eqn in _kernel_eqns(call.params["jaxpr"]):
        # an operand that ends in [bt, Dp] is a cache tile (of one head or
        # of all of them)
        tiles = [getattr(x.aval, "shape", ())[-2:] == (BT, Dh)
                 for x in eqn.invars]
        if eqn.primitive.name == "dot_general" and any(tiles):
            tile_dots.append(eqn)
        if eqn.primitive.name == "convert_element_type" and tiles[0]:
            tile_converts.append(eqn)
    assert len(tile_dots) == 2                       # q.kT and p.v
    highest = [e.params["precision"] is not None
               and jax.lax.Precision.HIGHEST in tuple(e.params["precision"])
               for e in tile_dots]
    if cache_dtype == jnp.bfloat16:
        assert not tile_converts and not any(highest)
        for e in tile_dots:
            assert {x.aval.dtype for x in e.invars} == {jnp.dtype("bfloat16")}
            assert e.params["preferred_element_type"] == jnp.float32
    else:
        assert all(highest)
        for e in tile_dots:
            assert {x.aval.dtype for x in e.invars} == {jnp.dtype("float32")}


# What the parent of PR 33 traced for a bf16 cache, case by case (Hkv, G, T,
# kwargs): equations in the kernel, and in its visit loop the count of every
# primitive ("name:count ..."). PR 33 widened the LATENT kernel's visit
# through arguments ``_walk_row`` and ``kv_block_walk`` share with this
# kernel; nothing here may move with it. The horizon cases are the tail
# copy's: a full layer's copy of a visit's block is the block whole or the
# pieces of its live rows (16, 32, 64 and 128 rows), each of the five
# started and waited for under a ``cond`` (10 copies each way, 11 conds),
# the rows counted from the row's ``pos`` (``get:9``); rings and windows
# copy whole blocks as before.
PARENT_KERNEL = {
    "hkv8_horizon": (
        8, 8, 1024, {}, 363,
        "add:25 and:28 broadcast_in_dim:7 concatenate:1 cond:11 "
        "convert_element_type:22 div:1 dma_start:10 dma_wait:10 "
        "dot_general:2 eq:3 exp:2 get:9 iota:1 jit:7 le:1 lt:13 max:1 "
        "min:4 mul:17 multiple_of:18 ne:12 or:1 reduce_max:1 "
        "reduce_sum:1 rem:2 select_n:6 sign:2 slice:4 sub:11 swap:3"),
    "hkv1_horizon": (
        1, 4, 1024, {}, 362,
        "add:25 and:28 broadcast_in_dim:6 concatenate:1 cond:11 "
        "convert_element_type:22 div:1 dma_start:10 dma_wait:10 "
        "dot_general:2 eq:3 exp:2 get:9 iota:1 jit:7 le:1 lt:13 max:1 "
        "min:4 mul:17 multiple_of:18 ne:12 or:1 reduce_max:1 "
        "reduce_sum:1 rem:2 select_n:6 sign:2 slice:4 sub:11 swap:3"),
    "hkv8_ring": (
        8, 8, 512, {'window': 384, 'ring': True}, 345,
        "add:25 and:9 broadcast_in_dim:7 concatenate:1 cond:1 "
        "convert_element_type:18 div:2 dma_start:2 dma_wait:2 "
        "dot_general:2 eq:6 exp:2 get:7 iota:1 jit:20 le:1 lt:17 "
        "max:1 min:5 mul:8 multiple_of:2 ne:16 or:1 reduce_max:1 "
        "reduce_sum:1 rem:8 select_n:18 sign:4 slice:4 sub:12 swap:3"),
    "hkv1_ring": (
        1, 8, 512, {'window': 384, 'ring': True}, 344,
        "add:25 and:9 broadcast_in_dim:6 concatenate:1 cond:1 "
        "convert_element_type:18 div:2 dma_start:2 dma_wait:2 "
        "dot_general:2 eq:6 exp:2 get:7 iota:1 jit:20 le:1 lt:17 "
        "max:1 min:5 mul:8 multiple_of:2 ne:16 or:1 reduce_max:1 "
        "reduce_sum:1 rem:8 select_n:18 sign:4 slice:4 sub:12 swap:3"),
    "hkv8_window_on_horizon": (
        8, 8, 1024, {'window': 300}, 204,
        "add:14 and:5 broadcast_in_dim:7 concatenate:1 cond:1 "
        "convert_element_type:13 div:2 dma_start:2 dma_wait:2 "
        "dot_general:2 eq:1 exp:2 get:7 gt:1 iota:1 jit:9 le:1 lt:6 "
        "max:2 min:3 mul:6 multiple_of:2 ne:6 or:1 reduce_max:1 "
        "reduce_sum:1 rem:3 select_n:7 sign:4 slice:4 sub:10 swap:3"),
}


@pytest.mark.parametrize("case", PARENT_KERNEL)
def test_kernel_is_what_it_was_before_the_latent_visit_widened(case):
    """``flash_decode_lse``'s kernel for a bf16 cache, ``Hkv`` 8 and 1, ring
    and horizon: a visit is still a block of 256 positions of all heads (two
    ``[2, Hkv, 256, Dp]`` buffers; on a ring or a window one copy started
    and one waited for a stack, on a horizon the pieces above), its two
    products still read the bf16 tiles as stored against a
    16-row ``q`` and the padded three-piece stack of ``p``, and the loop
    holds the primitives it held, each as often."""
    hkv, g, T, kw, n_eqns, counts = PARENT_KERNEL[case]
    B, Dh = 3, 128
    q = jnp.zeros((B, hkv, g, Dh), jnp.bfloat16)
    k = v = jnp.zeros((2, B, hkv, T, Dh), jnp.bfloat16)
    outer = jax.make_jaxpr(lambda q, k, v: fd.flash_decode_lse(
        q, k, v, jnp.arange(B), layer=1, **kw))(q, k, v)
    (call,) = [e for e in outer.eqns if e.primitive.name == "pallas_call"]
    assert call.params["name"] == "flash_decode"
    kernel = call.params["jaxpr"]
    assert len(list(_kernel_eqns(kernel))) == n_eqns
    assert [x.aval.shape for x in kernel.invars
            if getattr(x.aval, "shape", ())[-2:] == (BT, Dh)] == [
        (2, hkv, BT, Dh)] * 2
    (loop,) = [e for e in _kernel_eqns(kernel)
               if e.primitive.name in ("while", "scan")]
    inside = [e for sub in jax.core.jaxprs_in_params(loop.params)
              for e in _kernel_eqns(sub)]
    assert dict(collections.Counter(
        e.primitive.name for e in inside)) == {
        k: int(n) for k, n in (kn.split(":") for kn in counts.split())}
    gp3 = -(-3 * (-(-g // 8) * 8) // 16) * 16
    dots = [e for e in inside if e.primitive.name == "dot_general"]
    assert [tuple(x.aval.shape for x in e.invars) for e in dots] == [
        ((hkv, 16, Dh), (hkv, BT, Dh)), ((hkv, gp3, BT), (hkv, BT, Dh))]
    for e in dots:
        assert {x.aval.dtype for x in e.invars} == {jnp.dtype("bfloat16")}
        assert e.params["preferred_element_type"] == jnp.float32
        assert e.params["precision"] is None


def test_large_scores_stable():
    """Online softmax must not overflow with large logits."""
    rng = np.random.default_rng(4)
    B, Hkv, G, Dh, T = 1, 1, 1, 8, 300
    q = rand(rng, B, Hkv, G, Dh) * 30
    k = rand(rng, B, Hkv, T, Dh) * 30
    v = rand(rng, B, Hkv, T, Dh)
    got = fused(q, k, v, T - 1)
    assert np.isfinite(np.asarray(got)).all()
    want = decode_attention_reference(q, k, v, T - 1)
    np.testing.assert_allclose(got, want, atol=1e-4, rtol=1e-4)


# -- lse-exposing variant (sequence-parallel decode merge) -------------------


def test_lse_matches_reference_lse():
    """flash_decode_lse's (out, lse) vs the reference pair, across GQA
    shapes, multi-block caches, and block-boundary positions."""
    from elephas_tpu.ops.flash_decode import (
        decode_attention_reference_lse,
        flash_decode_lse,
    )

    rng = np.random.default_rng(5)
    for (hkv, g, dh, t) in [(2, 2, 16, 40), (1, 4, 32, 300), (2, 5, 16, 257)]:
        q = rand(rng, 2, hkv, g, dh)
        k = rand(rng, 2, hkv, t, dh)
        v = rand(rng, 2, hkv, t, dh)
        for pos in (0, t // 2, t - 1):
            got_o, got_lse = flash_decode_lse(q, k, v, pos, interpret=True)
            want_o, want_lse = decode_attention_reference_lse(q, k, v, pos)
            np.testing.assert_allclose(got_o, want_o, atol=1e-5, rtol=1e-5,
                                       err_msg=f"out pos={pos}")
            np.testing.assert_allclose(got_lse, want_lse, atol=1e-5,
                                       rtol=1e-5, err_msg=f"lse pos={pos}")


def test_lse_merge_reconstructs_full_attention():
    """The logsumexp partial merge (the sharded-decode contract): splitting
    the cache into R slices, attending each with its own lse, and merging
    must equal attention over the whole cache."""
    from elephas_tpu.ops.flash_decode import (
        decode_attention_reference,
        flash_decode_lse,
    )

    rng = np.random.default_rng(6)
    B, Hkv, G, Dh, T, R = 2, 2, 2, 16, 64, 4
    Tl = T // R
    q = rand(rng, B, Hkv, G, Dh)
    k = rand(rng, B, Hkv, T, Dh)
    v = rand(rng, B, Hkv, T, Dh)
    for pos in (0, 13, Tl - 1, Tl, T - 1):
        outs, lses = [], []
        for r in range(R):
            pos_local = pos - r * Tl
            o_r, lse_r = flash_decode_lse(
                q, k[:, :, r * Tl:(r + 1) * Tl], v[:, :, r * Tl:(r + 1) * Tl],
                max(0, min(pos_local, Tl - 1)), interpret=True)
            lses.append(np.where(pos_local >= 0, np.asarray(lse_r), -np.inf))
            outs.append(np.asarray(o_r))
        m = np.max(lses, axis=0)
        w = np.exp(np.asarray(lses) - m)                      # [R, B, Hkv, G]
        merged = (w[..., None] * np.asarray(outs)).sum(0) / w.sum(0)[..., None]
        want = decode_attention_reference(q, k, v, pos)
        np.testing.assert_allclose(merged, want, atol=1e-5, rtol=1e-5,
                                   err_msg=f"pos={pos}")


def test_per_row_positions():
    """pos as a [B] vector: each row's visibility bound is independent
    (the batched-speculative-decoding contract) and equals per-row scalar
    calls."""
    rng = np.random.default_rng(7)
    B, Hkv, G, Dh, T = 4, 2, 2, 16, 64
    q = rand(rng, B, Hkv, G, Dh)
    k = rand(rng, B, Hkv, T, Dh)
    v = rand(rng, B, Hkv, T, Dh)
    pos = np.array([0, 13, 31, 63], np.int32)
    got = fused(q, k, v, jnp.asarray(pos))
    for b in range(B):
        want_b = decode_attention_reference(q[b:b + 1], k[b:b + 1],
                                            v[b:b + 1], int(pos[b]))
        np.testing.assert_allclose(got[b:b + 1], want_b, atol=1e-5,
                                   rtol=1e-5, err_msg=f"row {b}")


def test_lse_windowed_and_past_end_positions():
    """Round 5: the windowed kernel must accept positions PAST the cache
    end (a sequence-sharded rank whose slice the window partially left
    keeps global arithmetic that way) — alignment-padding rows masked,
    kv block index clipped, exact vs the reference at every pos in and
    beyond the cache."""
    from elephas_tpu.ops.flash_decode import (
        decode_attention_reference_lse,
        flash_decode_lse,
    )

    rng = np.random.default_rng(6)
    hkv, g, dh, t, w = 2, 2, 16, 40, 12
    q = rand(rng, 2, hkv, g, dh)
    k = rand(rng, 2, hkv, t, dh)
    v = rand(rng, 2, hkv, t, dh)
    for pos in (0, 5, t - 1, t, t + w // 2, t + w - 2):
        got_o, got_lse = flash_decode_lse(q, k, v, pos, interpret=True,
                                          window=w)
        want_o, want_lse = decode_attention_reference_lse(q, k, v, pos,
                                                          window=w)
        np.testing.assert_allclose(got_o, want_o, atol=1e-5, rtol=1e-5,
                                   err_msg=f"out pos={pos}")
        np.testing.assert_allclose(got_lse, want_lse, atol=1e-5,
                                   rtol=1e-5, err_msg=f"lse pos={pos}")


# -- stacked cache: the decode step's form ------------------------------------

BT = 256                                # the kernel's block of positions
# the small f32 stack, and the two served head shapes cut in T only
# (K-EXAONE: 8 KV heads x 8 queries of 128; Mixtral: x 4), bf16 caches
SHAPES = {
    "small": dict(L=3, B=3, Hkv=2, G=2, T=300, Dh=16, dtype=jnp.float32),
    "served_g8": dict(L=2, B=5, Hkv=8, G=8, T=3 * BT, Dh=128,
                      dtype=jnp.bfloat16),
    "served_g4": dict(L=2, B=5, Hkv=8, G=4, T=3 * BT, Dh=128,
                      dtype=jnp.bfloat16),
}
STACKED = ([("small", case, layer) for layer in (0, 1, 2)
            for case in ("scalar", "per_row", "window", "ring")]
           + [(shape, case, 1) for shape in ("served_g8", "served_g4")
              for case in ("per_row", "window", "ring", "scan")])


def _stack(seed, L, B, Hkv, G, T, Dh, dtype):
    rng = np.random.default_rng(seed)
    return (rand(rng, B, Hkv, G, Dh), rand(rng, L, B, Hkv, T, Dh).astype(dtype),
            rand(rng, L, B, Hkv, T, Dh).astype(dtype))


def _stacked_case(shape, case):
    """``(q, k, v, pos, kwargs)``: on the served shapes one batch whose
    rows sit at 0, the last row of a block, the first of the next,
    mid-cache and ``T - 1`` at once."""
    dims = dict(SHAPES[shape])
    if shape == "small":
        T = dims["T"]
        pos, kw = {
            "scalar": (257, {}),
            "per_row": ([0, 255, T - 1], {}),
            "window": ([5, 256, T - 1], {"window": 70}),
            "ring": ([3, T - 1, 5 * T + 7], {"window": T - 4, "ring": True}),
        }[case]
    else:
        T = dims["T"]
        pos, kw = {
            "per_row": ([0, BT - 1, BT, BT + 77, T - 1], {}),
            "scan": ([0, BT - 1, BT, BT + 77, T - 1], {}),
            # the window starts mid-block (row 3: at 301 of block 1; row
            # 2: clipped at 0), and the last row lies past the cache end
            "window": ([0, BT - 1, BT, 600, T + 100], {"window": 300}),
            # the served rings: one block of 256 rows, window 128
            "ring": ([0, 127, 128, BT - 1, 9 * BT + 7],
                     {"window": 128, "ring": True}),
        }[case]
        if case == "ring":
            dims["T"] = BT
    q, k, v = _stack(11, **dims)
    return q, k, v, jnp.asarray(pos, jnp.int32), kw


@pytest.mark.parametrize("shape,case,layer", STACKED)
def test_stacked_cache_with_layer_index(shape, case, layer, monkeypatch):
    """``k``/``v`` ``[L, B, Hkv, T, Dh]`` with a layer index: the kernel
    reads layer ``l`` of the stack in place and must equal the reference
    on ``k[l]``, ``v[l]`` — output and lse — for every way the decode step
    calls it (``scan``: a traced scalar position under ``lax.scan``, as
    ``generate`` calls it); the one-layer (4-D) form must still equal the
    same; and the kernel visits exactly the blocks ``kv_block_walk`` says,
    which on a horizon cache are the live ones and no more."""
    visits = []
    attend = fd._attend_block

    def counted(*args):
        jax.debug.callback(lambda: visits.append(1))
        return attend(*args)

    monkeypatch.setattr(fd, "_attend_block", counted)
    q, k, v, pos, kw = _stacked_case(shape, case)
    T = k.shape[3]
    want_o, want_lse = fd.decode_attention_reference_lse(
        q, k[layer], v[layer], pos, **kw)
    if case == "scan":
        # every row at the step's one position
        want_o, want_lse = (jnp.stack(x) for x in zip(*(
            fd.decode_attention_reference_lse(q, k[layer], v[layer], p)
            for p in pos)))
        rows = q.shape[0]
        run = jax.jit(lambda l: jax.lax.scan(
            lambda _, p: (None, fd.flash_decode_lse(
                q, k, v, p, interpret=True, layer=l)), None, pos)[1])
    else:
        rows = 1
        # a traced layer index, as under the decode step's scan
        run = jax.jit(lambda l: fd.flash_decode_lse(
            q, k, v, pos, interpret=True, layer=l, **kw))
    got_o, got_lse = jax.block_until_ready(run(layer))
    jax.effects_barrier()
    counted_visits = len(visits)
    np.testing.assert_allclose(got_o, want_o, atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(got_lse, want_lse, atol=1e-5, rtol=1e-5)
    _, walked, live = fd.kv_block_walk(
        np.broadcast_to(np.asarray(pos), (q.shape[0],)), T,
        kw.get("window"), kw.get("ring", False))
    assert counted_visits == rows * walked.sum()
    if shape == "small" and case == "ring":
        # a ring of two blocks is walked whole; rows 0 and 2 (3 and 1,507
        # positions in) see slots of one block and of both
        assert list(live) == [1, 2, 2] and list(walked) == [2, 2, 2]
    else:
        assert list(walked) == list(live)
    if case == "scan":
        return
    ref_o, ref_lse = fd.decode_attention_reference_lse(q, k, v, pos,
                                                       layer=layer, **kw)
    np.testing.assert_array_equal(ref_o, want_o)
    np.testing.assert_array_equal(ref_lse, want_lse)
    flat_o, flat_lse = fd.flash_decode_lse(q, k[layer], v[layer], pos,
                                           interpret=True, **kw)
    np.testing.assert_array_equal(flat_o, got_o)
    np.testing.assert_array_equal(flat_lse, got_lse)


def test_block_walk_against_the_masks():
    """``kv_block_walk``'s ``first``/``walked``/``live`` against a brute
    count from the reference's own visibility rule, at every position of
    short and long caches: no visible key outside the walk, and ``live``
    is the number of blocks that hold one."""
    kv_block_walk = fd.kv_block_walk
    for T, window, ring in [(40, None, False), (700, None, False),
                            (700, 70, False), (700, 300, False),
                            (256, 128, True), (700, 300, True),
                            (512, 600, True)]:
        bt = min(BT, T)
        n_t = -(-T // bt)
        span = 3 * T if ring else (T if window is None else T + window - 1)
        pos = np.arange(span)
        first, walked, live = kv_block_walk(pos, T, window, ring)
        slots = np.arange(T)[None, :]
        if ring:
            seen = (pos[:, None] - slots) % T < np.minimum(window,
                                                           pos[:, None] + 1)
        else:
            seen = slots <= pos[:, None]
            if window is not None:
                seen &= slots > pos[:, None] - window
        blocks = np.zeros((span, n_t), bool)
        for t in range(n_t):
            blocks[:, t] = seen[:, t * bt:(t + 1) * bt].any(axis=1)
        inside = ((np.arange(n_t)[None] >= first[:, None])
                  & (np.arange(n_t)[None] < (first + walked)[:, None]))
        assert not (blocks & ~inside).any(), (T, window, ring)
        np.testing.assert_array_equal(live, blocks.sum(axis=1))
        if not ring:
            np.testing.assert_array_equal(walked, live)
        assert (walked >= 1).all() and (first + walked <= n_t).all()
        # and the same numbers for a traced position, as the kernel asks
        got = jax.jit(lambda p: kv_block_walk(p, T, window, ring))(
            jnp.asarray(pos[-1], jnp.int32))
        assert [int(x) for x in got] == [first[-1], walked[-1], live[-1]]


# -- the tail copy: a full layer's last block up to its live rows ------------

# rows at every kind of tail (pos % 256 of 0, 15, 16, 31, 32, 127 and 255,
# and 1,023: the last block full) and of mixed depth: row 0 is one block
# (the call's first copy is a trimmed one), row 3 (one block) follows a
# deep row and precedes one, so a row's first copy, started by the row
# before it, is trimmed too
TAIL_POS = [0, BT + 15, 2 * BT + 16, 31, 3 * BT + 32, 127, BT + 255, 1023]
# (Hkv, G, stacked cache with a traced layer index)
TAIL_CASES = {
    "mha_hkv16": (16, 1, False),
    "mha_hkv16_stacked": (16, 1, True),
    "gqa_4to1": (4, 4, False),
    "gqa_4to1_stacked": (4, 4, True),
}


def _tail_call(q, k, v, pos, stacked, granule, monkeypatch):
    """``flash_decode_lse`` with its tail copied in ``granule``s (``None``:
    whole blocks) in the TPU interpreter, whose uninitialised VMEM holds
    NaN (what a row no copy reached would show), on layer 1 of the stack
    (a traced index) or on that layer alone."""
    from jax.experimental.pallas import tpu as pltpu

    monkeypatch.setattr(fd, "_TAIL_GRANULE", granule)
    kw = dict(interpret=pltpu.InterpretParams(uninitialized_memory="nan"))
    if stacked:
        return jax.jit(lambda l: fd.flash_decode_lse(
            q, k, v, pos, layer=l, **kw))(1)
    return fd.flash_decode_lse(q, k[1], v[1], pos, **kw)


@pytest.mark.parametrize("case", TAIL_CASES)
def test_tail_copy_is_the_whole_blocks_bit_for_bit(case, monkeypatch):
    """Copying a row's last block only up to its live rows changes no bit
    of the output or the lse against the whole-block copy, and both are
    the reference's on the same bf16 arrays, for MHA and GQA, the stacked
    cache with a traced layer and the one-layer form."""
    hkv, g, stacked = TAIL_CASES[case]
    rng = np.random.default_rng(42)
    B, T, dh = len(TAIL_POS), 4 * BT, 128
    q = rand(rng, B, hkv, g, dh).astype(jnp.bfloat16)
    k, v = (rand(rng, 2, B, hkv, T, dh).astype(jnp.bfloat16) for _ in "kv")
    pos = jnp.asarray(TAIL_POS, jnp.int32)
    got_o, got_lse = _tail_call(q, k, v, pos, stacked, fd._TAIL_GRANULE,
                                monkeypatch)
    whole_o, whole_lse = _tail_call(q, k, v, pos, stacked, None, monkeypatch)
    np.testing.assert_array_equal(got_o, whole_o)
    np.testing.assert_array_equal(got_lse, whole_lse)
    want_o, want_lse = fd.decode_attention_reference_lse(q, k[1], v[1], pos)
    np.testing.assert_allclose(got_o, want_o, atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(got_lse, want_lse, atol=1e-5, rtol=1e-5)


def test_tail_copy_reads_no_row_past_its_piece(monkeypatch):
    """Every position of a row's cache past what
    :func:`kv_copied_positions` says its copies cover is NaN: the trimmed
    kernel's output is finite and the reference's on the finite cache, so
    it read none of them; the whole-block copy reads them, and ``p = 0``
    times a NaN row of V is NaN."""
    rng = np.random.default_rng(43)
    hkv, g, dh, T = 4, 4, 128, 4 * BT
    B = len(TAIL_POS)
    q = rand(rng, B, hkv, g, dh).astype(jnp.bfloat16)
    k, v = (rand(rng, 2, B, hkv, T, dh).astype(jnp.bfloat16) for _ in "kv")
    pos = jnp.asarray(TAIL_POS, jnp.int32)
    copied = fd.kv_copied_positions(np.asarray(TAIL_POS), T)
    past = np.arange(T)[None, :] >= copied[:, None]               # [B, T]
    assert past.any(axis=1).sum() == B - 1     # all but the row at 1,023
    nan = jnp.asarray(past[None, :, None, :, None])
    k_nan, v_nan = (jnp.where(nan, jnp.nan, x).astype(x.dtype)
                    for x in (k, v))
    got_o, got_lse = _tail_call(q, k_nan, v_nan, pos, True, fd._TAIL_GRANULE,
                                monkeypatch)
    assert np.isfinite(got_o).all() and np.isfinite(got_lse).all()
    want_o, want_lse = fd.decode_attention_reference_lse(q, k[1], v[1], pos)
    np.testing.assert_allclose(got_o, want_o, atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(got_lse, want_lse, atol=1e-5, rtol=1e-5)
    whole_o, _ = _tail_call(q, k_nan, v_nan, pos, True, None, monkeypatch)
    assert not np.isfinite(whole_o).all()


def test_copied_positions_against_the_masks():
    """``kv_copied_positions`` against a count from the reference's own
    visibility rule: on a full layer every walked block but the last whole
    and the last up to the granule that holds its last visible key; on a
    ring, a window on a horizon and with ``granule=None`` (the latent
    kernel's walk) every walked block whole; short caches whose block is
    no whole number of granules whole too."""
    for T, window, ring, granule in [
            (40, None, False, 16), (48, None, False, 16),
            (64, None, False, 32), (700, None, False, 16),
            (700, None, False, 32), (1024, None, False, 16),
            (700, None, False, None), (700, 70, False, 16),
            (256, 128, True, 16), (700, 300, True, 16)]:
        bt = min(BT, -(-T // 8) * 8)
        n_t = -(-T // bt)
        span = 3 * T if ring else T + 40
        pos = np.arange(span)
        got = fd.kv_copied_positions(pos, T, window, ring, granule=granule)
        first, walked, _ = fd.kv_block_walk(pos, T, window, ring)
        trims = (granule is not None and window is None and not ring
                 and bt % granule == 0 and bt > granule)
        want = walked * bt
        if trims:
            seen = np.arange(n_t * bt)[None, :] <= pos[:, None]
            last = first + walked - 1
            for i, p in enumerate(pos):
                tail = seen[i, last[i] * bt:(last[i] + 1) * bt]
                granules = tail.reshape(-1, granule).any(axis=1)
                want[i] = (walked[i] - 1) * bt + granule * (
                    np.flatnonzero(granules)[-1] + 1)
        np.testing.assert_array_equal(got, want, err_msg=str(
            (T, window, ring, granule)))
        assert (got <= walked * bt).all()
        # and the same number for a traced position, as the kernel asks
        traced = jax.jit(lambda p: fd.kv_copied_positions(
            p, T, window, ring, granule=granule))(jnp.asarray(pos[-7],
                                                              jnp.int32))
        assert int(traced) == got[-7]
    # the latent kernel's own block: whole blocks of 1,024
    lbt = fd.latent_block_t(8192)
    assert int(fd.kv_copied_positions(np.int64(3700), 8192, block=lbt,
                                      granule=None)) == 4 * lbt


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("shape", [(3, 4, 2, 512, 16), (2, 3, 1, 40, 8)])
@pytest.mark.parametrize("per_row", [False, True])
def test_cache_write_row_in_place_equals_reference(per_row, shape, dtype):
    """The aliasing row-write kernel: exactly the reference's one new row
    per batch row in the named layer and nothing else, for scalar and
    per-row offsets (clamped like dynamic_update_slice), tiled caches and
    the short one-block cache."""
    from elephas_tpu.ops.flash_decode import (
        cache_write_row_reference,
        flash_cache_write_row,
    )

    rng = np.random.default_rng(12)
    L, B, Hkv, T, Dh = shape
    k, v = (rand(rng, *shape).astype(dtype) for _ in range(2))
    k_new, v_new = (rand(rng, B, Hkv, Dh).astype(dtype) for _ in range(2))
    offsets = ([jnp.asarray(rng.integers(0, T, size=B), jnp.int32),
                jnp.full((B,), T + 3, jnp.int32)] if per_row
               else [0, T // 2 + 1, T - 1, T + 3])
    for layer in range(L):
        for pos in offsets:
            got = jax.jit(functools.partial(flash_cache_write_row,
                                            interpret=True))(
                k, v, k_new, v_new, layer, pos)
            want = cache_write_row_reference(k, v, k_new, v_new, layer, pos)
            for g, w, old, new in zip(got, want, (k, v), (k_new, v_new)):
                np.testing.assert_array_equal(np.asarray(g, np.float32),
                                              np.asarray(w, np.float32))
                at = np.clip(np.broadcast_to(np.asarray(pos), (B,)), 0, T - 1)
                changed = np.asarray(old, np.float32).copy()
                changed[layer, np.arange(B), :, at, :] = np.asarray(
                    new, np.float32)
                np.testing.assert_array_equal(np.asarray(g, np.float32),
                                              changed)
