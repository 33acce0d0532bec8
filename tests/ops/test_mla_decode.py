"""The latent decode kernel (``ops/flash_decode.mla_decode``) interpreted
on the CPU against its ``jax.numpy`` reference, and the latent row writer:
rows at the edges of the kernel's 1,024-position blocks at once, the
stacked cache with a traced layer, under ``lax.scan``; the visits it makes
against ``kv_block_walk`` at its own block; and, on the kernel's jaxpr, ONE
tile copy a visit, three pieces of ``p`` and no tile converted up."""

import importlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

fd = importlib.import_module("elephas_tpu.ops.flash_decode")
BT = fd._BLOCK_T
# a latent visit's block where the cache is whole ones (the cell's 8,192)
LBT = fd.latent_block_t(8 * fd._LATENT_BLOCK_T)


def _case(L=3, B=6, T=4 * BT, Dc=256, H=8, dtype=jnp.float32, seed=0):
    rng = np.random.default_rng(seed)
    c = jnp.asarray(rng.standard_normal((L, B, 1, T, Dc)), dtype)
    q = jnp.asarray(rng.standard_normal((B, H, Dc)), dtype)
    return q, c


def test_latent_block_is_the_widest_that_tiles_the_cache():
    """A latent visit covers 1,024 positions where the cache is whole
    blocks of that, else 512, else ``flash_decode``'s 256 (or the whole of
    a shorter cache): one function, which the kernel, ``decode_walks`` and
    the engine's counters all ask."""
    assert LBT == fd._LATENT_BLOCK_T == 1024 == 4 * BT
    assert [fd.latent_block_t(T) for T in
            (8192, 1024, 4608, 512, 768, 2304, 256, 40, 8)] == [
        1024, 1024, 512, 512, 256, 256, 256, 40, 8]
    for T in (8192, 4608, 768, 40):
        assert T % fd.latent_block_t(T) == 0
    # the walk in those blocks: a row at 3,700 of 8,192 makes 4 visits
    assert [int(x) for x in fd.kv_block_walk(
        np.int64(3700), 8192, block=fd.latent_block_t(8192))] == [0, 4, 4]
    assert [int(x) for x in fd.kv_block_walk(np.int64(3700), 8192)] == [
        0, 15, 15]


# what the wider block can get wrong, each against the reference on the
# same arrays: (q dtype, cache dtype, H, T, pos, stacked-and-traced layer)
HALF = LBT // 2
EXACT = {
    # the last position of a block's first half, the first of its second,
    # its last, the first of a new visit, mid-cache and T-1, in ONE call
    "edges_f32": (jnp.float32, jnp.float32, 8, 3 * LBT,
                  [0, HALF - 1, HALF, LBT - 1, LBT, 2 * LBT + 7, 3 * LBT - 1,
                   3], False),
    "edges_bf16": (jnp.bfloat16, jnp.bfloat16, 8, 3 * LBT,
                   [0, HALF - 1, HALF, LBT - 1, LBT, 2 * LBT + 7,
                    3 * LBT - 1, 3], False),
    "scalar_pos_first_of_a_visit": (jnp.bfloat16, jnp.bfloat16, 8, 2 * LBT,
                                    LBT, False),
    "scalar_pos_last_of_a_visit": (jnp.float32, jnp.float32, 8, 2 * LBT,
                                   LBT - 1, False),
    "traced_layer_bf16": (jnp.bfloat16, jnp.bfloat16, 16, 2 * LBT,
                          [LBT - 1, LBT, 2 * LBT - 1], True),
    "f32_q_beside_bf16_cache": (jnp.float32, jnp.bfloat16, 8, 2 * LBT,
                                [0, HALF, LBT, 2 * LBT - 1], False),
    "f32_cache_highest": (jnp.float32, jnp.float32, 16, 2 * LBT,
                          [HALF - 1, LBT - 1, LBT + 1], True),
    "bf16_q_beside_f32_cache": (jnp.bfloat16, jnp.float32, 8, LBT,
                                [0, LBT - 1], False),
    "h5_not_whole_tiles": (jnp.bfloat16, jnp.bfloat16, 5, 2 * LBT,
                           [HALF, LBT - 1, LBT], False),
    "h12_f32_q": (jnp.float32, jnp.bfloat16, 12, 2 * LBT,
                  [0, LBT, 2 * LBT - 1], False),
    "one_block_of_1024": (jnp.bfloat16, jnp.bfloat16, 8, LBT,
                          [0, HALF, LBT - 1], False),
    "one_block_of_40": (jnp.float32, jnp.float32, 4, 40, [0, 17, 39], False),
    "blocks_of_256_in_768": (jnp.bfloat16, jnp.bfloat16, 8, 3 * BT,
                             [0, BT - 1, BT, 3 * BT - 1], False),
    "blocks_of_512_in_1536": (jnp.bfloat16, jnp.bfloat16, 8, 6 * BT,
                              [2 * BT - 1, 2 * BT, 6 * BT - 1], True),
}


@pytest.mark.parametrize("case", EXACT)
def test_equals_reference_on_the_same_operands(case):
    """Every way the kernel is called, at the edges of its blocks: the
    float32 result of the SAME operands (a bf16 cache gives up no bit: the
    reference multiplies the same bf16 arrays at ``HIGHEST``)."""
    q_dtype, c_dtype, H, T, pos, stacked = EXACT[case]
    B = np.size(pos)
    _, c = _case(L=2, B=B, T=T, Dc=256, H=H, dtype=c_dtype, seed=3)
    q = jnp.asarray(np.random.default_rng(4).standard_normal((B, H, 256)),
                    q_dtype)
    pos = jnp.asarray(pos, jnp.int32)
    want = fd.mla_decode_reference(q, c, pos, layer=1, rank=128, scale=0.11)
    if stacked:
        got = jax.jit(lambda l: fd.mla_decode(
            q, c, pos, layer=l, rank=128, scale=0.11, interpret=True))(1)
    else:
        # the one-layer form is the same call over a one-layer stack
        got = fd.mla_decode(q, c[1], pos, rank=128, scale=0.11,
                            interpret=True)
    assert got.shape == (B, H, 128) and got.dtype == jnp.float32
    np.testing.assert_allclose(got, want, atol=5e-6, rtol=5e-6)


def test_reference_by_hand():
    q, c = _case(L=1, B=2, T=BT, Dc=128, H=3)
    pos = np.array([5, 100])
    got = np.asarray(fd.mla_decode_reference(q, c, jnp.asarray(pos), layer=0,
                                             rank=128, scale=0.2))
    for b in range(2):
        rows = np.asarray(c[0, b, 0, :pos[b] + 1], np.float64)
        s = np.asarray(q[b], np.float64) @ rows.T * 0.2
        p = np.exp(s - s.max(-1, keepdims=True))
        p /= p.sum(-1, keepdims=True)
        np.testing.assert_allclose(got[b], p @ rows, atol=1e-5)


def test_traced_layer_and_position_under_scan():
    """The decode step's use: the stack in a ``lax.scan``'s carry, layer
    and position traced, a row written (in place) and then attended."""
    q, c = _case(L=3, B=2, T=2 * LBT, Dc=128, H=4)
    new = jnp.asarray(np.random.default_rng(5).standard_normal((3, 2, 128)),
                      jnp.float32)

    def run(write, attend):
        def body(carry, xs):
            c, pos = carry
            layer, row = xs
            c = write(c, row, layer, pos)
            return (c, pos + 1), attend(q, c, pos, layer)
        return jax.jit(lambda c: jax.lax.scan(
            body, (c, jnp.asarray([LBT - 1, 17])),
            (jnp.arange(3), new)))(c)

    (c_k, _), out_k = run(
        lambda c, row, l, p: fd.flash_latent_write_row(c, row, l, p,
                                                       interpret=True),
        lambda q, c, p, l: fd.mla_decode(q, c, p, layer=l, rank=128,
                                         scale=0.3, interpret=True))
    (c_r, _), out_r = run(
        fd.latent_write_row_reference,
        lambda q, c, p, l: fd.mla_decode_reference(q, c, p, l, 128, 0.3))
    np.testing.assert_array_equal(c_k, c_r)
    np.testing.assert_allclose(out_k, out_r, atol=5e-6)
    # the rows went where the positions said, one a layer (row 0 steps
    # from the last position of a visit into the next one)
    assert (np.asarray(c_k[1, 0, 0, LBT]) == np.asarray(new[1, 0])).all()
    assert (np.asarray(c_k[2, 1, 0, 19]) == np.asarray(new[2, 1])).all()


@pytest.mark.parametrize("per_row", [False, True])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_latent_write_row_in_place_equals_reference(per_row, dtype):
    _, c = _case(L=2, B=3, T=BT, Dc=128, dtype=dtype)
    new = jnp.asarray(np.random.default_rng(1).standard_normal((3, 128)),
                      dtype)
    pos = jnp.asarray([0, 77, BT - 1]) if per_row else jnp.asarray(40)
    want = fd.latent_write_row_reference(c, new, 1, pos)
    got = fd.flash_latent_write_row(c, new, 1, pos, interpret=True)
    np.testing.assert_array_equal(got, want)
    assert int((np.asarray(got != c)).sum()) <= 3 * 128
    # off the TPU the dispatcher is the reference
    np.testing.assert_array_equal(fd.latent_write_row(c, new, 1, pos), want)


def _kernel_eqns(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _kernel_eqns(sub)


def _call(q, c, **kw):
    outer = jax.make_jaxpr(lambda q, c: fd.mla_decode(
        q, c, jnp.arange(q.shape[0]), layer=0, rank=128, **kw))(q, c)
    (call,) = [e for e in outer.eqns if e.primitive.name == "pallas_call"]
    assert call.params["name"] == "mla_decode"
    return call


def test_one_tile_copy_a_visit_and_no_tile_converted_up():
    """In the kernel's jaxpr for a bf16 cache: the visit loop starts ONE
    copy (the next ``[1024, Dc]`` tile, into the other half of a double
    buffer) and waits for one; the two products read the tile and its
    first ``rank`` columns as bf16, ``preferred_element_type`` float32,
    with no ``HIGHEST`` and no conversion of a tile; the float32
    probabilities are what is split (three bf16 pieces stacked: 3 x 16
    rows)."""
    q, c = _case(L=1, B=2, T=2 * LBT, Dc=256, H=16, dtype=jnp.bfloat16)
    call = _call(q, c)
    kernel = call.params["jaxpr"]
    # the tile buffer: two tiles of one visit's block, as the cache holds it
    assert [v.aval.shape for v in kernel.invars
            if getattr(v.aval, "shape", ())[-2:] == (LBT, 256)] == [
        (2, 1, LBT, 256)]
    (loop,) = [e for e in _kernel_eqns(kernel)
               if e.primitive.name in ("while", "scan")]
    inside = [e for sub in jax.core.jaxprs_in_params(loop.params)
              for e in _kernel_eqns(sub)]
    assert sum(e.primitive.name == "dma_start" for e in inside) == 1
    assert sum(e.primitive.name == "dma_wait" for e in inside) == 1
    dots = [e for e in inside if e.primitive.name == "dot_general"]
    assert [tuple(x.aval.shape for x in e.invars) for e in dots] == [
        ((1, 16, 256), (1, LBT, 256)),          # q . row, all columns
        ((1, 48, LBT), (1, LBT, 128))]          # p (3 pieces) . the values
    for e in dots:
        assert {x.aval.dtype for x in e.invars} == {jnp.dtype("bfloat16")}
        assert e.params["preferred_element_type"] == jnp.float32
        assert e.params["precision"] is None
    for e in inside:
        if e.primitive.name == "convert_element_type":
            assert e.invars[0].aval.shape[-2:] not in ((LBT, 256),
                                                        (LBT, 128))
    # the three pieces: p and its two remainders, stacked in float32 and
    # rounded to bf16 once
    (stack,) = [e for e in inside if e.primitive.name == "concatenate"]
    assert [x.aval.shape for x in stack.invars] == [(1, 16, LBT)] * 3
    assert {x.aval.dtype for x in stack.invars} == {jnp.dtype("float32")}
    # a float32 cache keeps the package's rule for float32: HIGHEST
    qf, cf = _case(L=1, B=2, T=2 * LBT, Dc=256, H=16)
    dots = [e for e in _kernel_eqns(_call(qf, cf).params["jaxpr"])
            if e.primitive.name == "dot_general"]
    assert len(dots) == 2 and all(
        jax.lax.Precision.HIGHEST in tuple(e.params["precision"])
        for e in dots)


def test_visits_are_the_block_walks():
    """The kernel takes its bounds from ``kv_block_walk`` at its own block
    (no window, no ring): a row at ``pos`` visits ``pos // 1024 + 1``
    blocks, which is what the engine counts (``decode_walks`` hands it the
    same block). Shown by poisoning every tile beyond a row's walk."""
    T = 4 * LBT
    q, c = _case(L=1, B=5, T=T, Dc=128, H=4)
    pos = np.array([0, LBT - 1, LBT, 2 * LBT + 9, 4 * LBT - 1])
    assert fd.latent_block_t(T) == LBT
    first, walked, live = fd.kv_block_walk(pos, T, None, False, LBT)
    assert list(walked) == [1, 1, 2, 3, 4] and list(first) == [0] * 5
    assert (walked == live).all()
    # a cache tile beyond a row's walk is never read: poison them
    poisoned = np.asarray(c).copy()
    for b, w in enumerate(walked):
        poisoned[0, b, 0, w * LBT:] = np.nan
    got = fd.mla_decode(q, jnp.asarray(poisoned), jnp.asarray(pos), layer=0,
                        rank=128, scale=0.2, interpret=True)
    want = fd.mla_decode_reference(q, c, jnp.asarray(pos), 0, 128, 0.2)
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_allclose(got, want, atol=5e-6)
    # and the walk is not a block short: a poisoned LAST walked tile shows
    poisoned[0, 3, 0, 2 * LBT:3 * LBT] = np.nan
    bad = np.asarray(fd.mla_decode(
        q, jnp.asarray(poisoned), jnp.asarray(pos), layer=0, rank=128,
        scale=0.2, interpret=True))
    assert not np.isfinite(bad[3]).all()
    assert np.isfinite(bad[[0, 1, 2, 4]]).all()


def test_shapes_the_kernel_cannot_tile_are_refused_in_words():
    q, c = _case(L=1, B=1, T=BT, Dc=128, H=2)
    with pytest.raises(ValueError, match="whole 128-column lanes"):
        fd.mla_decode(q[..., :100], c[..., :100], 0, layer=0, rank=64)
    with pytest.raises(ValueError, match="whole 128-column lanes"):
        fd.mla_decode(q, c, 0, layer=0, rank=100)
