"""The latent decode kernel (``ops/flash_decode.mla_decode``) interpreted
on the CPU against its ``jax.numpy`` reference, and the latent row writer:
rows at the cache's edges at once, the stacked cache with a traced layer,
under ``lax.scan``; the visits it makes against ``kv_block_walk``; and, on
the kernel's jaxpr, ONE tile copy a visit and no tile converted up."""

import importlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

fd = importlib.import_module("elephas_tpu.ops.flash_decode")
BT = fd._BLOCK_T


def _case(L=3, B=6, T=4 * BT, Dc=256, H=8, dtype=jnp.float32, seed=0):
    rng = np.random.default_rng(seed)
    c = jnp.asarray(rng.standard_normal((L, B, 1, T, Dc)), dtype)
    q = jnp.asarray(rng.standard_normal((B, H, Dc)), dtype)
    return q, c


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 5e-6),
                                       (jnp.bfloat16, 5e-6)])
def test_rows_at_the_edges_at_once(dtype, tol):
    """Rows at positions 0, 255, 256, mid-cache and T-1 in ONE call: each
    walks its own blocks. A bf16 cache gives the float32 result of the
    same (bf16) operands: no bit given up."""
    q, c = _case(dtype=dtype)
    T = c.shape[3]
    pos = jnp.asarray([0, BT - 1, BT, T // 2 + 7, T - 1, 3])
    for layer in (0, 2):
        want = fd.mla_decode_reference(q, c, pos, layer=layer, rank=128,
                                       scale=0.11)
        got = fd.mla_decode(q, c, pos, layer=layer, rank=128, scale=0.11,
                            interpret=True)
        assert got.shape == (6, 8, 128) and got.dtype == jnp.float32
        np.testing.assert_allclose(got, want, atol=tol, rtol=tol)
    # the one-layer form is the same call over a one-layer stack
    np.testing.assert_array_equal(
        fd.mla_decode(q, c[1], pos, rank=128, scale=0.11, interpret=True),
        fd.mla_decode(q, c, pos, layer=1, rank=128, scale=0.11,
                      interpret=True))


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
    q, c = _case(L=3, B=2, T=2 * BT, Dc=128, H=4)
    new = jnp.asarray(np.random.default_rng(5).standard_normal((3, 2, 128)),
                      jnp.float32)

    def run(write, attend):
        def body(carry, xs):
            c, pos = carry
            layer, row = xs
            c = write(c, row, layer, pos)
            return (c, pos + 1), attend(q, c, pos, layer)
        return jax.jit(lambda c: jax.lax.scan(
            body, (c, jnp.asarray([BT - 1, 17])),
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
    # the rows went where the positions said, one a layer
    assert (np.asarray(c_k[1, 0, 0, BT]) == np.asarray(new[1, 0])).all()
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
    copy (the next tile) and waits for one; the two products read the
    ``[bt, Dc]`` tile and its first ``rank`` columns as bf16, with no
    ``HIGHEST`` and no conversion of a tile; the float32 probabilities are
    what is split (three bf16 pieces stacked: 3 x 16 rows)."""
    q, c = _case(L=1, B=2, T=2 * BT, Dc=256, H=16, dtype=jnp.bfloat16)
    call = _call(q, c)
    (loop,) = [e for e in _kernel_eqns(call.params["jaxpr"])
               if e.primitive.name in ("while", "scan")]
    inside = [e for sub in jax.core.jaxprs_in_params(loop.params)
              for e in _kernel_eqns(sub)]
    assert sum(e.primitive.name == "dma_start" for e in inside) == 1
    assert sum(e.primitive.name == "dma_wait" for e in inside) == 1
    dots = [e for e in inside if e.primitive.name == "dot_general"]
    assert [tuple(x.aval.shape for x in e.invars) for e in dots] == [
        ((1, 16, 256), (1, BT, 256)),           # q . row, all columns
        ((1, 48, BT), (1, BT, 128))]            # p (3 pieces) . the values
    for e in dots:
        assert {x.aval.dtype for x in e.invars} == {jnp.dtype("bfloat16")}
        assert e.params["preferred_element_type"] == jnp.float32
        assert e.params["precision"] is None
    for e in inside:
        if e.primitive.name == "convert_element_type":
            assert e.invars[0].aval.shape[-2:] not in ((BT, 256), (BT, 128))
    # a float32 cache keeps the package's rule for float32: HIGHEST
    qf, cf = _case(L=1, B=2, T=2 * BT, Dc=256, H=16)
    dots = [e for e in _kernel_eqns(_call(qf, cf).params["jaxpr"])
            if e.primitive.name == "dot_general"]
    assert len(dots) == 2 and all(
        jax.lax.Precision.HIGHEST in tuple(e.params["precision"])
        for e in dots)


def test_visits_are_the_block_walks():
    """The kernel takes its bounds from ``kv_block_walk`` (no window, no
    ring): a row at ``pos`` visits ``pos // 256 + 1`` blocks, which is what
    the engine counts. Counted by running the kernel interpreted with the
    walk function wrapped."""
    q, c = _case(L=1, B=5, T=4 * BT, Dc=128, H=4)
    pos = np.array([0, BT - 1, BT, 2 * BT + 9, 4 * BT - 1])
    first, walked, live = fd.kv_block_walk(pos, 4 * BT, None, False)
    assert list(walked) == [1, 1, 2, 3, 4] and list(first) == [0] * 5
    assert (walked == live).all()
    # a cache tile beyond a row's walk is never read: poison them
    poisoned = np.asarray(c).copy()
    for b, w in enumerate(walked):
        poisoned[0, b, 0, w * BT:] = np.nan
    got = fd.mla_decode(q, jnp.asarray(poisoned), jnp.asarray(pos), layer=0,
                        rank=128, scale=0.2, interpret=True)
    want = fd.mla_decode_reference(q, c, jnp.asarray(pos), 0, 128, 0.2)
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_allclose(got, want, atol=5e-6)


def test_shapes_the_kernel_cannot_tile_are_refused_in_words():
    q, c = _case(L=1, B=1, T=BT, Dc=128, H=2)
    with pytest.raises(ValueError, match="whole 128-column lanes"):
        fd.mla_decode(q[..., :100], c[..., :100], 0, layer=0, rank=64)
    with pytest.raises(ValueError, match="whole 128-column lanes"):
        fd.mla_decode(q, c, 0, layer=0, rank=100)
