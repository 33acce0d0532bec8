"""Gated DeltaNet (``ops/gated_delta.py``): the chunkwise (WY) form and the
decode update, reference and interpreted Pallas kernel, against the
one-position recurrence; the short convolution's chunk and step; the
state's packed layout. CPU, float32, small widths.

Tolerances. The three forms are the same float32 mathematics in another
order of additions: with states and outputs of order 1 they agree to a few
float32 roundings a position, 2e-5 absolute here. A state rounded to
bfloat16 between positions carries 8 bits, and after 256 positions is
1e-2 away: the control that the tolerance can tell float32 from the
nearest precision below."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from elephas_tpu.ops import gated_delta as gd

ATOL = 2e-5


def _inputs(B, T, H, dk, dv, alpha, beta_max=2.0, seed=0):
    """Unit-length q (scaled) and k, values of order 1, decays scattered
    around ``alpha`` and ``beta`` up to ``beta_max``."""
    rng = np.random.default_rng(seed)

    def n(*shape):
        return jnp.asarray(rng.standard_normal(shape), jnp.float32)

    q = gd.l2_normalize(n(B, T, H, dk)) * dk ** -0.5
    k = gd.l2_normalize(n(B, T, H, dk))
    g = jnp.log(alpha) * jnp.asarray(rng.uniform(0.5, 1.5, (B, T, H)),
                                     jnp.float32)
    beta = jnp.asarray(rng.uniform(0.0, beta_max, (B, T, H)), jnp.float32)
    return q, k, n(B, T, H, dv), g, beta, n(B, H, dk, dv)


@pytest.mark.parametrize("alpha", [0.5, 0.999], ids=["fast", "slow"])
@pytest.mark.parametrize("T", [37, 64, 150], ids=["under", "at", "over"])
def test_chunkwise_form_is_the_recurrence(alpha, T):
    """Lengths under, at and over a block of 64, fast and slow decay, beta
    up to 2, from a carried-in state."""
    q, k, v, g, beta, s0 = _inputs(2, T, 3, 8, 16, alpha, seed=T)
    o_ref, s_ref = gd.gdn_recurrence(q, k, v, g, beta, s0)
    o, s = gd.gdn_chunk(q, k, v, g, beta, s0)
    np.testing.assert_allclose(o, o_ref, atol=ATOL)
    np.testing.assert_allclose(s, s_ref, atol=ATOL)
    # and from the zero state a sequence starts with
    zero = jnp.zeros_like(s0)
    np.testing.assert_allclose(
        gd.gdn_chunk(q, k, v, g, beta, zero)[0],
        gd.gdn_recurrence(q, k, v, g, beta, zero)[0], atol=ATOL)


def test_nothing_past_n_valid_touches_the_state():
    """A padded bucket: positions from ``n_valid`` on (per row) fold
    nothing in, and the outputs before them are the unpadded ones."""
    q, k, v, g, beta, s0 = _inputs(2, 128, 2, 8, 16, 0.9, seed=5)
    n_valid = jnp.asarray([100, 37])
    o, s = gd.gdn_chunk(q, k, v, g, beta, s0, n_valid=n_valid)
    for b, n in enumerate((100, 37)):
        cut = [x[b:b + 1, :n] for x in (q, k, v, g, beta)]
        o_ref, s_ref = gd.gdn_recurrence(*cut, s0[b:b + 1])
        np.testing.assert_allclose(s[b], s_ref[0], atol=ATOL)
        np.testing.assert_allclose(o[b, :n], o_ref[0], atol=ATOL)
    # without the mask the padding IS folded in: the rule is not vacuous
    _, s_all = gd.gdn_chunk(q, k, v, g, beta, s0)
    assert float(jnp.abs(s_all[1] - s[1]).max()) > 1e-2
    # a scalar n_valid, traced
    o1, s1 = jax.jit(lambda n: gd.gdn_chunk(q, k, v, g, beta, s0, n_valid=n))(
        jnp.int32(37))
    np.testing.assert_allclose(s1[1], s[1], atol=ATOL)


def test_two_chunks_equal_one():
    """A continuation chunk from the first chunk's state."""
    q, k, v, g, beta, s0 = _inputs(1, 200, 2, 8, 16, 0.97, seed=2)
    o, s = gd.gdn_chunk(q, k, v, g, beta, s0)
    a = [x[:, :70] for x in (q, k, v, g, beta)]
    b = [x[:, 70:] for x in (q, k, v, g, beta)]
    o_a, s_a = gd.gdn_chunk(*a, s0)
    o_b, s_b = gd.gdn_chunk(*b, s_a)
    np.testing.assert_allclose(jnp.concatenate([o_a, o_b], 1), o, atol=ATOL)
    np.testing.assert_allclose(s_b, s, atol=ATOL)


def test_a_bfloat16_state_fails_where_float32_passes():
    """256 positions of slow decay: the float32 forms agree within the
    tolerance, the recurrence with its state rounded to bfloat16 every
    position does not, by a factor of hundreds."""
    q, k, v, g, beta, s0 = _inputs(1, 256, 2, 8, 16, 0.999, seed=9)
    o_ref, s_ref = gd.gdn_recurrence(q, k, v, g, beta, s0)
    o, s = gd.gdn_chunk(q, k, v, g, beta, s0)
    assert float(jnp.abs(s - s_ref).max()) < ATOL
    assert float(jnp.abs(o - o_ref).max()) < ATOL
    o_lo, s_lo = gd.gdn_recurrence(q, k, v, g, beta, s0,
                                   state_dtype=jnp.bfloat16)
    assert float(jnp.abs(s_lo - s_ref).max()) > 100 * ATOL
    assert float(jnp.abs(o_lo - o_ref).max()) > 20 * ATOL


def _decode_case(B=5, H=4, dk=8, dv=64, L=3, seed=0):
    q, k, v, g, beta, _ = _inputs(B, 1, H, dk, dv, 0.9, seed=seed)
    rng = np.random.default_rng(seed + 1)
    state = jnp.asarray(rng.standard_normal(
        (L, B, H // gd.state_group(H, dv), dk, gd.state_group(H, dv) * dv)),
        jnp.float32)
    return q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0], state


LIVE = {"every_row": None,
        "some": [True, False, True, True, False],
        "none": [False] * 5,
        "last_only": [False, False, False, False, True],
        "first_only": [True, False, False, False, False]}


@pytest.mark.parametrize("live", LIVE.values(), ids=LIVE.keys())
@pytest.mark.parametrize("kernel", [False, True], ids=["einsum", "pallas"])
def test_decode_update_is_one_position_of_the_recurrence(kernel, live):
    """The einsum form and the interpreted kernel against ``gdn_step``:
    layer 1 of a three-layer packed stack (two heads a tile: dv 64), live
    rows updated in place, every other row and layer untouched."""
    q, k, v, g, beta, state = _decode_case()
    live = None if live is None else jnp.asarray(live)
    H = q.shape[1]
    if kernel:
        o, new = jax.jit(lambda st, l: gd.gdn_decode(
            q, k, v, g, beta, st, l, live, interpret=True))(state,
                                                            jnp.int32(1))
    else:
        o, new = gd.gdn_decode_reference(q, k, v, g, beta, state, 1, live)
    o_ref, s_ref = gd.gdn_step(q, k, v, g, beta,
                               gd.unpack_state(state[1], H))
    rows = np.ones(5, bool) if live is None else np.asarray(live)
    got = gd.unpack_state(new[1], H)
    np.testing.assert_allclose(got[rows], s_ref[rows], atol=ATOL)
    np.testing.assert_allclose(np.asarray(o)[rows], o_ref[rows], atol=ATOL)
    # a row that is not live keeps its state, bit for bit; so do the
    # other layers
    np.testing.assert_array_equal(np.asarray(new[1])[~rows],
                                  np.asarray(state[1])[~rows])
    np.testing.assert_array_equal(new[0], state[0])
    np.testing.assert_array_equal(new[2], state[2])


def test_decode_kernel_under_scan_walks_the_layers():
    """The kernel inside ``lax.scan`` with the stack in the carry and the
    layer index traced, eight rows (more than its three buffers), twelve
    steps against the recurrence."""
    B, H, dk, dv, L, T = 8, 2, 8, 128, 2, 12
    q, k, v, g, beta, _ = _inputs(B, T, H, dk, dv, 0.95, seed=3)
    live = jnp.asarray([True, True, False, True, True, True, False, True])
    state = jnp.zeros((L, B, H, dk, dv), jnp.float32)

    def step(st, xs):
        def layer(st, l):
            o, st = gd.gdn_decode(*xs, st, l, live, interpret=True)
            return st, o

        st, o = jax.lax.scan(layer, st, jnp.arange(L))
        return st, o[1]

    xs = tuple(jnp.moveaxis(x, 1, 0) for x in (q, k, v, g, beta))
    state, o = jax.jit(lambda st: jax.lax.scan(step, st, xs))(state)
    o_ref, s_ref = gd.gdn_recurrence(
        q, k, v, g, beta, jnp.zeros((B, H, dk, dv), jnp.float32))
    rows = np.asarray(live)
    np.testing.assert_allclose(jnp.moveaxis(o, 0, 1)[rows], o_ref[rows],
                               atol=ATOL)
    np.testing.assert_allclose(state[1][rows], s_ref[rows], atol=ATOL)
    assert not np.asarray(state)[:, ~rows].any()


def test_decode_kernel_refuses_another_layout():
    q, k, v, g, beta, state = _decode_case()
    with pytest.raises(ValueError, match="packed float32 stack"):
        gd.gdn_decode(q, k, v, g, beta, state.astype(jnp.bfloat16), 0,
                      interpret=True)
    with pytest.raises(ValueError, match="packed float32 stack"):
        gd.gdn_decode(q, k, v, g, beta, state[:, :3], 0, interpret=True)


def test_state_layout_packs_heads_to_whole_lanes():
    assert gd.state_group(30, 192) == 2        # the published sizes
    assert gd.state_group(4, 64) == 2 and gd.state_group(8, 32) == 4
    assert gd.state_group(4, 128) == 1 and gd.state_group(4, 256) == 1
    assert gd.state_group(3, 192) == 1         # heads do not divide
    s = jnp.arange(2 * 4 * 3 * 64, dtype=jnp.float32).reshape(2, 4, 3, 64)
    p = gd.pack_state(s)
    assert p.shape == (2, 2, 3, 128)
    # heads 2j and 2j + 1 side by side in tile j
    np.testing.assert_array_equal(p[:, 1, :, :64], s[:, 2])
    np.testing.assert_array_equal(p[:, 1, :, 64:], s[:, 3])
    np.testing.assert_array_equal(gd.unpack_state(p, 4), s)


def test_short_convolution_chunk_step_and_tail():
    rng = np.random.default_rng(0)
    B, S, C, W = 2, 10, 6, 4
    x = jnp.asarray(rng.standard_normal((B, S, C)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((W, C)), jnp.float32)
    zero = jnp.zeros((B, (W - 1) * C), jnp.float32)
    y, tail = gd.conv_chunk(x, zero, w)
    # by hand: w[W - 1] weighs the position itself
    want3 = jax.nn.silu(sum(w[j] * x[:, j] for j in range(W)))
    np.testing.assert_allclose(y[:, 3], want3, atol=1e-6)
    np.testing.assert_allclose(y[:, 0], jax.nn.silu(w[3] * x[:, 0]),
                               atol=1e-6)
    np.testing.assert_array_equal(tail.reshape(B, 3, C), x[:, -3:])
    # a step a position gives the chunk's outputs and its tail
    t, ys = zero, []
    for i in range(S):
        yi, t = gd.conv_step(x[:, i], t, w)
        ys.append(yi)
    np.testing.assert_allclose(jnp.stack(ys, 1), y, atol=1e-6)
    np.testing.assert_array_equal(t, tail)
    # a continuation chunk from the tail; and n_valid keeps padding out
    y2, tail2 = gd.conv_chunk(x[:, 4:], gd.conv_chunk(x[:, :4], zero, w)[1],
                              w)
    np.testing.assert_allclose(y2, y[:, 4:], atol=1e-6)
    _, t_valid = gd.conv_chunk(x, zero, w, n_valid=jnp.asarray([4, 2]))
    np.testing.assert_array_equal(t_valid[0].reshape(3, C), x[0, 1:4])
    np.testing.assert_array_equal(t_valid[1].reshape(3, C)[1:], x[1, :2])
    assert not np.asarray(t_valid[1].reshape(3, C)[0]).any()
