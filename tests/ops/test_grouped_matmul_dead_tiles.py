"""``ops.grouped_matmul``: dead tiles (``gmap == E``) are skipped and fetch
nothing, and a layer of a STACK of group weights is read in place. The
Pallas kernels interpreted on the CPU against the jax.numpy reference."""

import numpy as np
import pytest

import jax.numpy as jnp

from elephas_tpu.ops import grouped_matmul as G

TM, K, N, E = 8, 128, 256, 3


def _case(seed=0, tiles=(0, 0, 1, 2, 3, 3)):
    rng = np.random.default_rng(seed)
    gmap = jnp.asarray(tiles, jnp.int32)          # 3 == E: dead
    lhs = jnp.asarray(rng.standard_normal((len(tiles) * TM, K)), jnp.float32)
    rhs = jnp.asarray(rng.standard_normal((E, K, N)), jnp.float32)
    return lhs, rhs, gmap


def _live_rows(gmap):
    return np.repeat(np.asarray(gmap) < E, TM)


@pytest.mark.parametrize("deep", [False, True])
def test_live_tiles_match_the_reference_and_dead_tiles_are_left(deep):
    lhs, rhs, gmap = _case()
    if deep:                                      # the K-sliced kernel
        lhs = jnp.tile(lhs, (1, 16))              # K = 2048 > _K_CHUNK
        rhs = jnp.tile(rhs, (1, 16, 1))
    want = np.asarray(G.gmm_reference(lhs, rhs, gmap))
    got = np.asarray(G.gmm(lhs, rhs, gmap, True))
    live = _live_rows(gmap)
    np.testing.assert_allclose(got[live], want[live], rtol=1e-4, atol=1e-3)
    # the reference multiplies a dead tile by the last group's weights
    # (its rows are never read); it does not fail on the index
    assert np.isfinite(want).all()


def test_all_tiles_live_is_the_kernel_of_before():
    lhs, rhs, gmap = _case(tiles=(0, 1, 1, 2))
    np.testing.assert_allclose(
        np.asarray(G.gmm(lhs, rhs, gmap, True)),
        np.asarray(G.gmm_reference(lhs, rhs, gmap)), rtol=1e-4, atol=1e-3)
    # ...and its transposed twin (the hand-written backward's dx)
    rhs_t = jnp.swapaxes(rhs, 1, 2)
    np.testing.assert_allclose(
        np.asarray(G.gmm_t(lhs, rhs_t, gmap, True)),
        np.asarray(G.gmm_reference(lhs, rhs_t, gmap, transpose_rhs=True)),
        rtol=1e-4, atol=1e-3)


@pytest.mark.parametrize("layer", [0, 2])
def test_a_layer_of_a_stack_is_read_in_place(layer):
    lhs, rhs, gmap = _case(seed=4)
    rng = np.random.default_rng(7)
    stack = jnp.stack([jnp.asarray(rng.standard_normal(rhs.shape),
                                   jnp.float32) for _ in range(3)])
    got = np.asarray(G.gmm_stacked(lhs, stack, jnp.asarray(layer), gmap,
                                   True))
    want = np.asarray(G.gmm_reference(lhs, stack[layer], gmap))
    live = _live_rows(gmap)
    np.testing.assert_allclose(got[live], want[live], rtol=1e-4, atol=1e-3)
    other = np.asarray(G.gmm_reference(lhs, stack[1], gmap))
    assert np.abs(got[live] - other[live]).max() > 1.0
