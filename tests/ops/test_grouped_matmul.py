"""Pallas grouped matmul (``ops.grouped_matmul``) vs its jnp oracles.

Kernels run under ``interpret=True`` on CPU (the real lowering is
TPU-only). Routing-level guarantees — the tile-aligned layout reproducing
the slot/one-hot executors' decisions bit-for-bit — are covered by the
``apply_gmm`` executor tests at the bottom; here the kernels themselves
are checked for values and gradients, including the K-chunked dispatch,
the transposed-weights twin, and empty groups (min-one-tile contract).

Tolerances are loose-ish (atol 5e-2 on O(10) magnitudes): XLA:CPU's
oneDNN matmuls use bf16-fastmath paths, so even two jnp lowerings of the
same contraction differ by ~1e-2 relative.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from elephas_tpu.ops import grouped_matmul as G
from elephas_tpu.parallel.expert import MoEFeedForward

ATOL = 5e-2


def _case(M, K, N, E, gmap, seed=0):
    rng = np.random.default_rng(seed)
    lhs = jnp.asarray(rng.standard_normal((M, K)), jnp.float32)
    rhs = jnp.asarray(rng.standard_normal((E, K, N)), jnp.float32)
    return lhs, rhs, jnp.asarray(gmap, jnp.int32)


def test_gmm_forward_matches_reference():
    lhs, rhs, gmap = _case(768, 256, 128, 4, [0, 1, 1, 2, 3, 3])
    out = G.gmm(lhs, rhs, gmap, True)
    ref = G.gmm_reference(lhs, rhs, gmap)
    assert out.shape == ref.shape
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=ATOL)


def test_gmm_t_forward_matches_reference():
    lhs, _, gmap = _case(768, 256, 128, 4, [0, 1, 1, 2, 3, 3])
    rng = np.random.default_rng(1)
    rhs_t = jnp.asarray(rng.standard_normal((4, 128, 256)), jnp.float32)
    out = G.gmm_t(lhs, rhs_t, gmap, True)
    ref = G.gmm_reference(lhs, rhs_t, gmap, transpose_rhs=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=ATOL)


def test_gmm_gradients_match_reference():
    lhs, rhs, gmap = _case(768, 256, 128, 4, [0, 1, 1, 2, 3, 3])

    def f(l, r):
        return jnp.sum(jnp.sin(G.gmm(l, r, gmap, True)))

    def fr(l, r):
        return jnp.sum(jnp.sin(G.gmm_reference(l, r, gmap)))

    gl, gr = jax.jit(jax.grad(f, (0, 1)))(lhs, rhs)
    gl_r, gr_r = jax.jit(jax.grad(fr, (0, 1)))(lhs, rhs)
    np.testing.assert_allclose(np.asarray(gl), np.asarray(gl_r), atol=ATOL)
    np.testing.assert_allclose(np.asarray(gr), np.asarray(gr_r), atol=ATOL)


def test_tgmm_matches_f64_oracle_and_zeroes_empty_groups():
    # group 2 is EMPTY but still owns one (all-sentinel) tile — the
    # min-one-tile contract the executor's layout guarantees; its weight
    # grad must come out exactly zero, not garbage.
    M, K, N, E, tm = 768, 256, 128, 4, 128
    rng = np.random.default_rng(2)
    lhs = np.zeros((M, K), np.float32)
    g = np.zeros((M, N), np.float32)
    # rows: e0 gets 192 (1.5 tiles -> pad), e1 gets 256, e3 gets 128
    fill = rng.standard_normal
    lhs[:192], g[:192] = fill((192, K)), fill((192, N))
    lhs[256:512], g[256:512] = fill((256, K)), fill((256, N))
    lhs[640:768], g[640:768] = fill((128, K)), fill((128, N))
    gmap = jnp.asarray([0, 0, 1, 1, 2, 3], jnp.int32)
    out = np.asarray(G.tgmm(jnp.asarray(lhs), jnp.asarray(g), gmap, E,
                            jnp.float32, True))
    seg = {0: (0, 256), 1: (256, 512), 3: (640, 768)}
    for e in range(E):
        if e in seg:
            a, b = seg[e]
            want = lhs[a:b].astype(np.float64).T @ g[a:b].astype(np.float64)
        else:
            want = np.zeros((K, N))
        np.testing.assert_allclose(out[e], want, atol=ATOL)


def test_k_chunked_paths_match(monkeypatch):
    monkeypatch.setattr(G, "_K_CHUNK", 128)  # force chunking at K=512
    lhs, rhs, gmap = _case(512, 512, 128, 4, [0, 1, 2, 3], seed=3)

    def f(l, r):
        return jnp.sum(jnp.sin(G.gmm(l, r, gmap, True)))

    def fr(l, r):
        return jnp.sum(jnp.sin(G.gmm_reference(l, r, gmap)))

    gl, gr = jax.jit(jax.grad(f, (0, 1)))(lhs, rhs)
    gl_r, gr_r = jax.jit(jax.grad(fr, (0, 1)))(lhs, rhs)
    np.testing.assert_allclose(np.asarray(gl), np.asarray(gl_r), atol=ATOL)
    np.testing.assert_allclose(np.asarray(gr), np.asarray(gr_r), atol=ATOL)


def test_tileable_gates():
    assert G.tileable(1024, 256, 128, 128)
    assert not G.tileable(1000, 256, 128, 128)   # rows not tile-aligned
    assert not G.tileable(1024, 192, 128, 128)   # K not lane-tileable
    assert not G.tileable(1024, 256, 100, 128)   # N not lane-tileable
    assert not G.tileable(1024, 2304, 128, 128)  # K > 2 chunks, not chunkable


# -- the MoE executor built on these kernels ---------------------------------


def _moe(act="swiglu", bias=False, cf=1.25, E=4, k=2):
    moe = MoEFeedForward(128, 128, E, k=k, capacity_factor=cf,
                         activation=act, bias=bias)
    params = {k: jnp.asarray(v) for k, v in moe.init(0).items()}
    return moe, params


@pytest.mark.parametrize("act,bias,cf,k", [
    ("swiglu", False, 1.25, 2),   # Mixtral expert shape
    ("relu", True, 0.5, 2),       # heavy drops: capacity keeps must agree
    ("gelu", False, 2.0, 2),
    ("gelu", True, 1.25, 1),      # one expert a token
    ("relu", False, 1.0, 3),      # three a token, no biases
])
def test_apply_gmm_matches_oracle(act, bias, cf, k):
    moe, params = _moe(act, bias, cf, k=k)
    x = jnp.asarray(np.random.default_rng(4).standard_normal((256, 128)),
                    jnp.float32)
    y, aux = jax.jit(moe.apply_gmm)(params, x)
    yr, auxr = jax.jit(moe.apply_reference)(params, x)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr), atol=1e-4)
    assert abs(float(aux) - float(auxr)) < 1e-5


def test_apply_gmm_gradients_match_oracle():
    moe, params = _moe()
    x = jnp.asarray(np.random.default_rng(5).standard_normal((256, 128)),
                    jnp.float32)

    def loss(p, fn):
        yy, aa = fn(p, x)
        return jnp.sum(yy ** 2) + aa

    g1 = jax.jit(jax.grad(lambda p: loss(p, moe.apply_gmm)))(params)
    g2 = jax.jit(jax.grad(lambda p: loss(p, moe.apply_reference)))(params)
    for k in g1:
        np.testing.assert_allclose(np.asarray(g1[k]), np.asarray(g2[k]),
                                   atol=1e-2)


def test_apply_gmm_ep_groups_match_oracle():
    moe, params = _moe(cf=1.0)
    x = jnp.asarray(np.random.default_rng(6).standard_normal((256, 128)),
                    jnp.float32)
    y, aux = jax.jit(lambda p, c: moe.apply_gmm(p, c, ep=4))(params, x)
    yr, auxr = jax.jit(lambda p, c: moe.apply_reference(p, c, ep=4))(params, x)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr), atol=1e-4)
    assert abs(float(aux) - float(auxr)) < 1e-5


def test_apply_gmm_kernel_path_interpret():
    # force the Pallas kernels (interpret mode) end to end, with a router
    # biased so one expert goes hungry (empty-group tiles exercised)
    moe, params = _moe()
    params = dict(params)
    wg = np.zeros((128, 4), np.float32)
    wg[:, 3] = -10.0  # expert 3 never chosen
    params["wg"] = jnp.asarray(wg)
    x = jnp.asarray(np.random.default_rng(7).standard_normal((256, 128)),
                    jnp.float32)
    y, aux = jax.jit(lambda p, c: moe.apply_gmm(p, c, interpret=True))(
        params, x)
    yr, auxr = jax.jit(moe.apply_reference)(params, x)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr), atol=ATOL)
    g = jax.jit(jax.grad(
        lambda p: jnp.sum(moe.apply_gmm(p, x, interpret=True)[0] ** 2)
    ))(params)
    for k, v in g.items():
        assert np.isfinite(np.asarray(v)).all(), k


def test_apply_gmm_rejects_expert_choice():
    moe = MoEFeedForward(128, 128, 4, k=2, routing="expert_choice")
    params = {k: jnp.asarray(v) for k, v in moe.init(0).items()}
    x = jnp.zeros((64, 128), jnp.float32)
    with pytest.raises(ValueError, match="token_choice"):
        moe.apply_gmm(params, x)


@pytest.mark.parametrize("n,E", [(100, 4), (100, 8), (96, 3)])
def test_apply_gmm_unaligned_token_counts(n, E):
    """k·N not a multiple of the row tile: the layout buffer must round
    up to tile alignment or the tile→expert geometry shears (regression:
    reshape crash at E=4, silently wrong output at E=8)."""
    moe = MoEFeedForward(128, 128, E, k=2, capacity_factor=1.25,
                         activation="swiglu", bias=False)
    params = {k: jnp.asarray(v) for k, v in moe.init(0).items()}
    x = jnp.asarray(np.random.default_rng(8).standard_normal((n, 128)),
                    jnp.float32)
    y, aux = jax.jit(moe.apply_gmm)(params, x)
    yr, auxr = jax.jit(moe.apply_reference)(params, x)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr), atol=1e-4)
    assert abs(float(aux) - float(auxr)) < 1e-5
